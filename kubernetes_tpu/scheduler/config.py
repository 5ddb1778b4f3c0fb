"""Scheduler configuration — Policy and component config.

Ref: pkg/scheduler/api (schedulerapi.Policy — predicates, priorities with
weights, extenders, hardPodAffinitySymmetricWeight) and
pkg/scheduler/apis/config (KubeSchedulerConfiguration: schedulerName,
algorithmSource, leader election, healthz/metrics binding). Both load from
JSON files or dicts; precedence flags > config file > defaults, applied by
the cmd entry (cmd/kube_scheduler.py).

Capability note (documented deviation): the batch kernel always evaluates
the FULL default predicate set — a Policy listing a predicate subset is
validated against the known names but does not disable the rest; the
result is a conservative superset of the requested filtering. Priority
weights take full effect everywhere: host-side static priorities through
ScoreCompiler, and the two device-resident resource priorities
(LeastRequested/BalancedAllocation) through the batch's resource_weights
vector. Extenders take full effect.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .extender import ExtenderConfig, HTTPExtender
from .predicates import DEFAULT_PREDICATES, ORDERING
from .priorities import DEFAULT_PRIORITY_WEIGHTS, HARD_POD_AFFINITY_WEIGHT

#: every predicate name a Policy may reference (registered + factory-made)
KNOWN_PREDICATES = set(ORDERING) | set(DEFAULT_PREDICATES) | {
    "GeneralPredicates", "CheckNodeUnschedulable", "NoVolumeZoneConflict",
    "CheckVolumeBinding", "MaxEBSVolumeCount", "MaxGCEPDVolumeCount",
    "MaxAzureDiskVolumeCount", "MaxCSIVolumeCountPred", "MatchInterPodAffinity"}

KNOWN_PRIORITIES = set(DEFAULT_PRIORITY_WEIGHTS)


@dataclass
class Policy:
    """Ref: schedulerapi.Policy (pkg/scheduler/api/types.go)."""
    predicates: Optional[List[str]] = None
    priorities: Optional[Dict[str, int]] = None   # name -> weight
    extenders: List[ExtenderConfig] = field(default_factory=list)
    hard_pod_affinity_symmetric_weight: int = HARD_POD_AFFINITY_WEIGHT

    @staticmethod
    def from_dict(data: dict) -> "Policy":
        preds = None
        if "predicates" in data:
            preds = [p["name"] for p in data["predicates"]]
            unknown = [n for n in preds if n not in KNOWN_PREDICATES]
            if unknown:
                raise ValueError(f"unknown predicates in policy: {unknown}")
        prios = None
        if "priorities" in data:
            prios = {p["name"]: int(p.get("weight", 1))
                     for p in data["priorities"]}
            unknown = [n for n in prios if n not in KNOWN_PRIORITIES]
            if unknown:
                raise ValueError(f"unknown priorities in policy: {unknown}")
        extenders = []
        for e in data.get("extenders", []):
            extenders.append(ExtenderConfig(
                url_prefix=e["urlPrefix"],
                filter_verb=e.get("filterVerb", ""),
                prioritize_verb=e.get("prioritizeVerb", ""),
                bind_verb=e.get("bindVerb", ""),
                weight=int(e.get("weight", 1)),
                node_cache_capable=bool(e.get("nodeCacheCapable", False)),
                ignorable=bool(e.get("ignorable", False))))
        return Policy(
            predicates=preds, priorities=prios, extenders=extenders,
            hard_pod_affinity_symmetric_weight=int(
                data.get("hardPodAffinitySymmetricWeight",
                         HARD_POD_AFFINITY_WEIGHT)))

    @staticmethod
    def from_file(path: str) -> "Policy":
        with open(path) as f:
            return Policy.from_dict(json.load(f))

    def weights(self) -> Dict[str, int]:
        """Effective priority weights: the policy's set, or the defaults."""
        if self.priorities is None:
            return dict(DEFAULT_PRIORITY_WEIGHTS)
        w = {name: 0 for name in DEFAULT_PRIORITY_WEIGHTS}
        w.update(self.priorities)
        return w


@dataclass
class LeaderElectionConfig:
    leader_elect: bool = False
    lease_duration_seconds: float = 15.0
    renew_deadline_seconds: float = 10.0
    retry_period_seconds: float = 2.0
    resource_namespace: str = "kube-system"
    resource_name: str = "kube-scheduler"


@dataclass
class KubeSchedulerConfiguration:
    """Ref: pkg/scheduler/apis/config KubeSchedulerConfiguration."""
    scheduler_name: str = "default-scheduler"
    policy: Optional[Policy] = None
    leader_election: LeaderElectionConfig = field(
        default_factory=LeaderElectionConfig)
    healthz_bind_port: int = 0           # 0 = disabled
    disable_preemption: bool = False
    batch_size: int = 1024               # batch extension (no ref analog)
    # accepted for compatibility; the batch kernel evaluates every node,
    # so sampling is unnecessary (generic_scheduler.go:434-453 exists to
    # cut serial per-pod cost the batch design does not pay)
    percentage_of_nodes_to_score: int = 50

    @staticmethod
    def from_dict(data: dict) -> "KubeSchedulerConfiguration":
        cfg = KubeSchedulerConfiguration()
        cfg.scheduler_name = data.get("schedulerName", cfg.scheduler_name)
        cfg.disable_preemption = data.get("disablePreemption",
                                          cfg.disable_preemption)
        cfg.batch_size = int(data.get("batchSize", cfg.batch_size))
        cfg.healthz_bind_port = int(data.get("healthzBindPort", 0))
        cfg.percentage_of_nodes_to_score = int(
            data.get("percentageOfNodesToScore",
                     cfg.percentage_of_nodes_to_score))
        src = data.get("algorithmSource", {})
        pol = src.get("policy")
        if pol:
            if "file" in pol:
                cfg.policy = Policy.from_file(pol["file"]["path"])
            elif "inline" in pol:
                cfg.policy = Policy.from_dict(pol["inline"])
        le = data.get("leaderElection", {})
        if le:
            cfg.leader_election = LeaderElectionConfig(
                leader_elect=bool(le.get("leaderElect", False)),
                lease_duration_seconds=float(le.get("leaseDuration", 15.0)),
                renew_deadline_seconds=float(le.get("renewDeadline", 10.0)),
                retry_period_seconds=float(le.get("retryPeriod", 2.0)),
                resource_namespace=le.get("resourceNamespace", "kube-system"),
                resource_name=le.get("resourceName", "kube-scheduler"))
        return cfg

    @staticmethod
    def from_file(path: str) -> "KubeSchedulerConfiguration":
        with open(path) as f:
            return KubeSchedulerConfiguration.from_dict(json.load(f))


def build_scheduler(client, cfg: KubeSchedulerConfiguration):
    """Configurator: config -> a wired Scheduler (ref: factory.go
    CreateFromConfig/CreateFromProvider)."""
    from ..observability import SpanTracer
    from .scheduler import Scheduler
    policy = cfg.policy or Policy()
    extenders = [HTTPExtender(e) for e in policy.extenders]
    sched = Scheduler(
        client, batch_size=cfg.batch_size,
        scheduler_name=cfg.scheduler_name,
        disable_preemption=cfg.disable_preemption,
        extenders=extenders,
        # the served process exposes no flight recorder: its stages are
        # on /metrics and on the profiler's trace, and the per-pod
        # milestones would be sampled into a ring nobody can read
        tracer=SpanTracer(enabled=False))
    # rebuild the algorithm's scorer with policy weights
    if policy.priorities is not None or \
            policy.hard_pod_affinity_symmetric_weight != HARD_POD_AFFINITY_WEIGHT:
        sched.algorithm.scorer.set_weights(
            policy.weights(), policy.hard_pod_affinity_symmetric_weight)
    return sched
