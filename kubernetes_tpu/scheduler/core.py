"""Batch scheduling core — the genericScheduler equivalent.

Ref: pkg/scheduler/core/generic_scheduler.go. Where the reference's
`Schedule` handles ONE pod (snapshot -> findNodesThatFit -> PrioritizeNodes ->
selectHost, :184-254), `BatchScheduler.schedule` handles a whole batch:

    cache.update_snapshot      O(delta) generation scan   (cache.go:210-246)
    mirror.apply(dirty)        O(delta) rows to HBM
    PodBatchTensors            term-compile the pod axis
    kernels.schedule_batch     serial-semantics assign scan, on device
    -> [(pod, node_name | None)]

No node sampling: the reference trades decision quality for speed via
numFeasibleNodesToFind (50%, :434-453); the batch kernel evaluates every node
for every pod in one shot, so sampling is unnecessary.

MatchInterPodAffinity runs through the incremental topology index
(topology.py — the M3 sparse topologyPairsMaps analog): per batch, every
constraint template's node mask is one vectorized evaluation over [T, N]
term-presence matrices (device matmuls for large T), fed into the kernel's
unique-mask rows. Volume predicates (NoDiskConflict, Max*VolumeCount,
zone/binding) still run per-node on the host, only for pods that carry
volumes. In-batch interactions are validated post-kernel by the repair
pass: ports/disk/attach against overlay NodeInfos, (anti-)affinity against
a BatchOverlay of winner term counts; a conflict demotes the pod to retry
(the next cycle sees the winner via assume).

Failure diagnosis (`explain`) reruns the python predicates to produce the
reference's per-node FitError reasons (:598-664) — off the hot path, only for
pods that failed.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..api import helpers
from ..api.core import Pod
from ..api.serde import deepcopy_obj
from ..observability.tracer import NULL_TRACER
from .cache import Cache, Snapshot
from .nodeinfo import NodeInfo, pod_has_affinity_constraints
from . import predicates as preds
from . import priorities as prios
from . import sharding as sharding_mod
from .tensorize import PodBatchTensors, TensorMirror, TermCompiler
from .topology import AffinityProfile, BatchOverlay, TopologyIndex


@dataclass
class FitError(Exception):
    """Ref: core.FitError — why a pod fit nowhere. total_nodes is the
    cluster size; not_examined > 0 means the diagnosis was capped."""
    pod: Optional[Pod] = None
    failed_predicates: Dict[str, List[str]] = field(default_factory=dict)
    total_nodes: int = 0
    not_examined: int = 0

    def error(self) -> str:
        # aggregate like the reference's FitError.Error()
        counts: Dict[str, int] = {}
        for reasons in self.failed_predicates.values():
            for r in reasons:
                counts[r] = counts.get(r, 0) + 1
        parts = [f"{n} {r}" for r, n in sorted(counts.items())]
        total = self.total_nodes or len(self.failed_predicates)
        msg = "0/%d nodes are available: %s." % (total, ", ".join(parts))
        if self.not_examined:
            msg += (f" ({self.not_examined} node(s) not examined: "
                    f"diagnosis capped)")
        return msg


@dataclass
class ScheduleResult:
    pod: Pod
    node_name: Optional[str]          # None -> unschedulable (or retry)
    score: float = 0.0
    retry: bool = False               # lost an in-batch conflict; requeue
    reassigned: bool = False          # repair moved it off the kernel's pick


@dataclass
class PendingBatch:
    """A dispatched-but-unfetched batch (schedule_launch output): the device
    scan runs while the host commits the previous batch."""
    pods: List[Pod]
    profiles: Dict[int, AffinityProfile]
    batch: PodBatchTensors
    packed: object                    # [2, P] device handle (assign+scores)
    new_usage: dict                   # device usage after this batch
    residual_free: bool               # no repair possible -> usage chainable
    usage_epoch: int = 0              # mirror.usage_epoch at launch
    #: residual was affinity-only (no volumes/extenders/static scores):
    #: the NEXT batch may still chain usage on device — its stale affinity
    #: mask is repaired via stale_winners (below)
    affinity_chainable: bool = False
    #: True when this batch launched chained on a predecessor whose results
    #: were not yet committed; the drain fills stale_winners/phantom from
    #: that predecessor's commit before this batch is finished
    chained: bool = False
    #: the predecessor batch's committed (pod, node) winners — absent from
    #: this batch's snapshot/index/mask; repair validates against them via
    #: the BatchOverlay exactly like same-batch winners
    stale_winners: Optional[List[Tuple[Pod, str]]] = None
    #: the predecessor lost winners after this batch's usage was chained
    #: (repair demotions / commit drops): chained usage over-states, so
    #: kernel-unassigned pods here must RETRY, not park as unschedulable
    phantom: bool = False
    #: gang placement units [(pod indices, topology key, is_gang)] when
    #: this batch routed through the all-or-nothing kernel; finish uses
    #: them to demote whole gangs when repair invalidates any member
    gang_units: Optional[list] = None
    #: True when the in-scan topology tables (or their provable inertness)
    #: cover EVERY in-batch (anti-)affinity interaction AND the batch
    #: carries no ports/volumes/extenders: with no stale winners, the
    #: repair pass has nothing left to validate and is skipped outright
    inscan_cover: bool = False
    #: True when this batch ran the shard_map kernel (per-shard
    #: filter+score, cross-shard argmax) — schedule_finish attributes its
    #: fetch wait to scheduler_shard_sync_seconds
    sharded: bool = False
    #: structural signatures of the in-scan spread / soft tables (None
    #: when absent): a successor may chain THROUGH this batch's carried
    #: counts only when its own tables resolve to the same structure —
    #: see schedule_launch's carry-chaining gate
    spread_sig: Optional[Tuple] = None
    soft_sig: Optional[Tuple] = None


class _RepairReassigner:
    """Host-side serial re-solve for pods the repair pass would demote.

    The serial reference never demotes: pod i simply picks its best node
    GIVEN pods 1..i-1 (scheduler.go:514 assume-between-iterations). The
    kernel approximates that with a frozen constraint mask; when repair
    finds pod i's kernel pick invalidated by an earlier winner, this class
    reproduces the kernel's exact scoring on host numpy — running usage
    including every surviving earlier winner, the same resource priorities
    (kernels/batch.py _least_requested/_balanced_allocation, f32 floors),
    static rows, and (row, seq) tie-break hash — and walks candidates in
    that order so the repair can place the pod where the serial order
    would have, instead of burning a retry round.

    Usage base: mirror host truth + stale (chained predecessor) winners +
    surviving winners of THIS batch. In the mid-drain chained case the
    predecessor's winners may already be folded into host truth, making
    the base conservatively overstated for reassigned pods — feasibility
    never overpacks, and the single-batch (parity fixture) case is exact.
    """

    MAX_CANDIDATES = 64

    def __init__(self, mirror: TensorMirror, batch: PodBatchTensors,
                 stale_winners):
        self.mirror = mirror
        self.batch = batch
        self._stale = list(stale_winners or [])
        self._log: List[Tuple[int, str]] = []   # winners before materialize
        self._used = None
        self.reassigned_any = False

    def add_winner(self, i: int, node_name: str) -> None:
        if self._used is None:
            self._log.append((i, node_name))
        else:
            self._apply(i, node_name)

    def _apply(self, i: int, node_name: str) -> None:
        row = self.mirror.row_of.get(node_name)
        if row is None:
            return
        self._used[row] += self.batch.req[i]
        self._nz[row] += self.batch.nonzero_req[i]
        self._cnt[row] += 1.0

    def _materialize(self) -> None:
        from .nodeinfo import pod_resource, pod_resource_nonzero
        from .tensorize import COL_CPU, COL_EPH, COL_MEM, _f32_ceil
        t = self.mirror.t
        self._used = t.used.copy()
        self._nz = t.nonzero_used.copy()
        self._cnt = t.pod_count.copy()
        self._rows = np.arange(t.capacity, dtype=np.int64)
        for w_pod, w_node in self._stale:
            row = self.mirror.row_of.get(w_node)
            if row is None:
                continue
            r = pod_resource(w_pod)
            self._used[row, COL_CPU] += _f32_ceil(r.milli_cpu)
            self._used[row, COL_MEM] += _f32_ceil(r.memory)
            self._used[row, COL_EPH] += _f32_ceil(r.ephemeral_storage)
            for rname, v in r.scalar_resources.items():
                self._used[row, self.mirror.vocab.col(rname)] += _f32_ceil(v)
            nz_cpu, nz_mem = pod_resource_nonzero(w_pod)
            self._nz[row, 0] += nz_cpu
            self._nz[row, 1] += nz_mem
            self._cnt[row] += 1.0
        for i, node_name in self._log:
            self._apply(i, node_name)
        self._log = []

    def candidates(self, i: int):
        """Yield node names in the kernel's (score - tie penalty) order,
        feasible against the running usage; capped."""
        if self._used is None:
            self._materialize()
        from .tensorize import COL_CPU, COL_MEM
        t = self.mirror.t
        b = self.batch
        req = b.req[i]
        fits = b.unique_masks[b.mask_idx[i]] & t.node_ok & t.valid
        if b.mem_pressure_blocked[i]:
            fits = fits & ~t.mem_pressure
        fits = fits & ((self._used + req[None, :]) <= t.alloc).all(axis=1)
        fits = fits & (self._cnt + 1.0 <= t.max_pods)
        if not fits.any():
            return
        cap_cpu = t.alloc[:, COL_CPU]
        cap_mem = t.alloc[:, COL_MEM]
        nzr = b.nonzero_req[i]
        req_cpu = self._nz[:, 0] + nzr[0]
        req_mem = self._nz[:, 1] + nzr[1]
        safe_cpu = np.maximum(cap_cpu, 1.0)
        safe_mem = np.maximum(cap_mem, 1.0)
        lr_c = np.where((cap_cpu > 0) & (req_cpu <= cap_cpu),
                        np.floor((cap_cpu - req_cpu) * 10.0 / safe_cpu), 0.0)
        lr_m = np.where((cap_mem > 0) & (req_mem <= cap_mem),
                        np.floor((cap_mem - req_mem) * 10.0 / safe_mem), 0.0)
        lr = np.floor((lr_c + lr_m) / 2.0)
        cpu_frac = np.where(cap_cpu > 0, req_cpu / safe_cpu, 1.0)
        mem_frac = np.where(cap_mem > 0, req_mem / safe_mem, 1.0)
        ba = np.floor((1.0 - np.abs(cpu_frac - mem_frac)) * 10.0)
        ba = np.where((cpu_frac >= 1.0) | (mem_frac >= 1.0), 0.0, ba)
        rw = b.resource_weights
        score = rw[0] * lr + rw[1] * ba + b.unique_scores[b.score_idx[i]]
        # bit-identical tie-break to the kernel: low 16 bits are invariant
        # under int32 wraparound, so int64 + mask matches
        h = ((self._rows * -1640531527 + int(b.seq[i]) * 40503)
             & 0xFFFF).astype(np.float32)
        ranked = np.where(fits, score - h * np.float64(0.5 / 65536.0),
                          -np.inf)
        order = np.argsort(-ranked, kind="stable")
        for row in order[:self.MAX_CANDIDATES]:
            row = int(row)
            if not fits[row]:
                return
            name = self.mirror.name_of.get(row)
            if name is not None:
                yield name


def _required_pod_affinity(pod: Pod):
    """The pod's required podAffinity terms (empty where it has none)."""
    aff = pod.spec.affinity
    if aff is None or aff.pod_affinity is None:
        return ()
    return aff.pod_affinity \
        .required_during_scheduling_ignored_during_execution or ()


def _pod_has_conflict_volumes(pod: Pod) -> bool:
    for v in pod.spec.volumes:
        if v.gce_persistent_disk or v.aws_elastic_block_store or v.rbd or v.iscsi:
            return True
    return False


def _pod_has_attach_volumes(pod: Pod) -> bool:
    """Direct attach-limited sources (CSI reaches pods only via PVCs, which
    _pod_has_pvc covers)."""
    for v in pod.spec.volumes:
        if v.gce_persistent_disk or v.aws_elastic_block_store or v.azure_disk:
            return True
    return False


def _pod_has_pvc(pod: Pod) -> bool:
    return any(v.persistent_volume_claim for v in pod.spec.volumes)


class BatchScheduler:
    def __init__(self, cache: Cache, listers=None,
                 weights: Optional[Dict[str, int]] = None,
                 hard_pod_affinity_weight: Optional[int] = None,
                 volume_binder=None,
                 pvc_lister=None, pv_lister=None,
                 nominated=None, pdb_lister=None, extenders=None,
                 mesh=None):
        from . import priorities as prios_mod
        from .queue import NominatedPodMap
        from .scorer import ScoreCompiler
        from .volumebinder import FakeVolumeBinder
        #: out-of-process extenders (ref: core/extender.go); filter joins
        #: the residual host path, prioritize merges into static scores
        self.extenders = list(extenders or [])
        #: shared with the SchedulingQueue; feeds the kernel's reservation
        #: tensors and preemption's nominated-to-clear list
        self.nominated = nominated if nominated is not None else NominatedPodMap()
        self.pdb_lister = pdb_lister or (lambda: [])
        self._nom_key = None
        self._nom_dev = None
        self._nom_rows_by_key: Dict[str, int] = {}
        self.volume_binder = volume_binder or FakeVolumeBinder()
        self.pvc_lister = pvc_lister      # (namespace, name) -> PVC | None
        self.pv_lister = pv_lister        # (name) -> PV | None
        self._zone_conflict = preds.no_volume_zone_conflict_factory(
            pvc_lister or (lambda ns, name: None),
            pv_lister or (lambda name: None))
        # Max{EBS,GCEPD,AzureDisk,CSI}VolumeCount — default-set members
        # (defaults.go:40-56), host-evaluated on the residual path
        self._volume_count_preds = preds.default_max_volume_count_predicates(
            pvc_lister, pv_lister)
        self.cache = cache
        self.snapshot = Snapshot()
        self.mirror = TensorMirror(mesh=mesh)
        self.terms = TermCompiler(self.mirror)
        #: the M3 incremental topologyPairsMaps analog (topology.py)
        self.topology = TopologyIndex(self.mirror)
        self.scorer = ScoreCompiler(
            self.mirror, self.terms, listers=listers, weights=weights,
            hard_pod_affinity_weight=(
                hard_pod_affinity_weight if hard_pod_affinity_weight is not None
                else prios_mod.HARD_POD_AFFINITY_WEIGHT),
            topology=self.topology)
        self._seq_base = 0  # selectHost round-robin state across batches
        # True while host-computed static scores contribute (chain pre-check)
        self._static_likely = False
        #: gang.GangManager, installed by the scheduler shell; batches
        #: carrying PodGroup members route through the all-or-nothing
        #: kernel (kernels/gang.py) instead of schedule_batch
        self.gang = None
        #: tenancy.DRFAccount, installed by the scheduler shell: the
        #: preemption kernels fold its over-share ranks into the victim
        #: band sort so over-share tenants' pods price cheaper (None, or
        #: KTPU_DRF=0, keeps tenant-blind pricing)
        self.drf = None
        #: test controls, each the reference path a tier-1 test compares
        #: the served one against; no entry point sets them. False turns
        #: off the epoch-keyed term-table and profile caches
        #: (tests/test_topo_cache.py)
        self.topo_table_cache = True
        #: False pins non-gang batches to the classic per-pod kernel
        #: (tests/test_class_fastpath.py, test_chip_compile.py)
        self.class_scan = True
        #: False pins preemption to the serial per-node victim search of
        #: preemption.py (tests/test_preempt.py)
        self.preempt_kernel = True
        #: (node, generation, prio, ...) -> victim units: amortizes the
        #: preemption tensorize across a storm (kernels/preempt.py)
        self._preempt_unit_cache: Dict[Tuple, list] = {}
        #: launches that actually chained on a predecessor's device usage
        #: (tests pin that spread/soft batches keep chaining)
        self.chained_launches = 0
        #: residual-sig -> (profile_epoch, AffinityProfile): template
        #: profile resolution survives across batches until a profile-
        #: relevant topology change (new term, zero-crossing count)
        self._profile_cache: Dict[Tuple, Tuple[int, AffinityProfile]] = {}
        #: scheduler.SchedulerMetrics, installed by the shell (None in
        #: bare-algorithm tests); used for in-scan fallback counters
        self.sched_metrics = None
        #: observability.SpanTracer, installed by the shell: the device
        #: path's stages (self._stage) ride the same histogram family,
        #: trace and flight recorder as the shell's commit/bind stages
        self.tracer = NULL_TRACER
        self._fallback_streak: Dict[str, int] = {}
        #: an in-scan fallback was counted since the last launch (some
        #: are counted before it, when the shell sizes the batch)
        self._batch_fell_back = False
        #: (pod-list, plan) from the most recent _soft_plan: the drain's
        #: soft_batch_limit and the launch's _assign_soft_terms see the
        #: SAME list object when the batch wasn't truncated, so the O(P)
        #: channel-planning pass runs once per batch, not twice
        self._soft_plan_memo: Optional[Tuple[List[Pod], Optional[dict]]] = \
            None
        #: profile-cache effectiveness (term-table cache counters live
        #: on the TopologyIndex); the stages' times are on
        #: scheduler_scheduling_duration_seconds{operation}
        self.phase_stats = {"profile_builds": 0, "profile_hits": 0}

    def _stage(self, name: str, **attrs):
        """The timer of one boundary of a batch: SchedulerMetrics.stage
        under a shell, the trace annotation and the flight recorder's
        span alone for a bare algorithm."""
        if self.sched_metrics is not None:
            return self.sched_metrics.stage(self.tracer, name, **attrs)
        return self.tracer.stage(name, trace="sched." + name, **attrs)

    def refresh(self) -> None:
        dirty = self.cache.update_snapshot(self.snapshot)
        self.mirror.apply(self.snapshot, dirty)
        self._topology_apply(dirty)
        if dirty:
            # precise score gating: required-anti-only clusters never
            # produce an inter-pod priority contribution
            self.scorer.set_cluster_has_affinity_pods(
                self.topology.has_score_carriers())

    def _topology_apply(self, dirty) -> None:
        """TopologyIndex.apply, and SelectorSpread's SpreadIndex.apply
        beside it, under their own part of `refresh`. A topology index
        that no (anti-)affinity carrier or term has switched on yet only
        scans the dirty nodes for one; that, and the one pass over the
        whole snapshot that switches it on, stay in `refresh`. A spread
        index that no spread group has switched on does nothing."""
        spread = self.scorer.spread_index
        if not (self.topology.active or spread.active):
            self.topology.apply(self.snapshot, dirty)
            return
        with self._stage("topology_apply", nodes=len(dirty)):
            self.topology.apply(self.snapshot, dirty)
            spread.apply(self.snapshot, dirty)

    # ------------------------------------------------------- residual host path

    def _needs_residual(self, pod: Pod) -> bool:
        """MatchInterPodAffinity / NoDiskConflict / volume predicates need
        an extra mask row (extender filters are handled separately so they
        don't drag every pod through the template path). Unconstrained pods
        are masked only when some existing pod carries REQUIRED
        anti-affinity — the one carried constraint that can exclude them
        (preferred terms only score; carried required affinity only
        credits)."""
        return (pod_has_affinity_constraints(pod)
                or self.topology.has_required_anti_carriers()
                or _pod_has_conflict_volumes(pod) or _pod_has_pvc(pod)
                or _pod_has_attach_volumes(pod))

    def reads_host_placements(self, pods: List[Pod]) -> bool:
        """True when launching this batch builds host-side mask rows from
        where earlier pods were placed (the residual predicates above) —
        the pipelined drain settles its commit thread first, so the rows
        never depend on how many assumes had landed."""
        if self.topology.has_required_anti_carriers():
            return True
        # a pod with neither affinity nor volumes needs no residual row:
        # skip the per-predicate probes for the plain bulk of a batch
        return any((p.spec.affinity is not None or p.spec.volumes)
                   and self._needs_residual(p) for p in pods)

    def _has_filter_extenders(self) -> bool:
        return any(e.config.filter_verb for e in self.extenders)

    def _encoded_live_nodes(self):
        """(live_nodes, encoded_items), cached by mirror epoch — the filter
        and prioritize extender paths share one full-cluster JSON encode
        per snapshot instead of one each per batch."""
        if getattr(self, "_enc_nodes_epoch", None) != self.mirror.epoch:
            from ..api import serde as serde_mod
            live = [ni.node for ni in self.snapshot.node_infos.values()
                    if ni.node is not None]
            self._enc_nodes = (live, [serde_mod.encode(n) for n in live])
            self._enc_nodes_epoch = self.mirror.epoch
        return self._enc_nodes

    def _passes_basic_checks(self, pod: Pod) -> bool:
        """Ref: podPassesBasicChecks (generic_scheduler.go:188) — referenced
        PVCs must exist and not be deleting."""
        if self.pvc_lister is None:
            return True
        for vol in pod.spec.volumes:
            if not vol.persistent_volume_claim:
                continue
            pvc = self.pvc_lister(pod.metadata.namespace,
                                  vol.persistent_volume_claim.claim_name)
            if pvc is None or pvc.metadata.deletion_timestamp is not None:
                return False
        return True

    @staticmethod
    def _canon_pod_aff_term(t) -> Tuple:
        from ..api import labels as labelsmod
        return (labelsmod.canonical_selector(t.label_selector),
                t.topology_key, tuple(sorted(t.namespaces)))

    def _residual_sig(self, pod: Pod) -> Tuple:
        """Everything the residual evaluation can depend on:
        controller-stamped pods share it, so profile resolution, the
        vectorized affinity mask, and the volume per-node pass run once per
        TEMPLATE per batch, not once per pod (the affinity analog of the
        mask-row dedupe in PodBatchTensors). Structured canon, not repr() —
        a deep dataclass repr per pod per batch was the residual path's
        largest host cost. Cached on the pod object (like tensorize's
        _tsig): a pod retried across batches re-canonicalizes nothing —
        informer updates replace the object, so staleness can't stick."""
        sig = pod.__dict__.get("_rsig")
        if sig is not None:
            return sig
        aff = pod.spec.affinity
        aff_canon: Tuple = ()
        if aff is not None:
            parts = []
            for pa in (aff.pod_affinity, aff.pod_anti_affinity):
                if pa is None:
                    parts.append(None)
                    continue
                parts.append((
                    tuple(self._canon_pod_aff_term(t) for t in
                          pa.required_during_scheduling_ignored_during_execution or ()),
                    tuple((wt.weight,
                           self._canon_pod_aff_term(wt.pod_affinity_term))
                          for wt in
                          pa.preferred_during_scheduling_ignored_during_execution or ())))
            aff_canon = tuple(parts)
        vols = tuple(sorted(
            (v.name,
             v.persistent_volume_claim.claim_name
             if v.persistent_volume_claim else "",
             repr(v.gce_persistent_disk), repr(v.aws_elastic_block_store),
             repr(v.azure_disk), repr(v.rbd), repr(v.iscsi))
            for v in pod.spec.volumes))
        sig = (pod.metadata.namespace,
               tuple(sorted(pod.metadata.labels.items())),
               aff_canon, vols)
        pod.__dict__["_rsig"] = sig
        return sig

    def _residual_mask(self, pods: List[Pod]
                       ) -> Tuple[Optional[np.ndarray],
                                  Dict[int, AffinityProfile],
                                  Optional[np.ndarray]]:
        """(extra mask [P, N] | None, profiles, extra group ids [P] | None).
        Group ids name each pod's extra-mask ROW by template (two pods in
        one group provably share the row), so tensorization can dedupe
        mask rows by id instead of hashing 8K of row bytes per pod; None
        when filter extenders are in play (their masks are pod-addressed,
        no sharing is provable)."""
        profiles: Dict[int, AffinityProfile] = {}
        extra: Optional[np.ndarray] = None
        filter_extenders = [e for e in self.extenders
                            if e.config.filter_verb]
        live_nodes = []
        enc_nodes: Optional[list] = None
        if filter_extenders:
            live_nodes, enc_nodes = self._encoded_live_nodes()
        # pass 1: group internal-path pods by template signature; extenders
        # apply per pod (their masks are pod-addressed)
        sig_index: Dict[Tuple, int] = {}
        sig_reps: List[Pod] = []
        pod_sig = np.full((len(pods),), -1, np.int64)
        for i, pod in enumerate(pods):
            internal = self._needs_residual(pod)
            if not internal and not filter_extenders:
                continue
            if extra is None:
                extra = np.ones((len(pods), self.mirror.t.capacity), bool)
            if not self._passes_basic_checks(pod):
                extra[i, :] = False
                pod_sig[i] = -2  # group id for the shared all-False row
                continue
            if internal:
                sig = self._residual_sig(pod)
                u = sig_index.get(sig)
                if u is None:
                    u = len(sig_reps)
                    sig_index[sig] = u
                    sig_reps.append(pod)
                pod_sig[i] = u
            if filter_extenders and not self._apply_filter_extenders(
                    filter_extenders, pod, live_nodes, extra, i, enc_nodes):
                continue
        if not sig_reps:
            return extra, profiles, \
                (None if filter_extenders else pod_sig)
        with self._stage("affinity_masks", templates=len(sig_reps)):
            self._template_rows(pods, extra, pod_sig, profiles,
                                list(sig_index), sig_reps)
        return extra, profiles, (None if filter_extenders else pod_sig)

    def _template_rows(self, pods: List[Pod], extra: np.ndarray,
                       pod_sig: np.ndarray,
                       profiles: Dict[int, AffinityProfile],
                       sigs: List[Tuple], sig_reps: List[Pod]) -> None:
        """Pass 2 of _residual_mask: one vectorized affinity evaluation
        for ALL templates (topology.required_masks — numpy or device
        matmuls by size), plus the per-node volume loop only for
        templates that carry volumes, laid on the pods' rows of `extra`.
        Profile resolution is memoized ACROSS batches by template
        signature, invalidated by the topology index's profile_epoch
        (new terms, zero-crossing match/anti-carry counts — the only
        state a resolved profile depends on)."""
        sig_profiles = [self._cached_profile(sig, p)
                        for sig, p in zip(sigs, sig_reps)]
        constrained = [u for u, pr in enumerate(sig_profiles)
                       if pr.constrained]
        aff_rows: Dict[int, np.ndarray] = {}
        if constrained:
            rows = self.topology.required_masks(
                [sig_profiles[u] for u in constrained])
            for j, u in enumerate(constrained):
                aff_rows[u] = rows[j]
        if self.sched_metrics is not None:
            m = self.sched_metrics
            m.constraint_templates.inc(len(sig_reps))
            if constrained:
                m.constraint_terms.inc(self.topology.last_masks_terms)
                m.affinity_evaluations.inc(
                    stage="masks", route=self.topology.last_masks_route)
        vol_rows = [self._volume_row(rep) for rep in sig_reps]
        # templates whose residual row is provably all-True collapse back
        # to "no extra row" (id -1): one .all() per TEMPLATE keeps the
        # dedupe-by-id win while label-distinct but unconstrained
        # templates share the no-extra mask row instead of each minting
        # an identical all-True [N] row in the unique-mask bucket
        inert_u = [
            (aff_rows.get(u) is None or bool(aff_rows[u].all()))
            and (vol_rows[u] is None or bool(vol_rows[u].all()))
            for u in range(len(sig_reps))]
        for i in range(len(pods)):
            u = int(pod_sig[i])
            if u < 0:
                continue
            row = aff_rows.get(u)
            if row is not None:
                extra[i] &= row
            if vol_rows[u] is not None:
                extra[i] &= vol_rows[u]
            if sig_profiles[u].constrained:
                profiles[i] = sig_profiles[u]
            if inert_u[u]:
                pod_sig[i] = -1

    def _cached_profile(self, sig: Tuple, pod: Pod) -> AffinityProfile:
        """required_profile memoized by template signature across batches
        (a controller's 16k-pod burst resolves its constraint plan once per
        topology profile-epoch, not once per batch). Resolution itself may
        register new match terms — the epoch is read AFTER computing so
        the cached entry reflects the post-registration state."""
        if not self.topo_table_cache:
            self.phase_stats["profile_builds"] += 1
            return self.topology.required_profile(pod)
        hit = self._profile_cache.get(sig)
        if hit is not None and hit[0] == self.topology.profile_epoch:
            self.phase_stats["profile_hits"] += 1
            return hit[1]
        prof = self.topology.required_profile(pod)
        if len(self._profile_cache) > 4096:
            self._profile_cache.clear()
        self._profile_cache[sig] = (self.topology.profile_epoch, prof)
        self.phase_stats["profile_builds"] += 1
        return prof

    def _volume_row(self, pod: Pod) -> Optional[np.ndarray]:
        """One template's [capacity] volume-predicate mask (NoDiskConflict,
        Max*VolumeCount, zone conflict, volume binding), or None when the
        pod carries no volume constraints — the only predicates left on the
        per-node host loop."""
        has_disk = _pod_has_conflict_volumes(pod)
        has_pvc = _pod_has_pvc(pod)
        has_attach = has_pvc or _pod_has_attach_volumes(pod)
        if not (has_disk or has_pvc or has_attach):
            return None
        from types import SimpleNamespace
        meta = SimpleNamespace(memo={})  # Max*VolumeCount wanted-set memo
        row_mask = np.zeros((self.mirror.t.capacity,), bool)
        for name, ni in self.snapshot.node_infos.items():
            row = self.mirror.row_of.get(name)
            if row is None:
                continue
            ok = True
            if has_disk:
                ok, _ = preds.no_disk_conflict(pod, meta, ni)
            if ok and has_attach:
                for fn in self._volume_count_preds.values():
                    ok, _ = fn(pod, meta, ni)
                    if not ok:
                        break
            if ok and has_pvc:
                ok, _ = self._zone_conflict(pod, meta, ni)
                if ok and ni.node is not None:
                    ok = self.volume_binder.find_pod_volumes(pod, ni.node)
            row_mask[row] = ok
        return row_mask

    def _apply_filter_extenders(self, filter_extenders, pod: Pod,
                                live_nodes, extra: np.ndarray,
                                i: int, enc_nodes=None) -> bool:
        """AND each extender's feasible set into the pod's row. The batch
        deviation from the reference: extenders see ALL live nodes, not
        only internal-predicate survivors (core/extender.go runs after
        findNodesThatFit) — the intersection is identical. Returns False
        when a non-ignorable extender failed (the pod is unschedulable
        this cycle, ref: Filter error handling :258)."""
        from .extender import ExtenderError
        for e in filter_extenders:
            try:
                names, _failed = e.filter(pod, live_nodes, enc_nodes)
            except ExtenderError:
                if e.is_ignorable():
                    continue
                extra[i, :] = False
                return False
            allowed = np.zeros((extra.shape[1],), bool)
            for nm in names:
                row = self.mirror.row_of.get(nm)
                if row is not None:
                    allowed[row] = True
            extra[i] &= allowed
        return True

    def _apply_prioritize_extenders(self, pods: List[Pod],
                                    batch: "PodBatchTensors",
                                    static) -> None:
        """Merge extender prioritize scores into the batch's static score
        rows (ref: PrioritizeNodes :774-804 — weighted extender scores add
        to the internal sum). Errors are ignored per extender, matching
        the reference's ignorable-prioritize behavior."""
        from .extender import ExtenderError
        N = self.mirror.t.capacity
        live_nodes, enc_nodes = self._encoded_live_nodes()
        ext = np.zeros((len(pods), N), np.float32)
        for i, pod in enumerate(pods):
            for e in self.extenders:
                if not e.config.prioritize_verb:
                    continue
                try:
                    scores = e.prioritize(pod, live_nodes, enc_nodes)
                except ExtenderError:
                    continue
                for nm, s in scores.items():
                    row = self.mirror.row_of.get(nm)
                    if row is not None:
                        ext[i, row] += s
        if static is not None:
            idx, rows = static
            base = rows[np.asarray(idx[:len(pods)])]
        else:
            base = np.zeros((len(pods), N), np.float32)
        batch.set_static_scores(
            np.arange(len(pods), dtype=np.int32), base + ext)

    #: max batch size for pods whose soft scores would drift in-batch.
    #: SelectorSpread and preferred inter-pod (anti-)affinity
    #: both run IN-SCAN (running group counts / credit accumulators, on
    #: every kernel incl. the gang kernel's trial carry), so sub-chunking
    #: engages only when a batch OVERFLOWS the in-scan caps.
    SOFT_SCORE_CHUNK = 256

    def topo_scan_likely(self, pods: List[Pod]) -> bool:
        """True when this batch carries required ANTI-affinity — the
        in-scan counter workload whose per-step [K, N] gathers still make
        power-of-two padding worth splitting away (drain_pipelined's
        alignment split: +24% at r06, down from +33% pre-class-scan).
        Required AFFINITY batches measure FASTER unsplit (their tight
        feasible sets retry across launches), so they keep the padded
        single scan."""
        if self.topology.has_required_anti_carriers():
            return True
        return any(
            p.spec.affinity is not None
            and p.spec.affinity.pod_anti_affinity is not None
            and p.spec.affinity.pod_anti_affinity
            .required_during_scheduling_ignored_during_execution
            for p in pods)

    def soft_batch_limit(self, pods: List[Pod]) -> int:
        """How many of these pods may schedule in ONE kernel batch and
        still get the serial reference's decisions: the cut before a pod
        whose required affinity an earlier pod of the batch can widen
        (_affinity_growth_cut), and the soft-score limit below."""
        cut = self._affinity_growth_cut(pods)
        if cut < len(pods):
            self._count_inscan_fallback("aff_growth")
            return min(cut, self._soft_score_limit(pods[:cut]))
        self._end_inscan_streak("aff_growth")
        return self._soft_score_limit(pods)

    def _affinity_growth_cut(self, pods: List[Pod]) -> int:
        """Index of the first pod whose required affinity term an earlier
        pod of this batch can carry into a domain it was not in when the
        batch was popped; len(pods) where there is none.

        A batch's mask rows are taken at its start. For required
        ANTI-affinity and for a waived affinity term the scan keeps the
        winners' counts itself; a term that is not waived only ever
        gains matches, so its row stays right unless a winner lands
        where the term had no match: then the serial reference, which
        sees each bind, admits nodes the row excludes. A winner that
        matches the term can do that unless it requires the same term
        itself and the term has a match already (it then lands only
        where the term matches). scheduler_perf's pod-affinity pods all
        require the term they match, so their batches are never cut. The
        pods from the cut on are popped into the next batch, whose rows
        see the binds before it."""
        if not any(_required_pod_affinity(p) for p in pods):
            return len(pods)
        idx = self.topology
        #: residual signature -> (terms it reads unwaived, terms a bind of
        #: it can carry into a new domain)
        memo: Dict[Tuple, Tuple[frozenset, frozenset]] = {}
        widened: set = set()
        for i, pod in enumerate(pods):
            sig = self._residual_sig(pod)
            hit = memo.get(sig)
            if hit is None:
                reads, anchored = set(), set()
                for t in _required_pod_affinity(pod):
                    term = idx.ensure_match(
                        t.topology_key, idx._resolved_ns(t, pod),
                        t.label_selector)
                    matched = idx.match_domains(term.tid) > 0
                    if matched or not term.matches_pod(pod):
                        reads.add(term.tid)
                    if matched:
                        anchored.add(term.tid)
                hit = memo[sig] = (frozenset(reads),
                                   frozenset(idx.match_set(pod) - anchored))
            reads, widens = hit
            if i and not reads.isdisjoint(widened):
                return i
            widened |= widens
        return len(pods)

    def _soft_score_limit(self, pods: List[Pod]) -> int:
        """How many of these pods may schedule in ONE kernel batch without
        visible soft-score drift. Preferred inter-pod (anti-)affinity
        scores change with every in-batch winner; the serial reference
        re-scores per pod via assume-between-iterations. When the batch's
        soft term union fits the in-scan credit tables
        (_assign_soft_terms), the kernel re-scores per pod itself and the
        whole batch launches at once; only an overflowing union still
        schedules in SOFT_SCORE_CHUNK sub-batches. SelectorSpread never
        chunks: every spread group of the batch rides the scan's carry
        (_assign_spread_groups), and a pop that holds more than
        SPREAD_GROUP_CAP of them is cut before the first pod of the group
        past it (counted, reason spread_groups), which the next launch
        then scores from the counts this one leaves."""
        chunk = self.SOFT_SCORE_CHUNK
        cut = self._spread_group_cut(pods)
        if cut <= chunk or chunk <= 0:
            return cut
        if self.scorer.weights.get("InterPodAffinityPriority"):
            has_pref = any(
                p.spec.affinity is not None and (
                    (p.spec.affinity.pod_affinity is not None and
                     p.spec.affinity.pod_affinity
                     .preferred_during_scheduling_ignored_during_execution)
                    or (p.spec.affinity.pod_anti_affinity is not None and
                        p.spec.affinity.pod_anti_affinity
                        .preferred_during_scheduling_ignored_during_execution))
                for p in pods)
            if has_pref:
                # the plan of the pop, before the shell opens the cycle:
                # the launch of the same list finds it made
                with self._stage("soft_terms", pods=len(pods)):
                    plan = self._soft_plan_cached(pods)
                if plan is None:
                    # channel-union overflow: sub-chunk so frozen credits
                    # refresh between launches. Gang batches used to chunk
                    # UNCONDITIONALLY here (soft_gang); the gang kernel's
                    # trial/committed soft accumulators lifted that, so
                    # the counter now marks only gang batches that STILL
                    # overflow the in-scan caps — wired, not silent
                    if self.gang is not None:
                        from .gang import pod_group_key
                        if any(pod_group_key(p) is not None for p in pods):
                            self._count_inscan_fallback("soft_gang")
                    return chunk
        return cut

    #: spread groups one launch carries: the rows of the scan's [G, N]
    #: count carry (G bucketed; 128 MiB of device memory at 8,192 node
    #: rows). A pop holds about one group a Service it touches, some 110
    #: in a rollout of thousands of Services, so this follows the queue
    #: as TOPO_TERM_CAP does; a pop past it is cut there, never scored
    #: from a frozen row (_spread_group_cut)
    SPREAD_GROUP_CAP = 4096

    def _spread_group_cut(self, pods: List[Pod]) -> int:
        """The index of the first pod of spread group number
        SPREAD_GROUP_CAP + 1, or len(pods): a launch carries the groups
        before it exactly, and the pods from there on are the next
        launch's. A pop of no more pods than the cap cannot hold more
        groups, so the served path (pops of about a thousand) does not
        look."""
        listers = self.scorer.listers
        if len(pods) <= self.SPREAD_GROUP_CAP or listers is None or \
                not self.scorer.weights.get("SelectorSpreadPriority"):
            return len(pods)
        seen: set = set()
        groups: set = set()
        for i, pod in enumerate(pods):
            key = (pod.metadata.namespace,
                   tuple(sorted(pod.metadata.labels.items())))
            if key in seen:
                continue
            seen.add(key)
            sels = listers.selectors_for_pod(pod)
            if sels:
                gkey = self._spread_group_key(key, sels)
                if gkey not in groups and \
                        len(groups) == self.SPREAD_GROUP_CAP:
                    self._count_inscan_fallback("spread_groups")
                    return i
                groups.add(gkey)
        return len(pods)

    def _assign_spread_groups(self, pods: List[Pod],
                              batch: PodBatchTensors) -> Optional[Tuple]:
        """Group the pods that a Service or controller selects by
        (namespace, the set of selectors that match them): pods of one
        group read one count (the pods that match every selector of the
        set, selector_spreading.go countMatchingPods), whatever else
        their labels say. Install per-group base counts + zone ids so
        the kernel scores SelectorSpread from RUNNING counts (the serial
        semantics — selector_spreading.go:277 re-counts per pod).

        Every group of the batch gets a slot: one algorithm for 1 group
        and for 200. A group's base counts are read off
        scorer.SpreadIndex, which follows the binds (O(the group's bound
        pods); no walk over the nodes once the index is on), and cross to
        the device as (group, row, count) triplets. Past SPREAD_GROUP_CAP
        the caller has cut the pop (_spread_group_cut); a caller that did
        not leaves the groups past it to the batch-start row of
        scorer.static_scores, counted as spread_groups.

        Returns the batch's spread chain SIGNATURE (ordered group keys +
        everything the carried [G, N] counts' meaning depends on), or
        None when no spread tables ride. Two batches with equal
        signatures name group g identically, so a chained launch may
        seed its count carry from the predecessor's finals."""
        listers = self.scorer.listers
        weight = self.scorer.weights.get("SelectorSpreadPriority", 0)
        if listers is None or not weight:
            return None
        with self._stage("spread_groups", pods=len(pods)):
            return self._spread_groups(pods, batch, listers, float(weight))

    @staticmethod
    def _spread_group_key(labels_key: Tuple, sels: list) -> Tuple:
        """(namespace, the selectors' keys, sorted). A selector that does
        not name itself (a lister of the caller's own) leaves the label
        set to name the group."""
        keys = [getattr(sel, "key", None) for sel in sels]
        if any(k is None for k in keys):
            return (labels_key[0], (("labels", labels_key[1]),))
        return (labels_key[0], tuple(sorted(set(keys))))

    def _spread_groups(self, pods: List[Pod], batch: PodBatchTensors,
                       listers, weight: float) -> Optional[Tuple]:
        #: (ns, labels) -> its group's key, or None where nothing selects it
        group_of: Dict[Tuple, Optional[Tuple]] = {}
        #: group key -> its selectors
        sel_of: Dict[Tuple, list] = {}
        keys: List[Optional[Tuple]] = []
        overflow = False
        for pod in pods:
            key = (pod.metadata.namespace,
                   tuple(sorted(pod.metadata.labels.items())))
            if key not in group_of:
                sels = listers.selectors_for_pod(pod)
                gkey = self._spread_group_key(key, sels) if sels else None
                if gkey is not None and gkey not in sel_of:
                    if len(sel_of) >= self.SPREAD_GROUP_CAP:
                        gkey, overflow = None, True
                    else:
                        sel_of[gkey] = sels
                group_of[key] = gkey
            keys.append(key)
        if overflow:
            self._count_inscan_fallback("spread_groups")
        # canonical group order: slot g is sorted-template-key order, not
        # first-pod order — batches popping the same templates in a
        # rotated pod order land on the SAME signature, so the chained
        # count carry stays consumable. Pure renumbering: decisions are
        # invariant
        group_keys = sorted(sel_of)
        if not group_keys:
            return None
        slot = {k: g for g, k in enumerate(group_keys)}
        scorer = self.scorer
        scorer._refresh_epoch()
        index = scorer.spread_index
        if not index.active:
            index.activate(self.snapshot)
        row_of = self.mirror.row_of
        nz: List[Tuple[int, int, int]] = []
        largest = 0
        for g, key in enumerate(group_keys):
            total = 0
            for node, c in index.counts(key[0], sel_of[key]).items():
                row = row_of.get(node)
                if row is not None:
                    nz.append((g, row, c))
                    total += c
            largest = max(largest, total)
        # cross-group match lists: a winner must bump every group whose
        # selectors match its labels, not only its own. A group is filed
        # under an item its selectors require, so a label set meets its
        # candidates by its own items
        by_item: Dict[Tuple, List[int]] = {}
        apart: List[int] = []
        for g, key in enumerate(group_keys):
            item = prios.required_item(sel_of[key])
            if item is None:
                apart.append(g)
            else:
                by_item.setdefault((key[0], item), []).append(g)
        matched_of: Dict[Tuple, Tuple[int, ...]] = {}
        matched: List[Tuple[int, ...]] = []
        gidx = batch.spread_gidx
        for i, pod in enumerate(pods):
            key = keys[i]
            m = matched_of.get(key)
            if m is None:
                lbls = pod.metadata.labels
                cands = apart + [g for item in key[1]
                                 for g in by_item.get((key[0], item), ())]
                m = matched_of[key] = tuple(sorted(
                    g for g in set(cands)
                    if group_keys[g][0] == key[0]
                    and all(sel(lbls) for sel in sel_of[group_keys[g]])))
            matched.append(m)
            if group_of[key] is not None:
                gidx[i] = slot[group_of[key]]
        table = scorer.spread_round_table()
        # _spread_exact's int32 holds 6 * maxN * maxZ: a node's count is
        # under the table's side, a zone's under the group's bound pods
        # plus this batch
        if 6 * (table.shape[0] - 1) * (largest + len(pods)) >= 2 ** 31:
            self._count_inscan_fallback("spread_range")
        elif not overflow:
            self._end_inscan_streak("spread_groups", "spread_range")
        batch.set_spread(len(group_keys),
                         np.asarray(nz, np.int32).reshape(-1, 3).T,
                         matched, scorer.zone_ids_device(), scorer._n_zones,
                         weight, table)
        if self.sched_metrics is not None:
            self.sched_metrics.spread_groups.inc(len(group_keys))
            walked = index.rows_walked
            if walked:
                index.rows_walked = 0
                self.sched_metrics.spread_rows_walked.inc(walked)
        return (tuple(group_keys), scorer._n_zones, weight,
                self.mirror.epoch, scorer.spread_sel_gen,
                self.mirror.t.capacity)

    #: in-scan topology term cap per batch; bigger batches fall back to
    #: the repair overlay + reassignment path entirely
    TOPO_TERM_CAP = 512
    #: per-pod in-scan term fan-out cap (the kernel's K axis)
    TOPO_KMAX = 16

    def _count_inscan_fallback(self, reason: str) -> None:
        """No silent caps: every in-scan fallback (kmax/term-cap overflow,
        soft term-union overflow, a pop cut at the spread group cap or a
        spread group past what the score's int32 holds) is counted by
        reason and logged once per streak."""
        if self.sched_metrics is not None:
            self.sched_metrics.topo_inscan_fallbacks.inc(reason=reason)
        self._batch_fell_back = True
        streak = self._fallback_streak.get(reason, 0)
        if streak == 0:
            import logging
            logging.getLogger(__name__).warning(
                "in-scan topology fallback (%s): batch takes the repair/"
                "chunked path; further occurrences counted in "
                "scheduler_topo_inscan_fallbacks_total", reason)
        self._fallback_streak[reason] = streak + 1

    def _count_capped_scan(self, cap: str, n: int) -> None:
        """No silent caps (KTPU005): a truncated candidate search is
        counted by cap name and logged once per streak, like the
        in-scan fallbacks above."""
        if self.sched_metrics is not None:
            self.sched_metrics.capped_scans.inc(cap=cap)
        streak = self._fallback_streak.get(cap, 0)
        if streak == 0:
            import logging
            logging.getLogger(__name__).warning(
                "capped scan (%s): %d candidates truncated to the "
                "documented cap; further occurrences counted in "
                "scheduler_capped_scans_total", cap, n)
        self._fallback_streak[cap] = streak + 1

    def _end_inscan_streak(self, *reasons: str) -> None:
        """A batch made it through the in-scan caps: close these reasons'
        fallback streaks so the NEXT overflow logs again (the per-streak
        contract; without this the warning fires once per process)."""
        for reason in reasons:
            self._fallback_streak[reason] = 0

    def _assign_topology_terms(self, pods: List[Pod],
                               batch: PodBatchTensors,
                               profiles: Dict[int, AffinityProfile]) -> str:
        """In-scan required (anti-)affinity tables: the kernel scan tracks
        per-(term, domain) winner-match AND winner-carry counts so each
        pod's feasibility respects EARLIER SAME-BATCH winners in both
        anti-affinity directions — the serial reference's
        assume-between-iterations visibility (scheduler.go:514), which the
        frozen batch-start mask lacks. The repair overlay stays as the
        validator for ports/volumes/chained-predecessor winners.

        Returns coverage: "installed" (tables active), "inert" (provably
        no in-batch (anti-)affinity interaction exists to validate), or
        "fallback" (caps overflowed; only the repair overlay validates).

        Terms NO batch member matches are hoisted out entirely: their
        counters could never move in-scan (only winner matches bump them),
        so the pre-batch static mask already covers them — the per-pod K
        axis then chains only genuinely carried terms through the scan.
        The [T, N] dom table comes from the topology index's epoch-keyed
        cache (one gather per node-topology change, not per batch)."""
        if not profiles:
            return "inert"
        idx = self.topology
        anti_tids: List[int] = []
        aff_tids: List[int] = []
        seen: set = set()
        for prof in profiles.values():
            for tid in prof.req_anti:
                if tid not in seen:
                    seen.add(tid)
                    anti_tids.append(tid)
            for tid, waived in prof.req_aff:
                if waived and tid not in seen:
                    seen.add(tid)
                    aff_tids.append(tid)
        if not anti_tids and not aff_tids:
            return "inert"
        # hoist: restrict the term union to terms some batch member
        # MATCHES — an unmatched term's in-scan counter is provably static
        cand = seen
        matched: set = set()
        match_sets: Dict[Tuple, frozenset] = {}
        for pod in pods:
            mkey = (pod.metadata.namespace,
                    tuple(sorted(pod.metadata.labels.items())))
            ms = match_sets.get(mkey)
            if ms is None:
                ms = idx.match_set(pod)
                match_sets[mkey] = ms
            matched |= ms & cand
            if len(matched) == len(cand):
                break
        # sorted: the table's cache key is the term-id tuple, and batches
        # popping the same templates in a different pod order must land on
        # the same cached [T, N] table (positions are per-batch anyway)
        terms = sorted(tid for tid in set(anti_tids + aff_tids)
                       if tid in matched)
        if not terms:
            return "inert"  # every candidate term is in-batch inert
        if len(terms) > self.TOPO_TERM_CAP:
            self._count_inscan_fallback("term_cap")
            return "fallback"
        P = len(pods)
        dom, n_domains = idx.term_table(tuple(terms),
                                        use_cache=self.topo_table_cache)
        # sharded drain: the padded [T, N] table also lives ON DEVICE,
        # epoch-cached and sharded by the name rules, so steady-state
        # batches skip the per-batch table upload entirely
        dom_dev = None
        if self.mirror.mesh is not None:
            dom_dev, _ = idx.term_table_device(
                tuple(terms), use_cache=self.topo_table_cache,
                dom=dom, n_domains=n_domains)
        tpos = {tid: j for j, tid in enumerate(terms)}
        # per-pod [K] term-index lists (-1 padded): the kernel's cost per
        # scan step is O(K*N), independent of the batch's term union
        anti_l: List[List[int]] = []
        aff_l: List[List[int]] = []
        match_l: List[List[int]] = []
        kmax = 1
        match_memo: Dict[Tuple, List[int]] = {}
        for i, pod in enumerate(pods):
            prof = profiles.get(i)
            a: List[int] = []
            f: List[int] = []
            if prof is not None:
                a = [tpos[tid] for tid in prof.req_anti if tid in tpos]
                f = [tpos[tid] for tid, waived in prof.req_aff
                     if waived and tid in tpos]
            mkey = (pod.metadata.namespace,
                    tuple(sorted(pod.metadata.labels.items())))
            m = match_memo.get(mkey)
            if m is None:
                ms = match_sets.get(mkey)
                if ms is None:
                    # the hoist pass short-circuits once every candidate
                    # term is matched — later templates fill in here
                    ms = idx.match_set(pod)
                    match_sets[mkey] = ms
                m = [tpos[tid] for tid in ms if tid in tpos]
                match_memo[mkey] = m
            kmax = max(kmax, len(a), len(f), len(m))
            anti_l.append(a)
            aff_l.append(f)
            match_l.append(m)
        if kmax > self.TOPO_KMAX:
            self._count_inscan_fallback("kmax")
            return "fallback"  # degenerate fan-out: repair path handles it
        # direction 2 (winner CARRIES anti term t, later pod MATCHES it):
        # a pod needs an in-scan read on t only when the block isn't
        # already implied by its own direction-1 read — i.e. unless the
        # pod itself carries t AND every batch carrier of t also matches
        # it (then {carriers} ⊆ {matchers} makes direction 1 strictly
        # stronger). The common self-anti shape (each pod carries AND
        # matches its own color) needs NO direction-2 state at all, so
        # the extra [T, D] carry table ships only when some pure matcher
        # exists.
        carrier_pos: set = set()
        carrier_ok: Dict[int, bool] = {}
        for i in range(len(pods)):
            mset = set(match_l[i])
            for t in anti_l[i]:
                carrier_pos.add(t)
                if t not in mset:
                    carrier_ok[t] = False
        cmatch_l: List[List[int]] = []
        dir2_read: set = set()
        for i in range(len(pods)):
            aset = set(anti_l[i])
            cm = [t for t in match_l[i]
                  if t in carrier_pos
                  and not (t in aset and carrier_ok.get(t, True))]
            dir2_read.update(cm)
            cmatch_l.append(cm)
        canti_l = [[t for t in anti_l[i] if t in dir2_read]
                   for i in range(len(pods))] if dir2_read else None
        if dir2_read:
            kmax = max(kmax, max(len(l) for l in cmatch_l),
                       max(len(l) for l in canti_l))
            if kmax > self.TOPO_KMAX:
                self._count_inscan_fallback("kmax")
                return "fallback"

        def to_arr(lists: List[List[int]]) -> np.ndarray:
            K = max(1, kmax)
            out = np.full((P, K), -1, np.int32)
            for i, l in enumerate(lists):
                out[i, :len(l)] = l
            return out
        batch.set_topology_terms(
            dom, n_domains, to_arr(anti_l), to_arr(aff_l), to_arr(match_l),
            cmatch_tids=to_arr(cmatch_l) if dir2_read else None,
            canti_tids=to_arr(canti_l) if dir2_read else None,
            dom_dev=dom_dev)
        self._end_inscan_streak("term_cap", "kmax")
        return "installed"

    #: in-scan soft (preferred inter-pod affinity) channel caps: a batch
    #: whose credit-channel union or per-pod fan-out overflows these falls
    #: back to SOFT_SCORE_CHUNK sub-batching (counted, never silent)
    SOFT_TERM_CAP = 64
    SOFT_KMAX = 16

    def _soft_plan_cached(self, pods: List[Pod]):
        """_soft_plan, computed once per pod-list object. Keyed by list
        IDENTITY: a truncated batch (drain slices pods[:limit]) is a new
        list and recomputes; the plan itself only depends on batch specs
        plus match-set membership of tids the first call interned, both
        stable between pop and launch on the drain thread."""
        memo = self._soft_plan_memo
        if memo is not None and memo[0] is pods:
            return memo[1]
        plan = self._soft_plan(pods)
        self._soft_plan_memo = (pods, plan)
        return plan

    def _soft_plan(self, pods: List[Pod]):
        """Channel plan for in-scan preferred inter-pod (anti-)affinity
        credits, or None when the batch can't (or needn't) run them
        in-scan. Channels are per-(kind, term) accumulators a winner
        writes and later pods read at their nodes' domains:
            m:  winners MATCHING the term (readers: the term's owners, ±w)
            ca: winners carrying the term as required affinity
                (readers: matching pods, × hard_pod_affinity_weight)
            cp/cn: winners carrying it as preferred (anti-)affinity,
                weight-summed (readers: matching pods, × ±1)
        — exactly the topology index's count kinds, scoped to one batch."""
        w = self.scorer.weights.get("InterPodAffinityPriority", 0)
        if not w:
            return None
        idx = self.topology
        hard_w = float(self.scorer.hard_pod_affinity_weight)
        channels: Dict[Tuple[str, int], int] = {}
        chan_list: List[Tuple[str, int]] = []

        def slot(kind: str, tid: int) -> int:
            k = (kind, tid)
            s = channels.get(k)
            if s is None:
                s = len(chan_list)
                channels[k] = s
                chan_list.append(k)
            return s

        # pass 1: template dedupe; own preferred read terms + carried
        # write channels (a winner's contribution to later pods)
        tmpl_key: Dict[Tuple, int] = {}
        tmpl_pods: List[Pod] = []
        tmpl_pref: List[List[Tuple[int, float]]] = []
        tmpl_carry: List[List[Tuple[str, int, float]]] = []
        tmpl_of = np.zeros((len(pods),), np.int32)
        for i, pod in enumerate(pods):
            key = self._residual_sig(pod)
            t = tmpl_key.get(key)
            if t is None:
                t = len(tmpl_pods)
                tmpl_key[key] = t
                tmpl_pods.append(pod)
                pref: List[Tuple[int, float]] = []
                carry: List[Tuple[str, int, float]] = []
                aff = pod.spec.affinity
                pa = aff.pod_affinity if aff else None
                paa = aff.pod_anti_affinity if aff else None
                for sign, kind, wterms in (
                        (1.0, "cp",
                         pa.preferred_during_scheduling_ignored_during_execution
                         if pa else ()),
                        (-1.0, "cn",
                         paa.preferred_during_scheduling_ignored_during_execution
                         if paa else ())):
                    for wt in wterms or ():
                        if not wt.weight:
                            continue
                        term = idx.ensure_match(
                            wt.pod_affinity_term.topology_key,
                            idx._resolved_ns(wt.pod_affinity_term, pod),
                            wt.pod_affinity_term.label_selector)
                        slot("m", term.tid)
                        pref.append((term.tid, sign * float(wt.weight)))
                        carry.append((kind, term.tid, float(wt.weight)))
                if hard_w and pa is not None:
                    for rt in pa.required_during_scheduling_ignored_during_execution or ():
                        term = idx._intern(
                            rt.topology_key, idx._resolved_ns(rt, pod),
                            rt.label_selector)
                        carry.append(("ca", term.tid, 1.0))
                for kind, tid, _cw in carry:
                    slot(kind, tid)
                tmpl_pref.append(pref)
                tmpl_carry.append(carry)
            tmpl_of[i] = t
        if not any(tmpl_pref):
            # no batch member carries preferred terms: the one soft score
            # an in-batch winner can still move is the hard-affinity
            # symmetric credit. Where every reader of it is pinned to one
            # domain (the self-affine groups of a controller) the credit
            # is flat over the reader's nodes whatever lands, the static
            # rows are exact, and the batch keeps the class scan without
            # credit tables; any other reader gets its channels in-scan
            chan_list = self._unpinned_hard_credits(tmpl_pods, tmpl_carry)
            channels = {k: s for s, k in enumerate(chan_list)}
            tmpl_carry = [[c for c in carry if (c[0], c[1]) in channels]
                          for carry in tmpl_carry]
        if not chan_list:
            return None  # no in-batch credit can move: static rows suffice
        # canonical template order (repr: residual sigs mix None/str/tuple
        # and are not directly comparable) — like the channel sort below,
        # this keeps rotated-pod-order batches on one chain signature
        # (soft_base row r must mean the same template batch to batch).
        # Pure renumbering; per-template structures permute consistently
        tkeys = list(tmpl_key)
        torder = sorted(range(len(tmpl_pods)),
                        key=lambda t: repr(tkeys[t]))
        tremap = {old: new for new, old in enumerate(torder)}
        tmpl_pods = [tmpl_pods[t] for t in torder]
        tmpl_pref = [tmpl_pref[t] for t in torder]
        tmpl_carry = [tmpl_carry[t] for t in torder]
        tmpl_of = np.asarray([tremap[int(t)] for t in tmpl_of], np.int32)
        tkeys = [tkeys[t] for t in torder]
        if len(chan_list) > self.SOFT_TERM_CAP:
            self._count_inscan_fallback("soft_terms")
            return None
        # canonical channel order: the dom table's cache key is the slot
        # term tuple, so pod-order-insensitive slot numbering keeps
        # repeat batches on the cached table
        chan_list = sorted(chan_list)
        channels = {k: s for s, k in enumerate(chan_list)}
        # pass 2: per-template read/write slot lists against the full
        # channel union
        read_kinds = {"ca": hard_w, "cp": 1.0, "cn": -1.0}
        tmpl_reads: List[List[Tuple[int, float]]] = []
        tmpl_writes: List[List[Tuple[int, float]]] = []
        kmax = 0
        for t, rep in enumerate(tmpl_pods):
            mset = idx.match_set(rep)
            reads = [(channels[("m", tid)], pw)
                     for tid, pw in tmpl_pref[t]]
            writes = [(channels[(kind, tid)], cw)
                      for kind, tid, cw in tmpl_carry[t]]
            for kind, tid in chan_list:
                if tid not in mset:
                    continue
                if kind == "m":
                    writes.append((channels[(kind, tid)], 1.0))
                else:
                    reads.append((channels[(kind, tid)],
                                  read_kinds[kind]))
            kmax = max(kmax, len(reads), len(writes))
            tmpl_reads.append(reads)
            tmpl_writes.append(writes)
        if kmax > self.SOFT_KMAX:
            self._count_inscan_fallback("soft_kmax")
            return None
        self._end_inscan_streak("soft_terms", "soft_kmax", "soft_gang")
        return {"chan_list": chan_list, "tmpl_of": tmpl_of,
                "tmpl_pods": tmpl_pods, "reads": tmpl_reads,
                "writes": tmpl_writes, "kmax": max(1, kmax),
                "weight": float(w), "hard_w": hard_w,
                # canonically ordered template keys: part of the soft
                # chain signature (soft_base row r must mean the same
                # template on both sides of a chained launch)
                "tmpl_sigs": tuple(tkeys)}

    def _unpinned_hard_credits(self, tmpl_pods: List[Pod],
                               tmpl_carry: List[List[Tuple[str, int, float]]]
                               ) -> List[Tuple[str, int]]:
        """The ("ca", term) channels of a batch without preferred terms
        that some template reads over nodes of more than one domain: the
        credits an earlier winner of the same batch can move an argmax
        with (the serial reference re-scores after every bind).

        A template that matches a carried term t reads its credit. The
        read is flat, and needs no channel, when the template is pinned
        to one domain of t's topology key: it requires a term q on that
        key whose matches lie in at most one domain now and stay there,
        because every template of the batch that matches q requires q
        too (it lands only where q already matches; with no match yet,
        the first lands anywhere and the scan's waiver tables hold the
        rest to its domain). scheduler_perf's pod-affinity pods are all
        of this kind: each colour requires, matches and carries its own
        term."""
        idx = self.topology
        required = [{tid for kind, tid, _w in carry if kind == "ca"}
                    for carry in tmpl_carry]
        carried = set().union(*required) if required else set()
        if not carried:
            return []
        msets = [idx.match_set(rep) for rep in tmpl_pods]
        stays: Dict[int, bool] = {}

        def stays_in_one_domain(q: int) -> bool:
            hit = stays.get(q)
            if hit is None:
                hit = stays[q] = idx.match_domains(q) <= 1 and all(
                    q in required[j] for j, ms in enumerate(msets)
                    if q in ms)
            return hit

        keep = set()
        for r, ms in enumerate(msets):
            for t in carried & ms:
                tk = idx.term(t).tk
                if not any(idx.term(q).tk == tk and stays_in_one_domain(q)
                           for q in required[r]):
                    keep.add(("ca", t))
        return sorted(keep)

    def _assign_soft_terms(self, pods: List[Pod],
                           batch: PodBatchTensors) -> Optional[Tuple]:
        """Install in-scan preferred inter-pod (anti-)affinity credit
        tables: the kernel then re-scores soft credits per pod from
        running accumulators (the serial reference's re-score via
        assume-between-iterations), which lifts the SOFT_SCORE_CHUNK
        sub-batching for the common small-term-union case.

        Returns the batch's soft chain SIGNATURE (channel order +
        template order + everything the carried accumulators' meaning
        depends on), or None when no tables ride."""
        plan = self._soft_plan_cached(pods)
        self._soft_plan_memo = None   # batch consumed; drop the list ref
        if plan is None:
            return None
        idx = self.topology
        dom, n_domains = idx.term_table(
            tuple(tid for _, tid in plan["chan_list"]),
            use_cache=self.topo_table_cache)
        cap = self.mirror.t.capacity
        base_rows = []
        for rep in plan["tmpl_pods"]:
            raw = idx.score_vector(rep, plan["hard_w"])
            base_rows.append(raw if raw is not None
                             else np.zeros((cap,), np.float32))
        base = np.stack(base_rows)
        n = len(pods)
        K = plan["kmax"]
        read_tids = np.full((n, K), -1, np.int32)
        read_w = np.zeros((n, K), np.float32)
        write_tids = np.full((n, K), -1, np.int32)
        write_w = np.zeros((n, K), np.float32)
        for i in range(n):
            t = plan["tmpl_of"][i]
            for j, (s, rw) in enumerate(plan["reads"][t]):
                read_tids[i, j] = s
                read_w[i, j] = rw
            for j, (s, ww) in enumerate(plan["writes"][t]):
                write_tids[i, j] = s
                write_w[i, j] = ww
        batch.set_soft_terms(dom, n_domains, base, plan["tmpl_of"],
                             read_tids, read_w, write_tids, write_w,
                             plan["weight"])
        if self.sched_metrics is not None:
            self.sched_metrics.soft_channels.inc(len(plan["chan_list"]))
        return (tuple(plan["chan_list"]), plan["tmpl_sigs"],
                plan["kmax"], plan["weight"], plan["hard_w"],
                n_domains, self.mirror.epoch, self.mirror.t.capacity)

    def _make_reassigner(self, batch: Optional[PodBatchTensors],
                         stale_winners):
        """A host-side serial re-solver for repair losers, or None when the
        batch can't support one (no tensors, or nominated reservations are
        in play — the kernel's nom handling has no host replica, so those
        rare cycles keep the retry path)."""
        if batch is None:
            return None
        if self.nominated is not None and self.nominated.by_node():
            return None
        return _RepairReassigner(self.mirror, batch, stale_winners)

    def _repair_batch(self, results: List[ScheduleResult],
                      profiles: Dict[int, AffinityProfile],
                      stale_winners=None,
                      batch: Optional[PodBatchTensors] = None) -> bool:
        """Validate host-evaluated predicates against earlier winners in the
        same batch; losers are demoted to retry or serially reassigned.
        Skipped when nothing in the batch carries ports/affinity/disk
        constraints. Affinity interactions run against a BatchOverlay of
        winner term counts (O(terms) dict lookups per pod) — the batch
        analog of the serial reference's cache.AssumePod visibility between
        scheduleOne iterations. Returns True when any kernel winner was
        demoted or reassigned — the kernel's in-scan counters then
        over-state (they counted the original placement), so
        kernel-unassigned pods must retry, not park."""
        # overlay NodeInfos (winner clones) are only consulted by the
        # ports/disk/attach checks — skip their maintenance entirely for
        # affinity-only batches (the deepcopy per winner is the cost)
        track_nodes = any(
            helpers.pod_host_ports(r.pod) or _pod_has_conflict_volumes(r.pod)
            or _pod_has_pvc(r.pod) or _pod_has_attach_volumes(r.pod)
            for r in results)
        if not track_nodes and not profiles and not stale_winners:
            return False
        overlay: Dict[str, NodeInfo] = {}
        #: affinity tracking only matters when some pod validates it or a
        #: chained predecessor's winners are invisible to this batch's mask
        aff_overlay = BatchOverlay(self.topology) \
            if profiles or stale_winners else None
        any_winners = False
        if aff_overlay is not None and stale_winners:
            # a chained predecessor's committed winners: this batch's
            # snapshot/index/mask predate them, so they participate in
            # repair exactly like earlier same-batch winners
            for w_pod, w_node in stale_winners:
                aff_overlay.add_winner(w_pod, w_node)
            any_winners = True
        # PV names earlier winners will reserve: two winners in one batch
        # must not both claim the single matching PV (the serial reference
        # reserves via AssumePodVolumes between scheduleOne iterations)
        taken_pvs: set = set()
        empty_profile = AffinityProfile()
        reassigner = self._make_reassigner(batch, stale_winners)

        def overlay_node(name: str) -> Optional[NodeInfo]:
            ni = overlay.get(name)
            if ni is None:
                base = self.snapshot.node_infos.get(name)
                if base is None:
                    return None
                ni = base.clone()
                overlay[name] = ni
            return ni

        def node_passes(i: int, pod: Pod, name: str, has_ports: bool,
                        has_disk: bool, has_attach: bool):
            """(ok, pvs) for placing pod i on `name` given earlier winners
            — the SAME checks the kernel pick runs through below."""
            pvs_local: List[str] = []
            if _pod_has_pvc(pod):
                ni = overlay_node(name)
                if ni is None or ni.node is None:
                    return False, pvs_local
                found = self.volume_binder.preview_bindings(
                    pod, ni.node, exclude=taken_pvs)
                if found is None:
                    return False, pvs_local
                pvs_local = found
            if any_winners and (has_ports or has_disk or has_attach):
                ni = overlay_node(name)
                if ni is None:
                    return False, pvs_local
                if has_ports:
                    ok, _ = preds.pod_fits_host_ports(pod, None, ni)
                    if not ok:
                        return False, pvs_local
                if has_disk:
                    ok, _ = preds.no_disk_conflict(pod, None, ni)
                    if not ok:
                        return False, pvs_local
                if has_attach:
                    # earlier winners on this node count against limits
                    for fn in self._volume_count_preds.values():
                        ok, _ = fn(pod, None, ni)
                        if not ok:
                            return False, pvs_local
            if aff_overlay is not None and any_winners and \
                    aff_overlay.conflicts(pod, profiles.get(i, empty_profile),
                                          name):
                return False, pvs_local
            return True, pvs_local

        def try_reassign(i: int, res: ScheduleResult, has_ports: bool,
                         has_disk: bool, has_attach: bool):
            """Serial re-solve: walk candidates in kernel score order until
            one passes every check. Returns that node's pvs, or None."""
            if reassigner is None:
                return None
            for cand in reassigner.candidates(i):
                if cand == res.node_name:
                    continue  # the failed pick
                ok, pvs_c = node_passes(i, res.pod, cand, has_ports,
                                        has_disk, has_attach)
                if ok:
                    res.node_name = cand
                    res.reassigned = True
                    reassigner.reassigned_any = True
                    return pvs_c
            return None

        winner_moved = False
        for i, res in enumerate(results):
            if res.node_name is None:
                continue
            pod = res.pod
            has_ports = bool(helpers.pod_host_ports(pod))
            has_disk = _pod_has_conflict_volumes(pod)
            has_attach = _pod_has_attach_volumes(pod) or _pod_has_pvc(pod)
            ok, pvs = node_passes(i, pod, res.node_name, has_ports,
                                  has_disk, has_attach)
            if not ok:
                winner_moved = True
                # the serial reference would just have picked the next-best
                # node for this pod; do that here instead of a retry round
                pvs = try_reassign(i, res, has_ports, has_disk, has_attach)
                if pvs is None:
                    res.node_name = None
                    res.retry = True
                    continue
            # record the winner in the overlays; its PVs block later pods
            taken_pvs.update(pvs)
            if track_nodes:
                bound = deepcopy_obj(pod)
                bound.spec.node_name = res.node_name
                ni = overlay_node(res.node_name)
                if ni is not None:
                    ni.add_pod(bound)
            if aff_overlay is not None:
                aff_overlay.add_winner(pod, res.node_name)
            if reassigner is not None:
                reassigner.add_winner(i, res.node_name)
            any_winners = True
        if reassigner is not None and reassigner.reassigned_any:
            # reassigned pods sit on different rows than the kernel's
            # adopted usage counted them on; no dirty row repairs that —
            # drop device usage so the next launch re-uploads host truth
            self.mirror.invalidate_usage()
        return winner_moved

    # ------------------------------------------------------------- schedule

    def schedule(self, pods: List[Pod]) -> List[ScheduleResult]:
        """Schedule a batch; results preserve input order (which is the
        queue's priority-then-FIFO order, so the scan's serial semantics
        match the reference's one-at-a-time loop).

        Device discipline (every host<->device crossing is a fixed cost
        the batch cannot amortize): one dirty-row scatter + one scan
        dispatch + one packed fetch per batch.
        When the batch needed no host-side repair, the kernel's post-batch
        usage is adopted on device (TensorMirror.adopt_usage), so the next
        batch's scatter only rewrites rows the host actually disagrees on."""
        pending = self.schedule_launch(pods)
        if pending is None:
            return []
        return self.schedule_finish(pending)

    def schedule_launch(self, pods: List[Pod],
                        chain: Optional["PendingBatch"] = None,
                        chain_seq: Optional[int] = None
                        ) -> Optional["PendingBatch"]:
        """Front half of a batch: refresh + tensorize + device dispatch.
        Returns a PendingBatch whose results are fetched by schedule_finish —
        the device scan runs while the caller does host work (the pipelined
        drain overlaps batch N+1's kernel with batch N's bind/assume).

        `chain` pipelines this launch on the previous one *before its results
        are committed*: the kernel's usage input is the chain's post-batch
        device handle instead of the mirror's. Honored only when that handle
        is provably host truth + the chain's own assignments:
          - the chain batch is residual-free (no repair can demote a winner),
          - every cache mutation since the drain's bookkeeping point came
            from the drain's own assumes (`chain_seq`: either the expected
            mutation_seq, or a callable the pipelined drain supplies that
            performs the {mutation_seq == base + own assumes} comparison
            under the cache lock — the commit thread assumes concurrently,
            so a point-in-time integer cannot express the condition),
          - device state survived (no capacity/column resize), and
          - this batch carries no host-computed static scores (they would be
            one batch staler than the sequential path).
        Gang-carrying batches chain too (both directions): the gang kernel's
        trial/commit carry means its post-batch usage holds only COMMITTED
        gangs' placements, and every committed member is assumed (bind path
        or permit-gate reservation) — losses after the chain was taken
        (atomicity demotions, permit rejects) surface through the same
        phantom/epoch machinery as singleton losses.
        Otherwise returns None and the caller must flush the pipeline and
        relaunch unchained."""
        if not pods:
            return None
        from ..utils.features import DEFAULT_FEATURE_GATE
        from .kernels.batch import pack_results, schedule_batch
        with self._stage("refresh"):
            dirty = self.cache.update_snapshot(self.snapshot)
            # volume predicates can NEVER ride a chain (PV reservations need
            # committed state); affinity CAN — its stale mask (snapshot lacks
            # the chain's uncommitted winners) is repaired post-kernel against
            # stale_winners, the same overlay that validates same-batch winners
            affinity_only = not self._has_filter_extenders() and all(
                not (_pod_has_conflict_volumes(p) or _pod_has_pvc(p)
                     or _pod_has_attach_volumes(p)) for p in pods)
            chain_intact = chain_seq is not None and (
                chain_seq() if callable(chain_seq)
                else self.cache.mutation_seq == chain_seq)
            chaining = (chain is not None
                        and (chain.residual_free or chain.affinity_chainable)
                        and DEFAULT_FEATURE_GATE.enabled(
                            "SchedulerDeviceChaining")
                        and chain_intact
                        and not self._static_likely
                        and self.mirror.device_ready()
                        and affinity_only)
            if chaining:
                self.mirror.apply_chained(self.snapshot, dirty)
                self._topology_apply(dirty)
                if dirty:
                    # keep the scorer's gate fresh on the chained path too: if
                    # this drain's own commits introduced score-contributing
                    # carriers, static_scores below turns non-None and refuses
                    # the chain — matching the sequential path's scoring
                    self.scorer.set_cluster_has_affinity_pods(
                        self.topology.has_score_carriers())
            else:
                # the dirty list is consumed either way — a chain refusal must
                # still apply it, or the mirror would never see these updates
                # (update_snapshot won't return them again)
                self.mirror.apply(self.snapshot, dirty)
                self._topology_apply(dirty)
                if dirty:
                    self.scorer.set_cluster_has_affinity_pods(
                        self.topology.has_score_carriers())
                if chain is not None:
                    return None
        with self._stage("tensorize", pods=len(pods)):
            # the cached node vectors keep every key this batch uses
            self.terms.new_batch()
            self.scorer.new_batch()
            extra_mask, profiles, extra_group = self._residual_mask(pods)
            residual_free = extra_mask is None and not any(
                helpers.pod_host_ports(p) or _pod_has_conflict_volumes(p)
                for p in pods)
            affinity_chainable = affinity_only and not any(
                helpers.pod_host_ports(p) for p in pods)
            #: gang units present -> the all-or-nothing kernel decides this
            #: batch. Gang batches CHAIN like singleton batches: the kernel's
            #: trial/commit carry isolates uncommitted (rejected-gang) state,
            #: so its post-batch usage is exactly committed-gang placements —
            #: each of which the commit path assumes (bind or reservation)
            gang_units = self.gang.batch_groups(pods) \
                if self.gang is not None else None
            batch = PodBatchTensors(pods, self.mirror, self.terms,
                                    extra_mask=extra_mask,
                                    extra_group=extra_group,
                                    seq_base=self._seq_base,
                                    stage=self._stage)
            if self.sched_metrics is not None:
                self.sched_metrics.static_mask_rows.inc(batch.n_unique_masks)
            self._seq_base += len(pods)
            w = self.scorer.weights
            batch.resource_weights[0] = w.get("LeastRequestedPriority", 1)
            batch.resource_weights[1] = w.get("BalancedResourceAllocation", 1)
            # gang batches skip the in-scan spread/topology tables — the
            # gang kernel's trial/commit scan does not carry them; repair
            # (with whole-gang demotion) validates affinity interactions,
            # matching the pre-in-scan semantics. Soft credit tables DO ride
            # gang batches (trial/committed accumulators in the gang carry —
            # what lifted the soft_gang sub-batching), and nominated
            # reservations ride both kernels as the same phantom overlay (a
            # mixed batch's singletons must not steal a preemptor's freed
            # space).
            spread_sig = None
            topo_cover = "fallback"
            if gang_units is None:
                spread_sig = self._assign_spread_groups(pods, batch)
                topo_cover = self._assign_topology_terms(pods, batch, profiles)
        with self._stage("soft_terms", pods=len(pods)):
            soft_sig = self._assign_soft_terms(pods, batch)
        spread_present = spread_sig is not None
        soft_present = soft_sig is not None
        if self._batch_fell_back:
            self._batch_fell_back = False
            if self.sched_metrics is not None:
                self.sched_metrics.topo_inscan_fallback_batches.inc()
        with self._stage("dispatch", pods=len(pods)):
            nom_dev = self._nominated_device()
            if nom_dev is not None:
                # each pod's own nominated row, from the EXACT snapshot the
                # reservation tensor was built from (pod.status and even the
                # live map may lag) — subtraction and tensor can never desync
                for i, pod in enumerate(pods):
                    row = self._nom_rows_by_key.get(pod.metadata.key())
                    if row is not None:
                        batch.nom_row[i] = row
            if self.scorer.interpod_carriers():
                with self._stage("affinity_scores", pods=len(pods)):
                    static = self.scorer.static_scores(pods, batch)
                if self.sched_metrics is not None:
                    self.sched_metrics.affinity_evaluations.inc(
                        stage="scores", route="host")
            else:
                with self._stage("static_scores", pods=len(pods)):
                    static = self.scorer.static_scores(pods, batch)
            if static is not None and self.sched_metrics is not None:
                # the computed rows; the zero row every batch has is not one
                self.sched_metrics.static_score_rows.inc(
                    static[1].shape[0] - 1)
            has_prio_ext = any(e.config.prioritize_verb for e in self.extenders)
            # hysteresis: while host-computed static scores are in play, later
            # launches refuse the chain up front instead of discarding work.
            # In-scan spread/soft tables no longer force the flush: their
            # running counts CHAIN as carried device state (gated below), so
            # the old recompute-from-batch-start invalidation is gone
            self._static_likely = static is not None or has_prio_ext
            if has_prio_ext:
                if chaining:
                    return None  # host scores would lag the uncommitted chain
                self._apply_prioritize_extenders(pods, batch, static)
            elif static is not None:
                if chaining:
                    return None
                batch.set_static_scores(*static)
            if chaining and (spread_present or soft_present) and \
                    not self._chain_carries(chain, batch, spread_sig, soft_sig):
                # the predecessor's carried counts don't structurally match
                # this batch's tables — relaunch sequentially from host truth
                return None
            if chaining and not self.mirror.device_ready():
                # tensorize grew the column axis; chain handle stale
                return None
            if gang_units is None and self.class_scan:
                # the incremental class-indexed scan: per-(template, score-row)
                # masked-score rows in the carry, one column refresh per winner
                # (kernels/batch.py _schedule_batch_classes). Spread groups,
                # soft credits, and nominated reservations ride the carry /
                # phantom overlay, so EVERY non-gang batch takes the fast path
                batch.enable_class_scan()
                if self.sched_metrics is not None:
                    self.sched_metrics.scan_classes.inc(batch.n_classes)
            if chaining:
                node_cfg, usage = self.mirror.device_cfg(), chain.new_usage
                self.chained_launches += 1
            else:
                node_cfg, usage = self.mirror.device_cfg_usage()
            sharded = False
            if gang_units is not None:
                from .kernels.gang import gang_schedule_batch
                assign_d, scores_d, new_usage = gang_schedule_batch(
                    node_cfg, usage, batch.device(),
                    self._gang_device_table(gang_units, batch), nom_dev)
            elif batch._class_tables is not None \
                    and sharding_mod.use_shard_map(self.mirror.mesh,
                                                   self.mirror.t.capacity):
                # the sharded drain's hot path: per-shard filter+score with a
                # cross-shard argmax (kernels/batch.py schedule_batch_sharded)
                # — bit-identical decisions to the single-device class scan
                from .kernels.batch import schedule_batch_sharded
                sharded = True
                if self.sched_metrics is not None:
                    self.sched_metrics.sharded_batches.inc()
                assign_d, scores_d, new_usage = schedule_batch_sharded(
                    self.mirror.mesh, node_cfg, usage,
                    batch.device(), nom_dev)
            else:
                assign_d, scores_d, new_usage = schedule_batch(
                    node_cfg, usage, batch.device(), nom_dev)
            if self.sched_metrics is not None and self.mirror.mesh is not None:
                # padding added for shard divisibility is VISIBLE (KTPU005):
                # the gauge tracks the mirror's current shard-pad rows
                self.sched_metrics.mirror_shard_pad_rows.set(
                    self.mirror.shard_pad_rows)
            return PendingBatch(pods=pods, profiles=profiles, batch=batch,
                                sharded=sharded,
                                packed=pack_results(assign_d, scores_d),
                                new_usage=new_usage,
                                residual_free=residual_free,
                                affinity_chainable=affinity_chainable,
                                chained=chaining,
                                usage_epoch=self.mirror.usage_epoch,
                                gang_units=gang_units,
                                spread_sig=spread_sig, soft_sig=soft_sig,
                                inscan_cover=(affinity_chainable
                                              and topo_cover != "fallback"))

    def _chain_carries(self, chain: "PendingBatch", batch: PodBatchTensors,
                       spread_sig: Optional[Tuple],
                       soft_sig: Optional[Tuple]) -> bool:
        """Gate for chaining THROUGH in-scan spread/soft tables.

        The kernel's spread counts and soft credit accumulators ride the
        chained usage handle ("spread" / "soft_cnt" finals), accumulating
        every in-chain winner over the ANCHOR batch's base rows. A
        successor may consume them only when its own tables resolve to
        the same STRUCTURE (group/channel/template order, zones, weights
        — the chain signatures), so slot g/s means the same thing on both
        sides. When the gate passes, this batch's freshly computed base
        rows are REPLACED with the chain predecessor's (transitively the
        anchor's): commits landing mid-chain fold those same winners into
        freshly computed rows, and anchor-base + chained-counts already
        accounts for every one of them exactly once — the sum equals the
        sequential path's recompute, which is what the chained-vs-
        unchained spread parity test pins."""
        nu = chain.new_usage
        if not isinstance(nu, dict):
            return False
        if spread_sig is not None and (
                chain.spread_sig != spread_sig or "spread" not in nu):
            return False
        if soft_sig is not None and (
                chain.soft_sig != soft_sig or "soft_cnt" not in nu):
            return False
        if spread_sig is not None:
            batch.spread_nz = chain.batch.spread_nz
            batch.spread_zone = chain.batch.spread_zone
            batch.spread_zinit = chain.batch.spread_zinit
        if soft_sig is not None:
            batch.soft_base = chain.batch.soft_base
        return True

    def schedule_finish(self, pending: "PendingBatch") -> List[ScheduleResult]:
        """Back half: fetch results, host repair, adopt chained usage."""
        from .kernels.batch import unpack_results
        with self._stage("scan_wait", pods=len(pending.pods)) as scan_wait:
            assign, scores = unpack_results(pending.packed)
        if pending.sharded and self.sched_metrics is not None:
            # the fetch drains the cross-shard argmax pipeline: this is
            # the wall time spent synchronizing the mesh for this batch
            self.sched_metrics.shard_sync_seconds.observe(scan_wait.seconds)
        out: List[ScheduleResult] = []
        for i, pod in enumerate(pending.pods):
            row = int(assign[i])
            name = self.mirror.name_of.get(row) if row >= 0 else None
            out.append(ScheduleResult(pod, name, float(scores[i])))
        if pending.phantom:
            # the chained-in usage counted winners the predecessor later
            # lost: an unassigned pod may have been starved by that phantom
            # space — retry instead of parking as unschedulable (the next
            # cycle launches unchained from repaired host truth)
            for r in out:
                if r.node_name is None:
                    r.retry = True
        moved = False
        with self._stage("repair", pods=len(pending.pods)):
            if not (pending.inscan_cover and not pending.stale_winners):
                moved = self._repair_batch(
                    out, pending.profiles, pending.stale_winners,
                    # no serial reassignment for gang batches: the
                    # reassigner is blind to the gang's ICI-domain pin, so
                    # a "repaired" member could land outside the slice —
                    # demote-and-retry instead, and atomicity below
                    # demotes its gang with it
                    batch=None if pending.gang_units else pending.batch)
            # else: the kernel's in-scan tables already enforced every
            # in-batch (anti-)affinity interaction (both directions +
            # waived co-location) and the batch carries no ports/volumes/
            # extenders — the overlay walk would re-prove what the scan
            # decided
            if pending.gang_units:
                self._enforce_gang_atomicity(out, pending.gang_units)
        if moved and pending.batch.anti_dom is not None:
            # the in-scan (anti-)affinity counters counted a winner the
            # repair moved/demoted: pods the scan left unassigned may have
            # been blocked by that placement — retry them instead of
            # parking (the next cycle's counters reflect host truth)
            for r in out:
                if r.node_name is None:
                    r.retry = True
        if not any(r.retry for r in out):
            # every surviving assignment flows through cache.assume_pod, so
            # the chained usage matches host truth (or gets scatter-repaired).
            # The epoch is checked INSIDE adopt_usage (atomically with the
            # write): an invalidate_usage after this batch launched means
            # its usage input carries the phantom state that invalidation
            # dropped — re-adopting would resurrect it, so it is refused.
            # Only the mirror's three usage tensors are adopted — the
            # spread/soft carry finals riding new_usage exist solely for
            # the NEXT chained launch (PendingBatch.new_usage keeps them).
            self.mirror.adopt_usage(
                {k: pending.new_usage[k]
                 for k in ("used", "nonzero_used", "pod_count")},
                epoch=pending.usage_epoch)
        return out

    def _enforce_gang_atomicity(self, results: List[ScheduleResult],
                                units: list) -> None:
        """Post-repair all-or-nothing: host repair may demote individual
        members (ports/affinity/volume conflicts the kernel cannot see); a
        gang that lost ANY member binds none, and the survivors retry
        together next cycle. Kernel-level rejections (the whole gang
        already unassigned) park as unschedulable instead and are counted
        as rejected."""
        gm = self.gang
        for idxs, _tk, is_gang, _pin in units:
            if not is_gang:
                continue
            rs = [results[i] for i in idxs]
            placed = sum(1 for r in rs if r.node_name is not None)
            if 0 < placed < len(rs):
                for r in rs:
                    r.node_name = None
                    r.reassigned = False
                    r.retry = True
            elif placed == 0 and gm is not None and gm.metrics is not None:
                gm.metrics.gangs_rejected.inc()

    def _gang_device_table(self, units: list, batch: PodBatchTensors) -> dict:
        """Flattened gang-entry tensors for kernels/gang.py (entry-stream
        layout documented there). The entry axis equals the batch's padded
        pod axis, so gang batches introduce no new XLA bucket shapes;
        padding entries are their own empty units. Topology-key domain
        vectors come from the incremental topology index
        (TopologyIndex.node_domain_vector)."""
        P = batch.req.shape[0]
        N = self.mirror.t.capacity
        pod_idx = np.full((P,), -1, np.int32)
        start = np.zeros((P,), bool)
        end = np.zeros((P,), bool)
        # pads default to their own (position-numbered) unit ids; real
        # units use list order, which pad positions can never collide with
        gang_id = np.arange(P, dtype=np.int32)
        entry_dom = np.full((P,), -1, np.int32)
        pin_dom = np.full((P,), -1, np.int32)
        # capacity-aware domain feasibility inputs: the gang's in-batch
        # member count and elementwise-max member request, read by the
        # kernel at each gang's start entry (kernels/gang.py has_cap)
        need = np.zeros((P,), np.float32)
        greq = np.zeros((P, batch.req.shape[1]), np.float32)
        req_np = np.asarray(batch.req)
        dom_index: Dict[str, int] = {}
        dom_rows: List[np.ndarray] = []
        t = 0
        for u, (idxs, tk, _is_gang, pin) in enumerate(units):
            d = -1
            p_id = -1
            if tk:
                d = dom_index.get(tk, -1)
                if d < 0:
                    d = len(dom_rows)
                    dom_index[tk] = d
                    dom_rows.append(self.topology.node_domain_vector(tk)
                                    [:N].astype(np.int32))
                if pin is not None:
                    # the gang's earlier batches reserved in this domain:
                    # seed the kernel's carry so stragglers only join it.
                    # Interning handles a value no live node carries (the
                    # slice vanished) — the id matches nothing and the
                    # members wait for the permit timeout to clear the pin
                    p_id = self.topology._dom_id(tk, pin)
            unit_greq = req_np[idxs].max(axis=0) if idxs else None
            for j, i in enumerate(idxs):
                pod_idx[t] = i
                start[t] = j == 0
                end[t] = j == len(idxs) - 1
                gang_id[t] = u
                entry_dom[t] = d
                pin_dom[t] = p_id
                need[t] = len(idxs)
                greq[t] = unit_greq
                t += 1
        start[t:] = True
        end[t:] = True
        from .tensorize import _bucket
        K = _bucket(len(dom_rows), minimum=1)
        dom_tab = np.full((K, N), -1, np.int32)
        if dom_rows:
            dom_tab[:len(dom_rows)] = np.stack(dom_rows)
        from .kernels.batch import pack_inputs
        # the entry vectors cross in one buffer; dom_tab's node axis
        # shards with the mirror, by the name-keyed rule, on its own
        return pack_inputs(self.mirror.put_named, {
            "pod_idx": pod_idx, "start": start, "end": end,
            "gang_id": gang_id, "entry_dom_idx": entry_dom,
            "pin_dom": pin_dom, "need": need, "greq": greq,
            "dom_tab": dom_tab})

    def _nominated_device(self) -> Optional[dict]:
        """Aggregated nominated-pod reservations as device tensors
        ({used [N,R], count [N]}), or None when nothing is nominated.
        Cached by (nominated.version, mirror.epoch, tensor shape) — the
        mirror epoch covers node-row reuse: a deleted node's row can be
        handed to a new node, and a stale tensor would charge the old
        reservation to the wrong node. Nominations are rare so the
        rebuild+upload almost never runs. Nominees already assumed into
        the cache are excluded — their usage is real, not phantom."""
        from ..utils.features import DEFAULT_FEATURE_GATE
        if not DEFAULT_FEATURE_GATE.enabled("SchedulerNominatedReservations"):
            return None
        ver = self.nominated.version
        shape = (self.mirror.t.capacity, self.mirror.t.n_cols)
        key = (ver, self.mirror.epoch, shape)
        if key == self._nom_key:
            return self._nom_dev
        from .nodeinfo import pod_resource
        from .tensorize import COL_CPU, COL_EPH, COL_MEM, _f32_ceil
        used = None
        count = None
        rows_by_key: Dict[str, int] = {}
        for node_name, pods in self.nominated.by_node().items():
            row = self.mirror.row_of.get(node_name)
            if row is None:
                continue
            for p in pods:
                if self.cache.assigned_node(p.metadata.key()) is not None:
                    continue
                if used is None:
                    used = np.zeros(shape, np.float32)
                    count = np.zeros((shape[0],), np.float32)
                r = pod_resource(p)
                used[row, COL_CPU] += _f32_ceil(r.milli_cpu)
                used[row, COL_MEM] += _f32_ceil(r.memory)
                used[row, COL_EPH] += _f32_ceil(r.ephemeral_storage)
                for rname, v in r.scalar_resources.items():
                    used[row, self.mirror.vocab.col(rname)] += _f32_ceil(v)
                count[row] += 1.0
                rows_by_key[p.metadata.key()] = row
        if used is None:
            self._nom_dev = None
        else:
            # node-axis tensors: shard with the mirror's mesh
            self._nom_dev = {"used": self.mirror.put_named("used", used),
                             "count": self.mirror.put_named("count", count)}
        #: pod key -> reserved row, exactly as charged into _nom_dev
        self._nom_rows_by_key = rows_by_key
        self._nom_key = key
        return self._nom_dev

    # ------------------------------------------------------------ preempt

    def _fits_predicates(self, pod: Pod) -> Dict[str, object]:
        """The predicate set a victim-search fit check runs (same assembly
        as explain())."""
        all_preds = dict(preds.DEFAULT_PREDICATES)
        if _pod_has_pvc(pod) or _pod_has_attach_volumes(pod):
            all_preds.update(self._volume_count_preds)
            all_preds["NoVolumeZoneConflict"] = self._zone_conflict
            all_preds["CheckVolumeBinding"] = \
                preds.check_volume_binding_factory(self.volume_binder)
        return all_preds

    #: max candidate nodes that undergo the full clone+reprieve victim
    #: search per preempting pod (see the ranking proxy in preempt())
    PREEMPT_CANDIDATE_CAP = 100

    def preempt(self, pod: Pod):
        """Ref: generic_scheduler.go Preempt (:310-369). Returns a
        PreemptionPlan or None. Pure computation — the shell performs the
        API writes (nominate, delete victims, clear lower nominations)."""
        from . import preemption as pre
        self.refresh()
        infos = self.snapshot.node_infos
        # A standing nomination on a still-viable node blocks re-preemption:
        # the kernel's reservation tensors guarantee the freed space, so the
        # pod only needs to wait for the victim deletions to reach the cache.
        # (The reference gates on victims still carrying a DeletionTimestamp,
        # :1130-1150 — useless here because the in-process store deletes
        # instantly; without this guard a retry racing the delete events
        # re-preempts a SECOND node.) A vanished/shrunk node drops the
        # reservation and falls through to a fresh preemption.
        nn = self.nominated.node_for(pod.metadata.key())
        if nn:
            ni = infos.get(nn)
            if ni is not None and pre.node_could_ever_fit(pod, ni):
                return None
            self.nominated.delete(pod)
        if not pre.pod_eligible_to_preempt_others(pod, infos):
            return None
        # candidate rows: pod-independent constraints must pass — failures
        # preemption can't fix (ref: nodesWherePreemptionMightHelp
        # unresolvable reasons); cached vectors, no per-node python
        t = self.mirror.t
        vec = (self.terms.tolerations_vector(pod)
               & self.terms.node_selector_vector(pod)
               & t.node_ok & t.valid)
        hv = self.terms.hostname_vector(pod)
        if hv is not None:
            vec = vec & hv
        pdbs = list(self.pdb_lister())
        candidates = []
        for row in np.nonzero(vec)[0]:
            name = self.mirror.name_of.get(int(row))
            ni = infos.get(name) if name else None
            if ni is None or not pre.resource_screen(pod, ni):
                continue
            candidates.append((name, ni))
        if self.preempt_kernel:
            # batched victim-pricing kernel: all candidates tensorized at
            # once (no CAP truncation — the scan is O(N·V) device work,
            # not per-node python clones)
            return self._preempt_kernel_plan(pod, candidates, infos, pdbs)
        # serial reprieve path only: full-predicate fit closure + the
        # cluster-wide metadata its per-node clones derive from
        all_preds = self._fits_predicates(pod)

        def fits(p, meta, ni) -> bool:
            ok, _ = preds.pod_fits_on_node(p, meta, ni, all_preds)
            return ok
        base_meta = preds.PredicateMetadata(pod, infos)
        if len(candidates) > self.PREEMPT_CANDIDATE_CAP:
            self._count_capped_scan("preempt_candidates", len(candidates))
            # cost bound: the clone + reprieve loop per candidate is host
            # python (the reference absorbs full-cluster cost with 16
            # goroutines, :996); rank by a cheap proxy for pick_one_node's
            # criteria — PDB-clean first (its FIRST criterion), then
            # lowest max victim priority, then fewest lower-priority pods
            # — and search only the best CAP. A mass high-priority burst
            # over 5k full nodes stays O(CAP×pods/node) instead of
            # O(nodes×pods/node) per pod.
            prio = helpers.pod_priority(pod)

            def touches_pdb(p) -> bool:
                from ..api import labels as labelsmod
                for pdb in pdbs:
                    if pdb.metadata.namespace == p.metadata.namespace and \
                            pdb.spec.selector is not None and \
                            labelsmod.matches(pdb.spec.selector,
                                              p.metadata.labels):
                        return True
                return False

            from .nodeinfo import pod_resource
            need = pod_resource(pod)

            def proxy(item):
                """Greedy estimate of the MINIMAL victim set (lowest
                priority first until the preemptor's resources fit) and
                pick_one_node's criteria over THAT set — ranking by all
                lower-priority pods instead over-penalizes nodes whose
                minimal set is tiny (a divergence the proxy-equivalence
                fixture exposed)."""
                _, ni = item
                lower = sorted(
                    (p for p in ni.pods
                     if helpers.pod_priority(p) < prio),
                    key=helpers.pod_priority)
                free_cpu = ni.allocatable.milli_cpu \
                    - ni.requested.milli_cpu
                free_mem = ni.allocatable.memory - ni.requested.memory
                # extended scalars too (google.com/tpu): a TPU-bound
                # preemptor on cpu-rich nodes would otherwise estimate
                # empty victim sets everywhere and rank arbitrarily
                free_sc = {k: ni.allocatable.scalar_resources.get(k, 0)
                           - ni.requested.scalar_resources.get(k, 0)
                           for k in need.scalar_resources}

                def fits_now():
                    return (free_cpu >= need.milli_cpu
                            and free_mem >= need.memory
                            and all(free_sc[k] >= v for k, v in
                                    need.scalar_resources.items()))
                victims = []
                for p in lower:
                    if fits_now():
                        break
                    r = pod_resource(p)
                    free_cpu += r.milli_cpu
                    free_mem += r.memory
                    for k in free_sc:
                        free_sc[k] += r.scalar_resources.get(k, 0)
                    victims.append(p)
                has_pdb = any(touches_pdb(p) for p in victims) if pdbs \
                    else False
                prios = [helpers.pod_priority(p) for p in victims]
                return (has_pdb, max(prios, default=0),
                        sum(prios), len(victims))
            candidates.sort(key=proxy)
            candidates = candidates[:self.PREEMPT_CANDIDATE_CAP]
        else:
            self._end_inscan_streak("preempt_candidates")
        victims_map: Dict[str, Tuple[List[Pod], int]] = {}
        for name, ni in candidates:
            sel = pre.select_victims_on_node(pod, ni, infos, fits, pdbs,
                                             base_meta=base_meta)
            if sel is not None:
                victims_map[name] = sel
        node = pre.pick_one_node_for_preemption(victims_map)
        if node is None:
            return None
        victims, nviol = victims_map[node]
        return pre.PreemptionPlan(
            node_name=node, victims=victims, num_pdb_violations=nviol,
            nominated_to_clear=pre.nominated_pods_to_clear(
                pod, node, self.nominated.pods_for_node(node)))

    def _overshare_ranks(self):
        """The DRF pricing input for the victim tables: quantized
        over-share ranks per tenant, or None when no DRF account is
        installed, the flag is off, or every tenant sits at/below fair
        share (the legacy tenant-blind order in all three cases)."""
        if self.drf is None:
            return None
        from ..tenancy.drf import drf_enabled
        if not drf_enabled():
            return None
        return self.drf.overshare_ranks() or None

    def _preempt_kernel_plan(self, pod: Pod, candidates, infos, pdbs):
        """The batched path: tensorize every candidate's victims into
        band-sorted [N, V] pricing tables, run the masked prefix-sum fit
        scan + lexicographic winner on device, expand the winner's
        chosen prefix back into pods. PDB-violating victims ride the
        last-resort band; gang victims are priced as whole PodGroups."""
        from .kernels import preempt as pk
        tabs = pk.build_victim_tables(pod, candidates, infos, pdbs,
                                      unit_cache=self._preempt_unit_cache,
                                      overshare=self._overshare_ranks())
        if tabs is None:
            return None
        from . import preemption as pre
        a = tabs.arrays
        winner_d, chosen_d, _k, nviol_d = pk.price_nodes(
            a["free0"], a["cfree0"], a["need"], a["need_cnt"], a["freed"],
            a["fcnt"], a["valid"], a["pdb"], a["top"], a["psum"],
            a["gcnt"], a["startr"], a["row_valid"])
        winner = int(winner_d)
        if winner < 0:
            return None
        victims = tabs.expand(winner, np.asarray(chosen_d[winner]))
        if not victims:
            return None
        node = tabs.names[winner]
        return pre.PreemptionPlan(
            node_name=node, victims=victims,
            num_pdb_violations=int(nviol_d[winner]),
            nominated_to_clear=pre.nominated_pods_to_clear(
                pod, node, self.nominated.pods_for_node(node)))

    def preempt_gang(self, members: List[Pod], min_member: int,
                     topology_key: str):
        """Whole-gang preemption: price `min_member` member placements
        against every ICI domain at once (kernels/preempt.py
        price_domains) and return a GangPreemptionPlan — the victims to
        evict plus a nomination per member spread across the winning
        domain's freed nodes, so the nominated-reservation overlay
        shields the whole slice until the gang lands. Pure computation;
        the shell performs the API writes. Returns None when no domain
        can ever hold the gang."""
        if not members or min_member < 1:
            return None
        from . import preemption as pre
        from .kernels import preempt as pk
        self.refresh()
        infos = self.snapshot.node_infos
        rep = members[0]
        t = self.mirror.t
        vec = (self.terms.tolerations_vector(rep)
               & self.terms.node_selector_vector(rep)
               & t.node_ok & t.valid)
        candidates = []
        for row in np.nonzero(vec)[0]:
            name = self.mirror.name_of.get(int(row))
            ni = infos.get(name) if name else None
            if ni is None or ni.node is None:
                continue
            dom = ni.node.metadata.labels.get(topology_key) \
                if topology_key else ""
            if dom is None:
                continue  # the label is the slice membership card
            candidates.append((name, ni, dom))
        pdbs = list(self.pdb_lister())
        tabs = pk.build_domain_tables(members, candidates, infos, pdbs,
                                      min_member,
                                      overshare=self._overshare_ranks())
        if tabs is None:
            return None
        a = tabs.arrays
        winner_d, chosen_d, nviol_d = pk.price_domains(
            a["base"], a["need"], a["dslots"], a["valid"], a["pdb"],
            a["top"], a["psum"], a["gcnt"], a["startr"], a["row_valid"])
        winner = int(winner_d)
        if winner < 0:
            return None
        chosen = np.asarray(chosen_d[winner])
        victims = tabs.expand(winner, chosen)
        # spread the members over the domain's post-eviction slots in
        # sorted node order — the nomination layout
        nominations: List[Tuple[Pod, str]] = []
        ordered = sorted(members, key=lambda p: p.metadata.key())
        it = iter(ordered)
        done = False
        for node, slots in tabs.node_slots(winner, chosen):
            for _ in range(slots):
                m = next(it, None)
                if m is None:
                    done = True
                    break
                nominations.append((m, node))
            if done:
                break
        if len(nominations) < min(min_member, len(ordered)):
            return None  # the slot estimate shrank under us; retry later
        return pre.GangPreemptionPlan(
            domain=tabs.domains[winner], victims=victims,
            nominations=nominations,
            num_pdb_violations=int(nviol_d[winner]))

    #: nodes examined per failure diagnosis; the reference pays full-cluster
    #: cost per ATTEMPT inside its parallelized hot loop, but here explain()
    #: is purely diagnostic (events), so a capped sample keeps a mass-
    #: unschedulable burst from burning minutes of host python — the
    #: aggregate message still reports the total node count
    EXPLAIN_NODE_CAP = 100

    def explain(self, pod: Pod, node_cap: Optional[int] = None) -> FitError:
        """Host-path per-node failure reasons for events/conditions.
        Diagnoses up to `node_cap` nodes (EXPLAIN_NODE_CAP default; None
        from callers means the default, 0 means unlimited)."""
        cap = self.EXPLAIN_NODE_CAP if node_cap is None else node_cap
        meta = preds.PredicateMetadata(pod, self.snapshot.node_infos)
        all_preds = self._fits_predicates(pod)
        failed: Dict[str, List[str]] = {}
        examined = 0
        total = len(self.snapshot.node_infos)
        for name, ni in self.snapshot.node_infos.items():
            if cap and examined >= cap:
                break
            examined += 1
            ok, reasons = preds.pod_fits_on_node(pod, meta, ni, all_preds)
            if not ok:
                failed[name] = reasons
        return FitError(pod=pod, failed_predicates=failed,
                        total_nodes=total,
                        not_examined=total - examined)
