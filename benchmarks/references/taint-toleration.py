"""The plain reference of `dedicated-pools-5000n-taints`: harness/reference.py
extended by one predicate and one priority, as a serial scheduler (the
v1.15 default provider) computes them for pods that tolerate their own
pool's taint and require its label, and imported from nowhere in the
program.

  fit     PodToleratesNodeTaints: every taint of the node with effect
          NoSchedule or NoExecute is tolerated by one of the pod's
          tolerations, as upstream's ToleratesTaint reads one (an effect,
          where given, equal to the taint's; a key, where given, equal;
          operator `Equal` or empty: the values equal; `Exists`: any
          value; any other operator tolerates nothing). And
          PodMatchNodeSelector's required node-affinity side, with
          benchmarks/references/node-affinity.py's own term logic pointed
          at the pool label `dedicated` in place of the zone label: terms
          ORed, a term's `In` expressions ANDed, a node without the label
          satisfying none.
  score   TaintTolerationPriority, weight 1 (priorities.
          DEFAULT_PRIORITY_WEIGHTS): per node, the count of its
          PreferNoSchedule taints that none of the pod's tolerations with
          an empty or PreferNoSchedule effect tolerates; then upstream's
          NormalizeReduce(MaxPriority, reverse=true) over the fitting
          nodes in int64, 10 - 10 * count // maxCount, and 10 on every
          node where maxCount is 0. Added to the base's LeastRequested +
          BalancedResourceAllocation. NodeAffinityPriority is 0 on every
          node (no preferred term), as in node-affinity.py.

What it answers for beyond node-affinity.py's whitelist: node
`spec.taints[].{key, value, effect}` and pod `spec.tolerations[].{key,
operator, value, effect}`. Anything else (`tolerationSeconds`, a taint's
`timeAdded`, `spec.unschedulable`, a node-affinity key other than the
pool label, set-up objects) is refused with its path.

`extra_words`: 2 where the pod names a pool, the node's pool id for the
mask and the static score row the chip adds (32.0 B a pod and node).
Counts, never bytes: harness/roofline.py keeps the byte model.

`precision` below exact computes the base's two priorities in that
precision and leaves the predicate and the taint's integer score as
they are; `WITHOUT_TAINT_PRIORITY` (`--precision no-taint-priority` for
harness/control.py) keeps upstream's arithmetic and gives
TaintTolerationPriority the weight 0: the control that shows the row is
load-bearing.
"""

import numpy as np

from harness import cluster
from harness import reference as base

POOL = "dedicated"
MAX_PRIORITY = base.MAX_PRIORITY
HARD_EFFECTS = ("NoSchedule", "NoExecute")
SOFT_EFFECT = "PreferNoSchedule"
#: the control's rung that drops the priority this file adds
WITHOUT_TAINT_PRIORITY = "no-taint-priority"

#: node-affinity.py as a private copy of its own (the loader gives every
#: call a fresh module), its label pointed at the pool label: the same
#: required-term logic, whitelist and replay count, not a second copy
_terms = cluster.load_named("references", "node-affinity")
_terms.ZONE = POOL


def tolerates(toleration, taint):
    """Upstream's Toleration.ToleratesTaint over (key, operator, value,
    effect) and (key, value, effect)."""
    key, operator, value, effect = toleration
    t_key, t_value, t_effect = taint
    if effect and effect != t_effect:
        return False
    if key and key != t_key:
        return False
    if operator in ("", "Equal"):
        return value == t_value
    return operator == "Exists"


def _taints(node):
    return [(t.get("key", ""), t.get("value", ""), t.get("effect", ""))
            for t in (node.get("spec") or {}).get("taints") or []]


class PodFacts(_terms.PodFacts):
    __slots__ = ("tolerations",)
    reads = base.extended(_terms.PodFacts.reads, {
        "spec": {"tolerations"},
        "spec.tolerations": {"key", "operator", "value", "effect"}})

    def __init__(self, manifest):
        super().__init__(manifest)
        #: (key, operator, value, effect) of each toleration, sorted
        self.tolerations = tuple(sorted(
            (t.get("key", ""), t.get("operator", ""), t.get("value", ""),
             t.get("effect", ""))
            for t in manifest["spec"].get("tolerations") or []))

    @property
    def extra_words(self):
        """node-affinity.py's pool word, and the static score row's."""
        return super().extra_words + 1


class Reference(_terms.Reference):
    Facts = PodFacts
    reads = base.extended(_terms.Reference.reads, {
        "spec": {"taints"},
        "spec.taints": {"key", "value", "effect"}})

    def __init__(self, nodes, precision="exact", objects=()):
        #: TaintTolerationPriority's weight
        self.taint_weight = 1
        if precision == WITHOUT_TAINT_PRIORITY:
            precision, self.taint_weight = "exact", 0
        super().__init__(nodes, precision, objects)
        #: every distinct (key, value, effect) and [N] the nodes carrying
        #: it; taints never change in a replay
        self.taints = {}
        for row, n in enumerate(nodes):
            for taint in _taints(n):
                self.taints.setdefault(
                    taint, np.zeros(len(nodes), bool))[row] = True
        #: tolerations -> [N] fit by taints; [N] intolerable soft taints
        self._tolerated = {}
        self._soft = {}
        #: (pod, fits) of the last fits(): judge and decide ask scores()
        #: for the same pod straight after, with nothing bound between
        self._last_fits = (None, None)

    def tolerated(self, tolerations):
        """[N] nodes whose NoSchedule and NoExecute taints are all
        tolerated."""
        ok = self._tolerated.get(tolerations)
        if ok is None:
            ok = np.ones(len(self.names), bool)
            for taint, on in self.taints.items():
                if taint[2] in HARD_EFFECTS and not any(
                        tolerates(t, taint) for t in tolerations):
                    ok &= ~on
            self._tolerated[tolerations] = ok
        return ok

    def soft_counts(self, tolerations):
        """[N] int64 the PreferNoSchedule taints that no toleration with
        an empty or PreferNoSchedule effect tolerates."""
        count = self._soft.get(tolerations)
        if count is None:
            prefer = [t for t in tolerations if t[3] in ("", SOFT_EFFECT)]
            count = np.zeros(len(self.names), np.int64)
            for taint, on in self.taints.items():
                if taint[2] == SOFT_EFFECT and not any(
                        tolerates(t, taint) for t in prefer):
                    count += on
            self._soft[tolerations] = count
        return count

    def fits(self, pod):
        ok = super().fits(pod) & self.tolerated(pod.tolerations)
        self._last_fits = (pod, ok)
        return ok

    def taint_scores(self, pod):
        """[N] int64 TaintTolerationPriority, reduced over the nodes that
        fit the pod now."""
        fit = self._last_fits[1] if self._last_fits[0] is pod \
            else self.fits(pod)
        count = self.soft_counts(pod.tolerations)
        max_count = int(count[fit].max()) if fit.any() else 0
        if max_count == 0:
            return np.full(len(self.names), MAX_PRIORITY, np.int64)
        return MAX_PRIORITY - MAX_PRIORITY * count // max_count

    def scores(self, pod):
        s = super().scores(pod)
        if self.taint_weight:
            s = s + self.taint_weight * self.taint_scores(pod)
        return s

    def bind(self, pod, node_name):
        super().bind(pod, node_name)
        self._last_fits = (None, None)

    @classmethod
    def replay(cls, nodes, pods_in_order, bound_node, precision="exact",
               objects=()):
        """node-affinity.py's replay, its count of pods outside their
        label's values named for the pools (limit 0; each is also a bind
        that does not fit), and the binds on nodes that carry a
        PreferNoSchedule taint (no limit: what the priority holds down)."""
        out = super().replay(nodes, pods_in_order, bound_node, precision,
                             objects)
        out["pods_outside_their_pools"] = out.pop("pods_outside_their_zones")
        soft = {n["metadata"]["name"] for n in nodes
                if any(t[2] == SOFT_EFFECT for t in _taints(n))}
        out["binds_on_soft_tainted_nodes"] = sum(
            1 for m in pods_in_order
            if bound_node.get(m["metadata"]["name"]) in soft)
        return out


replay = Reference.replay
