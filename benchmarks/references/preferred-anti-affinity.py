"""The plain reference of `sched-perf-5000n-preferred-antiaffinity`:
harness/reference.py extended by one priority, as a serial scheduler
(the v1.15 default provider) computes it for pods that carry preferred
pod anti-affinity on kubernetes.io/hostname, and imported from nowhere
in the program. The base keeps fit (PodFitsResources and required
hostname anti-affinity, both ways), LeastRequestedPriority and
BalancedResourceAllocation.

  score   InterPodAffinityPriority, weight 1, as
          pkg/scheduler/algorithm/priorities/interpod_affinity.go
          CalculateInterPodAffinityPriority computes it. Over the fitting
          nodes, a node's count is the sum of two parts, each over the
          pods bound before it on the same host, in int64:
            - weight of each of the incoming pod's preferred anti terms
              that selects the bound pod (processTerm on the pod's own
              terms);
            - weight of each of the bound pod's preferred anti terms that
              selects the incoming pod (the symmetry).
          Then, as the function's last two loops do: min and max both
          start at 0 (`var maxCount, minCount int64`, widened over the
          nodes by `if counts > maxCount` and `if counts < minCount`),
          and a node scores int(float64(MaxPriority) * (float64(count -
          minCount) / float64(maxCount - minCount))), the quotient first
          as upstream writes it; 0 on every node where max = min.
          Computed for every pod, never assumed flat. (The source's text
          is written down without network: v1.15 names, no line numbers.)

Counts are kept a selector and a host: the bound pods a selector
selects (counted over everything bound before whenever the selector is
first asked for) and the weight of the preferred anti terms bound pods
carry with it.

What it answers for beyond the base's whitelist: `spec.affinity.
podAntiAffinity.preferredDuringSchedulingIgnoredDuringExecution` with
`weight` and `podAffinityTerm.labelSelector.matchLabels` on the hostname
`topologyKey`. Any other topology key, `namespaces`, `matchExpressions`
and preferred (or required) pod *affinity* are refused.

Departures, each on nothing a decision of the configuration turns on:
the pod template is that of the later scheduler_perf
performance-config.yaml (config/pod-with-preferred-pod-anti-affinity.
yaml), which this v1.15 snapshot does not have; the field and the
priority are v1.15's. The tie-break among equal scores is left open, as
in the base.

`extra_words`: 1, the one count a node that the chip has to read to
decide such a pod: the credit the node's host holds for this pod, which
both directions read. Stated by rule; harness/roofline.py keeps the byte
model.

`precision` below exact computes the normalisation in that type too,
in upstream's operand order (float32 where the base's int8 rung holds
fractions in 8 bits). On this configuration's one pod shape every rung
keeps the argmax among the hosts that hold the fewest pods of the
colour, and reads correct. `WITHOUT_INTERPOD_PRIORITY` (`--precision
no-interpod-priority` for harness/control.py) keeps upstream's
arithmetic and gives InterPodAffinityPriority the weight 0: the control
that shows the priority is load-bearing.
"""

import numpy as np

from harness import reference as base

PREFERRED = "preferredDuringSchedulingIgnoredDuringExecution"
#: the control's rung that drops the priority this file adds
WITHOUT_INTERPOD_PRIORITY = "no-interpod-priority"
_ANTI = "spec.affinity.podAntiAffinity"
_TERM = f"{_ANTI}.{PREFERRED}"


def selects(selector, labels):
    return all(labels.get(k) == v for k, v in selector)


class PodFacts(base.PodFacts):
    __slots__ = ("pref_anti",)
    reads = base.extended(base.PodFacts.reads, {
        _ANTI: {PREFERRED},
        _TERM: {"weight", "podAffinityTerm"},
        f"{_TERM}.podAffinityTerm": {"labelSelector", "topologyKey"},
        f"{_TERM}.podAffinityTerm.labelSelector": {"matchLabels"}})
    #: the credit of the node's host for this pod
    extra_words = 1

    def __init__(self, manifest):
        super().__init__(manifest)
        #: the preferred anti-affinity terms: (sorted matchLabels items,
        #: weight)
        self.pref_anti = []
        anti = (manifest["spec"].get("affinity") or {}).get(
            "podAntiAffinity") or {}
        for t in anti.get(PREFERRED) or []:
            term = t["podAffinityTerm"]
            if term["topologyKey"] != base.HOSTNAME:
                raise ValueError(
                    f"{_TERM}.podAffinityTerm.topologyKey "
                    f"{term['topologyKey']!r}: the reference "
                    f"{type(self).__module__} holds hostname terms only")
            self.pref_anti.append((tuple(sorted(
                term["labelSelector"]["matchLabels"].items())),
                int(t.get("weight", 0))))


class Reference(base.Reference):
    Facts = PodFacts

    def __init__(self, nodes, precision="exact", objects=()):
        self.interpod_weight = 1
        if precision == WITHOUT_INTERPOD_PRIORITY:
            precision, self.interpod_weight = "exact", 0
        super().__init__(nodes, precision, objects)
        n = len(self.names)
        #: label set -> [N] pods bound with these labels, a host
        self.bound = {}
        #: selector -> [N] bound pods it selects / weight of the
        #: preferred anti terms with it that bound pods carry, a host
        self.selected = {}
        self.pref_carried = {}
        #: label set -> (selectors known then, those of `selected` and
        #: those of `pref_carried` that select it)
        self._selecting = {}
        self._fits_of = (None, None)
        self._zero = np.zeros(n, np.int64)

    def _selected(self, sel):
        """[N] bound pods that `sel` selects, counted over everything
        bound before, whenever the selector is first asked for."""
        row = self.selected.get(sel)
        if row is None:
            row = self.selected[sel] = np.zeros(len(self.names), np.int64)
            for labels, per_host in self.bound.items():
                if selects(sel, dict(labels)):
                    row += per_host
        return row

    def _selectors_of(self, labels):
        """(label set's key, the selectors of `selected`, those of
        `pref_carried`) that select these labels, of those seen so
        far."""
        key = tuple(sorted(labels.items()))
        known = (len(self.selected), len(self.pref_carried))
        memo = self._selecting.get(key)
        if memo is None or memo[0] != known:
            memo = self._selecting[key] = (
                known,
                [s for s in self.selected if selects(s, labels)],
                [s for s in self.pref_carried if selects(s, labels)])
        return key, memo[1], memo[2]

    # ------------------------------------------------------------ fit

    def fits(self, pod):
        ok = super().fits(pod)
        self._fits_of = (pod, ok)
        return ok

    # ---------------------------------------------------------- score

    def counts(self, pod):
        """[N] the node's count for the pod, int64, both directions."""
        count = self._zero.copy()
        for sel, weight in pod.pref_anti:
            count -= weight * self._selected(sel)
        for sel in self._selectors_of(pod.labels)[2]:
            count -= self.pref_carried[sel]
        return count

    def interpod(self, pod, ok):
        """[N] InterPodAffinityPriority of the pod over the nodes of
        `ok`, min and max from 0."""
        count = self.counts(pod)
        if not ok.any():
            return self._zero.copy()
        lo = min(0, int(count[ok].min()))
        hi = max(0, int(count[ok].max()))
        if hi == lo:
            return self._zero.copy()
        if self.precision == "exact":
            q = (count - lo).astype(np.float64) / float(hi - lo)
            return (float(base.MAX_PRIORITY) * q).astype(np.int64)
        t = np.float32 if self.precision == "int8" \
            else base._dtype(self.precision)
        q = (count - lo).astype(t) / t(hi - lo)
        return np.floor(t(base.MAX_PRIORITY) * q) \
            .astype(np.float64).astype(np.int64)

    def scores(self, pod):
        seen, ok = self._fits_of
        if seen is not pod:
            ok = self.fits(pod)
        return super().scores(pod) \
            + self.interpod_weight * self.interpod(pod, ok)

    # --------------------------------------------------------- replay

    def bind(self, pod, node_name):
        super().bind(pod, node_name)
        self._fits_of = (None, None)
        row = self.row[node_name]
        key, selecting, _ = self._selectors_of(pod.labels)
        per_host = self.bound.get(key)
        if per_host is None:
            per_host = self.bound[key] = np.zeros(len(self.names), np.int64)
        per_host[row] += 1
        for sel in selecting:
            self.selected[sel][row] += 1
        for sel, weight in pod.pref_anti:
            carried = self.pref_carried.get(sel)
            if carried is None:
                carried = self.pref_carried[sel] = np.zeros(
                    len(self.names), np.int64)
            carried[row] += weight

    @classmethod
    def replay(cls, nodes, pods_in_order, bound_node, precision="exact",
               objects=()):
        """The base's replay, and the pods on the fullest and on the
        emptiest host at the end: how evenly the soft terms spread them
        (PERF.md section 4)."""
        out = super().replay(nodes, pods_in_order, bound_node, precision,
                             objects)
        per_host = dict.fromkeys((n["metadata"]["name"] for n in nodes), 0)
        for m in pods_in_order:
            node = bound_node.get(m["metadata"]["name"])
            if node in per_host:
                per_host[node] += 1
        out["pods_on_fullest_host"] = max(per_host.values(), default=0)
        out["pods_on_emptiest_host"] = min(per_host.values(), default=0)
        return out


replay = Reference.replay
