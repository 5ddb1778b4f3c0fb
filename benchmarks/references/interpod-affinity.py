"""The plain reference of `sched-perf-5000n-podaffinity`:
harness/reference.py extended by one predicate and one priority, as a
serial scheduler computes them for pods that carry a required
podAffinity term on the zone label, and imported from nowhere in the
program.

  fit     MatchInterPodAffinity, the affinity side: for each required
          term of the pod, some pod bound before it that the term's
          matchLabels select sits in the node's zone. The waiver
          (predicates.go, "the first pod of a collection"): a term that
          matches no bound pod in any zone and matches the pod's own
          labels asks only that the node carry the zone label. A node
          without the label never fits a pod with a term.
  score   InterPodAffinityPriority, weight 1, with the hard-affinity
          symmetric weight 1 (the default provider's): the pod's own
          required terms carry no weight; every pod bound before whose
          required term selects the incoming pod's labels credits every
          node of its own zone with 1. Over the nodes that fit,
          10 * (count - min) / (max - min), int floor, 0 where
          max = min. Computed for every pod, never assumed flat.

Counts are kept a (selector, zone) and a (label set, zone), over
everything bound before, so a selector first seen on the ten-thousandth
pod counts the pods bound before it.

What it answers for beyond the base's whitelist: `spec.affinity.
podAffinity` with required terms of `labelSelector.matchLabels` and the
zone `topologyKey`. A preferred term, `namespaces`, `matchExpressions`
and any other topology key are refused.

`extra_words`: the f32 words a node, beyond the base's, that the chip
has to read to decide such a pod: for each term the presence of a match
in the node's zone (which is also where a missing label shows), and one
for the priority, the credit the node's zone holds for this pod. Counts,
never bytes: harness/roofline.py keeps the byte model.

`precision` below exact computes the priority's normalisation in that
type too (the counts reach thousands a zone; bfloat16 is exact to 256).
"""

import numpy as np

from harness import reference as base

ZONE = "failure-domain.beta.kubernetes.io/zone"
_AFF = "spec.affinity.podAffinity"
_TERM = f"{_AFF}.{base.REQUIRED}"


def selects(selector, labels):
    return all(labels.get(k) == v for k, v in selector)


class PodFacts(base.PodFacts):
    __slots__ = ("aff",)
    reads = base.extended(base.PodFacts.reads, {
        "spec.affinity": {"podAffinity"},
        _AFF: {base.REQUIRED},
        _TERM: {"labelSelector", "topologyKey"},
        f"{_TERM}.labelSelector": {"matchLabels"}})

    def __init__(self, manifest):
        super().__init__(manifest)
        #: the required affinity terms: each the sorted matchLabels items
        self.aff = []
        aff = (manifest["spec"].get("affinity") or {}).get(
            "podAffinity") or {}
        for t in aff.get(base.REQUIRED) or []:
            if t["topologyKey"] != ZONE:
                raise ValueError(
                    f"{_TERM}.topologyKey {t['topologyKey']!r}: the "
                    f"reference {type(self).__module__} holds zone "
                    f"affinity only")
            self.aff.append(tuple(sorted(
                t["labelSelector"]["matchLabels"].items())))

    @property
    def extra_words(self):
        """One f32 a node for each required term (a match in the node's
        zone) and one for the priority (the zone's credit for this
        pod)."""
        return len(self.aff) + 1


class Reference(base.Reference):
    Facts = PodFacts

    def __init__(self, nodes, precision="exact", objects=()):
        super().__init__(nodes, precision, objects)
        ids = {}
        #: [N] zone id of the node, -1 without the label
        self.zone = np.array([
            ids.setdefault(z, len(ids)) if z is not None else -1
            for z in (n["metadata"].get("labels", {}).get(ZONE)
                      for n in nodes)], np.int64)
        self.has_zone = self.zone >= 0
        self.n_zones = len(ids)
        #: label set -> [Z] pods bound with these labels, a zone
        self.bound = {}
        #: selector -> [Z] bound pods it selects / that carry it as a
        #: required affinity term, a zone
        self.selected = {}
        self.carried = {}
        #: label set -> (selectors known then, those of `selected` and
        #: those of `carried` that select it)
        self._selecting = {}
        self._fits_of = (None, None)

    def _zones(self, counts):
        """[Z] a zone -> [N] a node; 0 where the node has no label."""
        return np.where(self.has_zone, counts[self.zone], 0)

    def _selected(self, sel):
        """[Z] bound pods that `sel` selects, counted over everything
        bound before, whenever the selector is first asked for."""
        row = self.selected.get(sel)
        if row is None:
            row = self.selected[sel] = np.zeros(self.n_zones, np.int64)
            for labels, per_zone in self.bound.items():
                if selects(sel, dict(labels)):
                    row += per_zone
        return row

    def _selectors_of(self, labels):
        """(label set's key, the selectors of `selected`, those of
        `carried`) that select these labels, of those seen so far."""
        key = tuple(sorted(labels.items()))
        known = (len(self.selected), len(self.carried))
        memo = self._selecting.get(key)
        if memo is None or memo[0] != known:
            memo = self._selecting[key] = (
                known,
                [s for s in self.selected if selects(s, labels)],
                [s for s in self.carried if selects(s, labels)])
        return key, memo[1], memo[2]

    # ------------------------------------------------------------ fit

    def fits(self, pod):
        ok = super().fits(pod)
        for sel in pod.aff:
            per_zone = self._selected(sel)
            if per_zone.sum() == 0 and selects(sel, pod.labels):
                ok &= self.has_zone     # waived: the key alone
            else:
                ok &= self._zones(per_zone) > 0
        self._fits_of = (pod, ok)
        return ok

    # ---------------------------------------------------------- score

    def interpod(self, pod, ok):
        """[N] InterPodAffinityPriority of the pod, normalised over the
        nodes of `ok`. A node's count is its zone's, so min, max and the
        ten steps are taken a zone (over the zones that hold a fitting
        node; the nodes without a label are one more, with count 0) and
        handed out to the nodes."""
        credit = np.zeros(self.n_zones + 1, np.int64)
        for sel in self._selectors_of(pod.labels)[2]:
            credit[:-1] += self.carried[sel]
        if not credit.any():
            return np.zeros(len(self.names), np.int64)
        fitting = np.zeros(self.n_zones + 1, bool)
        fitting[self.zone[ok]] = True       # zone -1 is the last slot
        if not fitting.any():
            return np.zeros(len(self.names), np.int64)
        lo, hi = credit[fitting].min(), credit[fitting].max()
        if hi == lo:
            return np.zeros(len(self.names), np.int64)
        if self.precision == "exact":
            steps = (base.MAX_PRIORITY * (credit - lo).astype(np.float64)
                     / float(hi - lo)).astype(np.int64)
        else:
            t = np.float32 if self.precision == "int8" \
                else base._dtype(self.precision)
            c, lo, hi = credit.astype(t), t(lo), t(hi)
            steps = np.floor(t(base.MAX_PRIORITY) * (c - lo) / (hi - lo)) \
                .astype(np.float64).astype(np.int64)
        return steps[self.zone]

    def scores(self, pod):
        seen, ok = self._fits_of
        if seen is not pod:
            ok = self.fits(pod)
        return super().scores(pod) + self.interpod(pod, ok)

    # --------------------------------------------------------- replay

    def bind(self, pod, node_name):
        super().bind(pod, node_name)
        self._fits_of = (None, None)
        z = self.zone[self.row[node_name]]
        if z < 0:
            return      # no zone, no topology pair: it counts nowhere
        key, selecting, _ = self._selectors_of(pod.labels)
        row = self.bound.get(key)
        if row is None:
            row = self.bound[key] = np.zeros(self.n_zones, np.int64)
        row[z] += 1
        for sel in selecting:
            self.selected[sel][z] += 1
        for sel in pod.aff:
            per_zone = self.carried.get(sel)
            if per_zone is None:
                per_zone = self.carried[sel] = np.zeros(self.n_zones,
                                                        np.int64)
            per_zone[z] += 1

    @classmethod
    def replay(cls, nodes, pods_in_order, bound_node, precision="exact",
               objects=()):
        """The base's replay, and how full the fullest zone ended: CPU
        requested by the pods bound there over the CPU its nodes can
        allocate (the configuration's room: PERF.md section 4)."""
        out = super().replay(nodes, pods_in_order, bound_node, precision,
                             objects)
        zone_of = {n["metadata"]["name"]:
                   n["metadata"].get("labels", {}).get(ZONE)
                   for n in nodes}
        room, used = {}, {}
        for n in nodes:
            z = zone_of[n["metadata"]["name"]]
            room[z] = room.get(z, 0) + base.milli(
                n["status"]["allocatable"]["cpu"])
        for m in pods_in_order:
            node = bound_node.get(m["metadata"]["name"])
            if node in zone_of:
                z = zone_of[node]
                used[z] = used.get(z, 0) + sum(
                    base.milli(c.get("resources", {}).get(
                        "requests", {}).get("cpu", "0"))
                    for c in m["spec"]["containers"])
        out["fullest_zone_fill"] = max(
            (used.get(z, 0) / room[z] for z in room if room[z]),
            default=0.0)
        return out


replay = Reference.replay
