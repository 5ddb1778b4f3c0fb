"""Static score compilation: the non-resource priorities as deduplicated
per-node score rows.

Ref: pkg/scheduler/algorithm/priorities/ and PrioritizeNodes
(generic_scheduler.go:672-812). The reference runs Map per (priority, node)
then Reduce per priority over the FILTERED node list. Here:

  - raw per-node vectors are compiled on the host through the same term
    cache as the filter terms (pods sharing tolerations/affinity/images hit
    the cache),
  - Reduce (NormalizeReduce / reversed / min-max / spread's zone blend) is
    vectorized numpy over the pod's statically-feasible node set,
  - the weighted sum is computed ONCE per unique score key (pods of one
    controller share terms, labels, and requests) and ships to the kernel as
    pod_batch["unique_scores"] [S, N] + ["score_idx"] [P], added on device to
    the resource scores (LeastRequested/Balanced, which the scan recomputes
    per step because they vary with in-batch usage).

Priorities whose contribution is CONSTANT over a pod's feasible nodes (e.g.
TaintToleration when no node has PreferNoSchedule taints: all 10) are
selection-invariant and dropped — ScheduleResult.score is therefore the
selection score, not the reference's absolute weighted sum. Once some node
carries a PreferNoSchedule taint (a cluster autoscaler's soft taint on its
scale-down candidates), every pod has a score key and TaintToleration a
row: one a (tolerations, mask row) of the batch, reverse-normalised over
its batch-start feasible set where upstream takes the nodes filtered at
each decision. With counts of 0 and 1 that is the same argmax: the
maximum falls to 0 only once no tainted node the row penalises fits, and
then the nodes that still fit score 10 either way.

In-batch drift: none for SelectorSpread in a singleton batch. Every
(namespace, label set) of a batch that a Service or controller selects
rides the scan as a spread group (core._assign_spread_groups): its base
counts come from SpreadIndex below, kept as binds and deletes land, its
running counts live in the scan's carry, and its score is upstream's
float64 int() exactly (kernels/batch.py _spread_exact), so no row of this
file carries it. A gang batch has no spread carry: there the row computed
here from batch-start counts stands (_spread_counts, _spread_reduce).
Preferred inter-pod affinity rides the scan's credit tables while its
term union fits them and is frozen at batch start past that (counted:
core._count_inscan_fallback). Hard (anti-)affinity stays exact via the
in-scan tables and core._repair_batch.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..api import helpers, labels as labelsmod, wellknown
from ..api.core import Pod
from ..api.meta import controller_ref
from . import priorities as prios
from .nodeinfo import NodeInfo
from .tensorize import (NodeVectorCache, TensorMirror, TermCompiler,
                        _canon_tolerations)

MAXP = float(prios.MAX_PRIORITY)


def interpod_reduce(raw: np.ndarray, fits: np.ndarray
                    ) -> Optional[np.ndarray]:
    """InterPodAffinityPriority's normalisation over the node axis
    (prios.minmax_normalize): MaxPriority * (raw - min) / (max - min),
    floored, with min and max over the feasible nodes and both from 0.
    None where every feasible node has one count: the row is then one
    value over them (0, or 10 where the count is positive), which moves
    no argmax over a feasible set that only shrinks inside a batch, so
    no row is computed or shipped for it."""
    if not fits.any():
        return None
    lo, hi = float(raw[fits].min()), float(raw[fits].max())
    if lo == hi:
        return None
    mn, mx = min(0.0, lo), max(0.0, hi)
    return np.floor(MAXP * (raw - mn) / (mx - mn))


def _canon_preferred_node_affinity(pod: Pod) -> Tuple:
    aff = pod.spec.affinity
    if not aff or not aff.node_affinity:
        return ()
    return tuple(
        (t.weight,
         tuple((r.key, r.operator, tuple(r.values))
               for r in t.preference.match_expressions),
         tuple((r.key, r.operator, tuple(r.values))
               for r in t.preference.match_fields))
        for t in aff.node_affinity.preferred_during_scheduling_ignored_during_execution)


def _canon_pod_affinity(pod: Pod) -> Tuple:
    """Canonical form of the pod's preferred (anti-)affinity terms — part of
    the static-score dedupe key (scorer rows are shared across pods whose
    affinity terms, labels, and namespace coincide)."""
    aff = pod.spec.affinity
    if not aff:
        return ()

    def canon_weighted(terms):
        out = []
        for wt in terms or []:
            t = wt.pod_affinity_term
            sel = labelsmod.canonical_selector(t.label_selector) \
                if t.label_selector is not None else None
            out.append((wt.weight, sel, t.topology_key,
                        tuple(sorted(t.namespaces))))
        return tuple(out)

    pa = canon_weighted(
        aff.pod_affinity.preferred_during_scheduling_ignored_during_execution
        if aff.pod_affinity else None)
    paa = canon_weighted(
        aff.pod_anti_affinity.preferred_during_scheduling_ignored_during_execution
        if aff.pod_anti_affinity else None)
    return (pa, paa)


def _has_preferred_pod_affinity(pod: Pod) -> bool:
    aff = pod.spec.affinity
    return bool(aff and (
        (aff.pod_affinity and
         aff.pod_affinity.preferred_during_scheduling_ignored_during_execution) or
        (aff.pod_anti_affinity and
         aff.pod_anti_affinity.preferred_during_scheduling_ignored_during_execution)))


def _zone_of(ni: NodeInfo) -> str:
    return ni.node.metadata.labels.get(wellknown.LABEL_ZONE, "")


def _node_flags(ni: NodeInfo) -> Tuple[bool, bool, bool]:
    """What a priority needs some node to have before it can tell nodes
    apart: a PreferNoSchedule taint, the prefer-avoid annotation, images."""
    return (any(t.effect == "PreferNoSchedule" for t in ni.taints),
            prios.PREFER_AVOID_PODS_ANNOTATION in ni.node.metadata.annotations,
            bool(ni.image_sizes))


class SpreadIndex:
    """SelectorSpread's counts, kept as binds and deletes land:
    (namespace, label set) -> node -> pods of that label set on the node
    that are not terminating. A spread group's base row is the sum over
    the label sets its selectors match: O(its bound pods), where
    prios.selector_spread_map walks every pod of every node.

    Switched on by the first batch that carries a spread group
    (`activate`: one pass over the snapshot, counted as rows walked);
    from then on `apply` follows the cache's dirty list beside
    TopologyIndex.apply, diffing a dirty node's pods by (key,
    resourceVersion) as that index does. A cluster no Service selects a
    pod of never pays for it."""

    def __init__(self):
        self.active = False
        #: node -> {pod key: (resourceVersion, label-set key or None)}
        self._nodes: Dict[str, Dict[str, Tuple]] = {}
        #: (namespace, sorted label items) -> {node: count}
        self._counts: Dict[Tuple, Dict[str, int]] = {}
        #: (namespace, label item) -> the label-set keys that hold it
        self._by_item: Dict[Tuple, set] = {}
        #: namespace -> every label-set key in it
        self._by_ns: Dict[str, set] = {}
        #: node rows visited to (re)build counts: the walk this index
        #: replaces, so 0 after the pass that switches it on
        self.rows_walked = 0

    def activate(self, snapshot) -> None:
        if self.active:
            return
        self.active = True
        names = list(snapshot.node_infos)
        self.rows_walked += len(names)
        self.apply(snapshot, names)

    def apply(self, snapshot, dirty_names) -> None:
        if not self.active:
            return
        for name in dirty_names:
            ni = snapshot.node_infos.get(name)
            have = self._nodes.get(name)
            if ni is None or ni.node is None:
                if have is not None:
                    for key in list(have):
                        self._sub(name, have, key)
                    del self._nodes[name]
                continue
            if have is None:
                have = self._nodes[name] = {}
            fresh = {p.metadata.key(): p for p in ni.pods}
            for key in list(have):
                p = fresh.get(key)
                if p is None or p.metadata.resource_version != have[key][0]:
                    self._sub(name, have, key)
            for key, p in fresh.items():
                if key not in have:
                    self._add(name, have, key, p)

    def _add(self, node: str, have: dict, key: str, pod: Pod) -> None:
        lkey = None
        if pod.metadata.deletion_timestamp is None:
            ns = pod.metadata.namespace
            lkey = (ns, tuple(sorted(pod.metadata.labels.items())))
            per_node = self._counts.get(lkey)
            if per_node is None:
                per_node = self._counts[lkey] = {}
                self._by_ns.setdefault(ns, set()).add(lkey)
                for item in lkey[1]:
                    self._by_item.setdefault((ns, item), set()).add(lkey)
            per_node[node] = per_node.get(node, 0) + 1
        have[key] = (pod.metadata.resource_version, lkey)

    def _sub(self, node: str, have: dict, key: str) -> None:
        _, lkey = have.pop(key)
        if lkey is None:
            return
        per_node = self._counts[lkey]
        left = per_node[node] - 1
        if left:
            per_node[node] = left
            return
        del per_node[node]
        if not per_node:
            ns = lkey[0]
            del self._counts[lkey]
            self._by_ns[ns].discard(lkey)
            for item in lkey[1]:
                self._by_item[(ns, item)].discard(lkey)

    def counts(self, namespace: str, selectors) -> Dict[str, int]:
        """node -> pods on it, in `namespace` and not terminating, whose
        labels satisfy EVERY one of `selectors` (selector_spreading.go
        countMatchingPods). The label sets to test are those filed under
        an item some selector requires; selectors that require none test
        every label set of the namespace."""
        item = prios.required_item(selectors)
        cands = self._by_item.get((namespace, item), ()) \
            if item is not None else self._by_ns.get(namespace, ())
        total: Dict[str, int] = {}
        for lkey in cands:
            lbls = dict(lkey[1])
            if all(sel(lbls) for sel in selectors):
                for node, c in self._counts[lkey].items():
                    total[node] = total.get(node, 0) + c
        return total


class ScoreCompiler:
    """Builds the static [P, N] score matrix for a batch."""

    def __init__(self, mirror: TensorMirror, terms: TermCompiler,
                 listers: Optional[prios.SpreadListers] = None,
                 weights: Optional[Dict[str, int]] = None,
                 hard_pod_affinity_weight: int = prios.HARD_POD_AFFINITY_WEIGHT,
                 topology=None):
        self.mirror = mirror
        self.terms = terms
        #: scheduler/topology.py TopologyIndex — when present, inter-pod
        #: affinity scoring is count-matrix gathers instead of the
        #: O(existing pods × terms) python scan per template
        self.topology = topology
        self.listers = listers
        self.weights = dict(weights if weights is not None
                            else prios.DEFAULT_PRIORITY_WEIGHTS)
        self.hard_pod_affinity_weight = hard_pod_affinity_weight
        #: the mirror epoch the zone ids and the three flags (node side,
        #: all of them) are true for
        self._epoch = -1
        self._vec_cache = NodeVectorCache(mirror, np.float32, "scores")
        self._zone_ids: Optional[np.ndarray] = None
        self._n_zones = 1
        #: per row, the zone label its zone id was numbered from (None:
        #: the row held no node), and whether it holds each property of
        #: _node_flags; _flag_counts is the rows that do, so a flag is
        #: "count > 0" without a walk
        self._row_zone: List[Optional[str]] = []
        self._row_flags = np.zeros((3, 0), bool)
        self._flag_counts = np.zeros((3,), np.int64)
        self._any_prefer_taints = False
        self._any_avoid_annotations = False
        self._any_images = False
        #: the in-scan spread groups' base counts (core keeps it applied)
        self.spread_index = SpreadIndex()
        #: (m, mesh) -> kernels.batch.spread_round_table(m) on the device
        self._round_tables: Dict[Tuple, object] = {}
        #: the zone ids on the device, and the (rescan, mesh) they are of
        self._zone_gen = 0
        self._zone_dev: Tuple[Optional[Tuple], object] = (None, None)
        self._cluster_has_affinity_pods = False
        #: bumped by invalidate_spread_selectors (Service/RC/RS/SS
        #: events): part of the spread chain signature, so a selector
        #: source changing mid-chain refuses the chained spread carry
        self.spread_sel_gen = 0

    def set_weights(self, weights: Dict[str, int],
                    hard_pod_affinity_weight: Optional[int] = None) -> None:
        """Install Policy weights (ref: CreateFromConfig applying
        policy.Priorities); invalidates the static-vector cache."""
        self.weights = dict(weights)
        if hard_pod_affinity_weight is not None:
            self.hard_pod_affinity_weight = hard_pod_affinity_weight
        self._epoch = -1
        self._vec_cache.clear()

    # ------------------------------------------------------- cached vectors

    def _refresh_epoch(self) -> None:
        """Bring the zone ids and the three "some node has ..." flags to
        the mirror's epoch by the rows whose node side was written since
        (a bind writes none): a row that kept its zone label leaves the
        numbering alone and moves the flags' counts by its before and
        after. A row whose label differs from the one recorded for it (a
        new node's always does), a removed row, a resize, or
        REBUILD_SHARE of the rows rescans everything, so zone ids and
        _n_zones are always what a full scan numbers."""
        m = self.mirror
        if self._epoch >= m.stamp_epoch("node"):
            return
        rows = m.rows_since(self._epoch, "node") if self._epoch >= 0 \
            and len(self._row_zone) == m.t.capacity else None
        if rows is not None:
            rows = rows.tolist()
            for row in rows:
                ni = m.infos[row]
                if ni is None or ni.node is None \
                        or _zone_of(ni) != self._row_zone[row]:
                    rows = None
                    break
        if rows is None:
            self._rescan()
        elif rows:
            before = self._row_flags[:, rows].sum(axis=1)
            for row in rows:
                self._row_flags[:, row] = _node_flags(m.infos[row])
            self._flag_counts += self._row_flags[:, rows].sum(axis=1) - before
            m.vector_rows_recomputed.inc(len(rows))
        self._epoch = m.epoch
        self._any_prefer_taints, self._any_avoid_annotations, \
            self._any_images = (bool(c) for c in self._flag_counts > 0)

    def _rescan(self) -> None:
        """The full walk: zones numbered in row order of first sight."""
        m = self.mirror
        cap = m.t.capacity
        zone_ids = np.zeros((cap,), np.int32)
        zones: Dict[str, int] = {"": 0}
        row_zone: List[Optional[str]] = [None] * cap
        flags = np.zeros((3, cap), bool)
        for row, ni in enumerate(m.infos):
            if ni is None or ni.node is None:
                continue
            z = row_zone[row] = _zone_of(ni)
            zid = zones.get(z)
            if zid is None:
                zid = len(zones)
                zones[z] = zid
            zone_ids[row] = zid
            flags[:, row] = _node_flags(ni)
        self._zone_ids = zone_ids
        self._n_zones = len(zones)
        self._row_zone = row_zone
        self._row_flags = flags
        self._flag_counts = flags.sum(axis=1)
        self._zone_gen += 1
        m.vector_rebuilds.inc(cache="zones")
        m.vector_rows_recomputed.inc(m.n_rows)

    def _vec(self, key: Tuple, fn, reads: str) -> np.ndarray:
        return self._vec_cache.vector(key, fn, reads)

    def new_batch(self) -> None:
        """core.schedule_launch opens each batch (NodeVectorCache)."""
        self._vec_cache.new_batch()

    def _node_affinity_raw(self, pod: Pod, meta: prios.PriorityMetadata
                           ) -> Optional[np.ndarray]:
        key = ("nodeaff", _canon_preferred_node_affinity(pod))
        if not key[1]:
            return None
        return self._vec(key, lambda ni: prios.node_affinity_map(pod, meta, ni),
                         reads="node")

    def _taint_raw(self, pod: Pod, meta: prios.PriorityMetadata
                   ) -> Optional[np.ndarray]:
        if not self._any_prefer_taints:
            return None  # all counts 0 -> reversed reduce gives constant 10
        key = ("tainttol", _canon_tolerations(pod))
        return self._vec(key, lambda ni: prios.taint_toleration_map(pod, meta, ni),
                         reads="node")

    def _image_raw(self, pod: Pod, meta: prios.PriorityMetadata
                   ) -> Optional[np.ndarray]:
        if not self._any_images:
            return None  # no node reports images -> all zeros
        images = tuple(sorted({c.image for c in pod.spec.containers if c.image}))
        if not images:
            return None
        key = ("img", images)
        return self._vec(key, lambda ni: prios.image_locality_map(pod, meta, ni),
                         reads="node")

    def _avoid_raw(self, pod: Pod, meta: prios.PriorityMetadata
                   ) -> Optional[np.ndarray]:
        if not self._any_avoid_annotations:
            return None  # constant 10 everywhere
        ref = controller_ref(pod.metadata)
        if ref is None or ref.kind not in ("ReplicationController", "ReplicaSet"):
            return None
        key = ("avoid", ref.kind, ref.name)
        return self._vec(key, lambda ni: prios.node_prefer_avoid_map(pod, meta, ni),
                         reads="node")

    def _spread_counts(self, pod: Pod, meta: prios.PriorityMetadata
                       ) -> Optional[np.ndarray]:
        if not meta.pod_selectors:
            return None
        # selectors derive from the pod's owning service/controller; key by
        # namespace + its labels (pods of one controller share both), and
        # by the generation of the selector sources: the vector outlives
        # an epoch, and a Service event changes what it counts
        key = ("spread", pod.metadata.namespace,
               tuple(sorted(pod.metadata.labels.items())),
               self.spread_sel_gen)
        return self._vec(key, lambda ni: prios.selector_spread_map(pod, meta, ni),
                         reads="pods")

    # ------------------------------------------------------------- compile

    def _pod_score_key(self, pod: Pod,
                       kernel_spread: bool = False) -> Optional[Tuple]:
        """Canonical key of everything that can make this pod's static score
        row differ from another pod's — None when no priority can contribute
        (the common resource-only case). Pods from one controller share the
        key, so rows are computed once per controller, not once per pod.
        `kernel_spread`: the pod rides an in-scan spread group, so
        SelectorSpread asks no row of this file for it."""
        w = self.weights
        parts = []
        contributes = False
        if w.get("NodeAffinityPriority"):
            k = _canon_preferred_node_affinity(pod)
            parts.append(k)
            contributes = contributes or bool(k)
        if w.get("TaintTolerationPriority") and self._any_prefer_taints:
            parts.append(_canon_tolerations(pod))
            contributes = True
        if w.get("ImageLocalityPriority") and self._any_images:
            images = tuple(sorted({c.image for c in pod.spec.containers
                                   if c.image}))
            parts.append(images)
            contributes = contributes or bool(images)
        if w.get("NodePreferAvoidPodsPriority") and self._any_avoid_annotations:
            ref = controller_ref(pod.metadata)
            if ref is not None and ref.kind in ("ReplicationController",
                                                "ReplicaSet"):
                parts.append((ref.kind, ref.name))
                contributes = True
            else:
                parts.append(None)
        spread_or_interpod = False
        if w.get("SelectorSpreadPriority") and self.listers is not None \
                and not kernel_spread \
                and self._pod_has_spread_selectors(pod):
            spread_or_interpod = True
        if w.get("InterPodAffinityPriority") and (
                _has_preferred_pod_affinity(pod) or
                self._cluster_has_affinity_pods):
            spread_or_interpod = True
        if spread_or_interpod:
            parts.append((pod.metadata.namespace,
                          tuple(sorted(pod.metadata.labels.items())),
                          _canon_pod_affinity(pod)))
            contributes = True
        if not contributes:
            return None
        return tuple(parts)

    def invalidate_spread_selectors(self) -> None:
        """A selector source changed. The scheduler shell calls this on
        Service/RC/RS/StatefulSet informer events (the same events that
        move parked pods back to active): the listers file the selectors
        by item and remember each label set's answer, which reads the
        sources and not the nodes, so without this a Service created
        mid-run would leave its label sets remembered as selector-less
        and silently skip spread scoring. The generation is part of the
        key of every cached spread-count vector and of the spread chain
        signature, so those start over as well."""
        if self.listers is not None:
            self.listers.invalidate()
        self.spread_sel_gen += 1

    def _pod_has_spread_selectors(self, pod: Pod) -> bool:
        """SelectorSpread contributes only when some service/controller
        selector matches the pod; without one, the whole (ns, labels)
        score-key component — and its per-template fits_row +
        PriorityMetadata work — is dead weight. A lookup by label item
        that the listers remember (prios.SpreadListers), so a
        selector-less 16k-pod burst skips static scoring entirely."""
        return bool(self.listers.selectors_for_pod(pod))

    def zone_ids_device(self):
        """The zone-id vector on the device (node-axis data: it shards
        with the mirror rows), shipped once a rescan of the zones and not
        once a launch: a bind renumbers no zone."""
        key = (self._zone_gen, id(self.mirror.mesh))
        if self._zone_dev[0] != key:
            self._zone_dev = (key, self.mirror.put_named(
                "spread_zone", self._zone_ids.astype(np.int32)))
        return self._zone_dev[1]

    def spread_round_table(self):
        """kernels.batch.spread_round_table for this cluster's largest pod
        limit (bucketed, at least 128), on the device: made and shipped
        once a size and mesh, handed to every batch that carries spread
        groups."""
        from .kernels.batch import spread_round_table
        from .tensorize import _bucket
        m = self.mirror
        limit = int(m.t.max_pods.max()) if m.t.max_pods.size else 0
        key = (_bucket(limit, minimum=128), id(m.mesh))
        tab = self._round_tables.get(key)
        if tab is None:
            tab = self._round_tables[key] = m.put_named(
                "spread_tab", spread_round_table(key[0]))
        return tab

    def static_scores(self, pods: List[Pod], batch
                      ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Deduplicated static scores: (score_idx [P], unique_rows [S, N]),
        or None when no priority contributes for any pod (resource-only
        batch — the device kernel needs no static term at all).

        Each unique (score key, feasibility row) computes ONE weighted row;
        reduces normalize over the representative pod's batch-start feasible
        set (the reference normalizes over filtered nodes,
        generic_scheduler.go PrioritizeNodes). `batch` is the
        PodBatchTensors (for mask_idx/req identity and fits_row)."""
        self._refresh_epoch()
        P = len(pods)
        score_idx = np.zeros((P,), np.int32)
        rows: List[np.ndarray] = [np.zeros((self.mirror.t.capacity,),
                                           np.float32)]
        row_of: Dict[Tuple, int] = {}
        any_contrib = False
        for i, pod in enumerate(pods):
            # pods in an in-scan spread group get their spread component
            # from the kernel's running counts — the static row must not
            # double-count it; same for inter-pod affinity when the batch
            # carries in-scan soft credit tables (core._assign_soft_terms)
            kernel_spread = bool(batch.spread_gidx[i] >= 0)
            skey = self._pod_score_key(pod, kernel_spread)
            if skey is None:
                continue
            kernel_interpod = getattr(batch, "soft_dom", None) is not None
            # the feasible set (normalization domain) depends on the mask
            # row, the request columns, and the pressure flag
            key = (skey, int(batch.mask_idx[i]), batch.req[i].tobytes(),
                   bool(batch.mem_pressure_blocked[i]), kernel_spread,
                   kernel_interpod)
            u = row_of.get(key)
            if u is None:
                row = self._compute_row(pod, batch.fits_row(i),
                                        skip_spread=kernel_spread,
                                        skip_interpod=kernel_interpod)
                if row is None:
                    u = 0
                else:
                    rows.append(row)
                    u = len(rows) - 1
                row_of[key] = u
            if u:
                any_contrib = True
            score_idx[i] = u
        if not any_contrib:
            return None
        return score_idx, np.stack(rows)

    def _compute_row(self, pod: Pod, fits: np.ndarray,
                     skip_spread: bool = False,
                     skip_interpod: bool = False) -> Optional[np.ndarray]:
        """One pod's weighted static score row [N] (None = all-constant)."""
        w = self.weights
        meta = prios.PriorityMetadata(pod, self.listers)
        total: Optional[np.ndarray] = None

        def acc(vec: np.ndarray, weight: float):
            nonlocal total
            if total is None:
                total = np.zeros((self.mirror.t.capacity,), np.float32)
            total += weight * vec

        def feas_max(raw: np.ndarray) -> float:
            vals = raw[fits]
            return float(vals.max()) if vals.size else 0.0

        if w.get("NodeAffinityPriority"):
            raw = self._node_affinity_raw(pod, meta)
            if raw is not None:
                mx = feas_max(raw)
                if mx > 0:
                    acc(np.floor(MAXP * raw / mx), w["NodeAffinityPriority"])
        if w.get("TaintTolerationPriority"):
            raw = self._taint_raw(pod, meta)
            if raw is not None:
                mx = feas_max(raw)
                if mx > 0:  # reversed NormalizeReduce
                    acc(MAXP - np.floor(MAXP * raw / mx),
                        w["TaintTolerationPriority"])
        if w.get("ImageLocalityPriority"):
            raw = self._image_raw(pod, meta)
            if raw is not None and raw.any():
                acc(raw, w["ImageLocalityPriority"])  # no reduce
        if w.get("NodePreferAvoidPodsPriority"):
            raw = self._avoid_raw(pod, meta)
            if raw is not None:
                acc(raw, w["NodePreferAvoidPodsPriority"])
        if w.get("SelectorSpreadPriority") and not skip_spread:
            counts = self._spread_counts(pod, meta)
            if counts is not None and counts.any():
                acc(self._spread_reduce(counts, fits),
                    w["SelectorSpreadPriority"])
        if w.get("InterPodAffinityPriority") and not skip_interpod:
            raw = self._interpod_raw(pod)
            if raw is not None:
                row = interpod_reduce(raw, fits)
                if row is not None:
                    acc(row, w["InterPodAffinityPriority"])
        return total

    def _spread_reduce(self, counts: np.ndarray, feas: np.ndarray
                       ) -> np.ndarray:
        """CalculateSpreadPriorityReduce with zone blending
        (selector_spreading.go zoneWeighting=2/3), in upstream's float64
        and operand order (the quotient, then times MaxPriority), int()
        last: prios.selector_spread_reduce over the node axis."""
        counts = counts.astype(np.float64)
        max_count = float(counts[feas].max()) if feas.any() else 0.0
        if max_count > 0:
            node_score = MAXP * ((max_count - counts) / max_count)
        else:
            node_score = np.full_like(counts, MAXP)
        zid = self._zone_ids
        have_zones = (zid[feas] > 0).any() if feas.any() else False
        if not have_zones:
            return np.floor(node_score).astype(np.float32)
        zcounts = np.bincount(zid, weights=counts * feas,
                              minlength=self._n_zones)
        max_zone = float(zcounts[1:].max()) if self._n_zones > 1 else 0.0
        zone_of_node = zcounts[zid]
        # zone-less nodes keep the default MaxPriority zone score
        # (selector_spreading.go: zoneScore initialized to MaxPriority and
        # only recomputed for nodes with a zone id)
        zone_score = np.where((zid > 0) & (max_zone > 0),
                              MAXP * ((max_zone - zone_of_node) /
                                      max(max_zone, 1.0)),
                              MAXP)
        blended = (node_score * (1.0 - prios.ZONE_WEIGHTING)) + \
            (prios.ZONE_WEIGHTING * zone_score)
        return np.floor(blended).astype(np.float32)

    def _interpod_raw(self, pod: Pod) -> Optional[np.ndarray]:
        """Preferred inter-pod (anti-)affinity + symmetric hard credit.
        Through the topology index when available (count-matrix gathers);
        the O(existing pods × terms) python scan over the snapshot is the
        fallback and the parity oracle. Only runs when the pod or the
        cluster carries (anti-)affinity terms."""
        if not _has_preferred_pod_affinity(pod) and \
                not self._cluster_has_affinity_pods:
            return None
        if self.topology is not None:
            return self.topology.score_vector(
                pod, self.hard_pod_affinity_weight)
        node_infos = {name: self.mirror.infos[row]
                      for name, row in self.mirror.row_of.items()
                      if self.mirror.infos[row] is not None}
        raw_by_name = prios.interpod_affinity_scores(
            pod, self.hard_pod_affinity_weight, node_infos)
        if not any(raw_by_name.values()):
            return None
        raw = np.zeros((self.mirror.t.capacity,), np.float32)
        for name, v in raw_by_name.items():
            raw[self.mirror.row_of[name]] = v
        return raw

    def set_cluster_has_affinity_pods(self, flag: bool) -> None:
        self._cluster_has_affinity_pods = flag

    def interpod_carriers(self) -> bool:
        """True while InterPodAffinityPriority is weighted and some bound
        pod carries a term that can credit it: every template of a batch
        then gets its raw row computed (core times that as
        affinity_scores)."""
        return bool(self.weights.get("InterPodAffinityPriority")) \
            and self._cluster_has_affinity_pods
