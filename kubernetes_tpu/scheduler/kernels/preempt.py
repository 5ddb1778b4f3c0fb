"""Batched victim-pricing preemption on device.

The serial port (scheduler/preemption.py, ref generic_scheduler.go
selectVictimsOnNode + pickOneNodeForPreemption) walks one candidate node
at a time, cloning NodeInfos and re-running the full predicate oracle per
reprieve step. This module re-prices the same decision as a tensor
program over ALL candidate nodes at once:

  - each candidate node's would-be victims are tensorized into
    priority-band-sorted ``[N, V]`` unit tables (cheapest band first,
    PDB-violating units masked to a LAST-RESORT band after every clean
    unit, ties broken youngest-first then by key — the eviction order);
  - "does the preemptor fit after evicting the <=k cheapest units" is a
    masked prefix-sum scan over the sorted band axis (cumsum of freed
    resources + freed pod slots vs the preemptor's request);
  - a whole PodGroup is priced as a SINGLE unit: evicting any member
    charges the entire group (top/sum priority, cluster-wide member
    count) while freeing only the group's on-node resources — evicting
    1 of 4 workers buys nothing and the cost table says so;
  - the winner node is the reference's pickOneNodeForPreemption
    tie-break order (fewest PDB violations, lowest top-victim priority,
    lowest priority sum, fewest victims, latest start among the
    top-priority victims, first remaining) expressed as one
    lexicographic argmax over per-node cost vectors.

``price_nodes_reference`` / ``price_domains_reference`` are numpy
mirrors with the same op order and f32 arithmetic — the parity oracles
(tests/test_preempt.py randomized fixtures), in the same role
gang_schedule_reference plays for the gang kernel.

Two deliberate modeling divergences from the serial path, which
``BatchScheduler.preempt_kernel = False`` keeps as the tests' control:

  - victim sets are PREFIXES of the band order; the serial reprieve
    loop may carve non-contiguous sets when re-adding a cheap victim
    happens not to break the fit. Prefix pricing is what makes the scan
    O(N·V) tensor work instead of per-node python.
  - the fit check is resources + pod-count (after the same
    pod-independent candidate screen the serial path applies); the
    reprieve loop's full-predicate fit also sees inter-pod affinity.
    A preemptor that still cannot place after its victims terminate
    simply stays pending — the eviction was wasted, not wrong.

``price_domains`` is the whole-gang variant: candidate rows are ICI
topology DOMAINS, the fit threshold is "minMember member-slots across
the domain's nodes", and each unit's value is the member-slot delta its
eviction unlocks on its node (per-node slot curves are concave-free by
construction: freed resources only grow, so the per-node sorted unit
stream has well-defined non-negative increments and a cross-node merge
in band order keeps them additive).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...api import helpers
from ...api.core import Pod
from ...api.scheduling import pod_group_key
from ..nodeinfo import NodeInfo, pod_resource
from ..preemption import filter_pods_with_pdb_violation, _more_important

INT32_MAX = np.int32(2**31 - 1)
INT32_MIN = np.int32(-(2**31))


# ----------------------------------------------------------- host tables

@dataclass
class _Unit:
    """One evictable pricing unit on one node: a singleton pod, or a
    whole PodGroup's on-node members (charged cluster-wide)."""

    key: str                      # deterministic final tie-break
    evict: List[Pod]              # every pod this eviction takes down
    freed: np.ndarray             # [R] resources freed ON THIS NODE
    fcnt: int                     # pod slots freed on this node
    pdb: bool                     # last-resort band (budget exhausted)
    top: int                      # highest victim priority in the unit
    psum: float                   # sum of victim priorities (whole group)
    gcnt: int                     # victims charged (whole group)
    start: str                    # latest start among top-priority victims
    startr: int = 0               # global rank of `start` (filled late)
    is_group: bool = False        # whole-PodGroup unit (never cached)
    #: quantized DRF over-share rank of the unit's tenant (0 at/below
    #: fair share, or when DRF is off) — over-share tenants' units sort
    #: into a cheaper eviction band
    oshare: int = 0


@dataclass
class VictimTables:
    """Everything price_nodes consumes plus the host-side unit metadata
    needed to expand the winner's chosen prefix back into pods."""

    names: List[str]
    units: List[List[_Unit]]
    res_names: List[str]
    arrays: Dict[str, np.ndarray] = field(default_factory=dict)

    def expand(self, row: int, chosen: np.ndarray) -> List[Pod]:
        """Winner row + chosen unit mask -> ordered victim pods (band
        order, whole groups expanded in sorted-key order)."""
        out: List[Pod] = []
        for v, unit in enumerate(self.units[row]):
            if v < len(chosen) and chosen[v]:
                out.extend(sorted(unit.evict,
                                  key=lambda p: p.metadata.key()))
        return out


def _res_columns(need) -> List[str]:
    """cpu/memory always, plus the preemptor's extended scalars — the
    only columns that can gate ITS fit."""
    return ["cpu", "memory"] + sorted(need.scalar_resources)


def _res_row(res, names: Sequence[str]) -> np.ndarray:
    row = np.zeros((len(names),), np.float32)
    for i, n in enumerate(names):
        if n == "cpu":
            row[i] = res.milli_cpu
        elif n == "memory":
            row[i] = res.memory
        else:
            row[i] = res.scalar_resources.get(n, 0)
    return row


def bound_group_index(infos: Dict[str, NodeInfo]) -> Dict[str, List[Pod]]:
    """gkey -> every BOUND member across the cluster: the expansion (and
    cost) of evicting any one of them."""
    out: Dict[str, List[Pod]] = {}
    for ni in infos.values():
        for p in ni.pods:
            gk = pod_group_key(p)
            if gk is not None:
                out.setdefault(gk, []).append(p)
    return out


def _unit_oshare(pods: Sequence[Pod], overshare) -> int:
    """The unit's DRF pricing term: the MAX over-share rank among its
    victims' tenants (a group mixing tenants prices at its most
    over-share member). 0 whenever DRF is off."""
    if not overshare:
        return 0
    from ...tenancy.drf import tenant_of
    return max((overshare.get(tenant_of(p), 0) for p in pods), default=0)


def _node_units(prio: int, ni: NodeInfo, pdbs,
                group_bound: Dict[str, List[Pod]],
                res_names: Sequence[str],
                overshare=None) -> Tuple[List[_Unit], bool]:
    """The node's evictable units in band (eviction) order, plus
    whether the list is CACHEABLE: any gang member among the node's
    potential victims makes it not — both surviving group units (their
    cluster-wide expansion) and groups filtered as off-limits (a remote
    member's priority) depend on state other nodes' generations track."""
    potential = [p for p in ni.pods if helpers.pod_priority(p) < prio]
    if not potential:
        return [], True
    singles: List[Pod] = []
    groups: Dict[str, List[Pod]] = {}
    for p in potential:
        gk = pod_group_key(p)
        if gk is None:
            singles.append(p)
        else:
            groups.setdefault(gk, []).append(p)
    # a group with any member at/above the preemptor's priority is
    # off-limits entirely: its eviction would take down a pod preemption
    # may never touch
    for gk in list(groups):
        members = group_bound.get(gk, groups[gk])
        if any(helpers.pod_priority(m) >= prio for m in members):
            del groups[gk]
    # PDB accounting in the reference's order (most important first,
    # cumulative disruptionsAllowed) over this node's surviving victims
    ordered = sorted(singles + [p for ps in groups.values() for p in ps],
                     key=_more_important)
    violating, _ok = filter_pods_with_pdb_violation(ordered, pdbs)
    viol = {p.metadata.key() for p in violating}
    units: List[_Unit] = []
    for p in singles:
        pr = helpers.pod_priority(p)
        units.append(_Unit(
            key=p.metadata.key(), evict=[p],
            freed=_res_row(pod_resource(p), res_names), fcnt=1,
            pdb=p.metadata.key() in viol, top=pr, psum=float(pr), gcnt=1,
            start=p.status.start_time or "",
            oshare=_unit_oshare([p], overshare)))
    for gk, here in sorted(groups.items()):
        members = group_bound.get(gk, here)
        prios = [helpers.pod_priority(m) for m in members]
        top = max(prios)
        freed = np.zeros((len(res_names),), np.float32)
        for m in here:
            freed += _res_row(pod_resource(m), res_names)
        units.append(_Unit(
            key=f"group:{gk}", evict=list(members), freed=freed,
            fcnt=len(here), pdb=any(m.metadata.key() in viol for m in here),
            top=top, psum=float(sum(prios)), gcnt=len(members),
            start=max((m.status.start_time or "") for m, pr in
                      zip(members, prios) if pr == top),
            is_group=True, oshare=_unit_oshare(members, overshare)))
    return units, not any(pod_group_key(p) is not None for p in potential)


def _rank_and_sort(per_row: List[List[_Unit]]) -> None:
    """Assign global start-time ranks, then sort each row into the
    eviction band order: clean before PDB, most over-share tenant first
    (the DRF pricing term — 0 for every unit when DRF is off, so the
    legacy order is unchanged), cheapest priority first, youngest
    (latest start) first within a band, key as the final deterministic
    tie. This is HOST code consumed by both price_nodes and its numpy
    reference, so kernel-vs-oracle parity holds by construction."""
    starts = sorted({u.start for row in per_row for u in row})
    rank = {s: i for i, s in enumerate(starts)}
    for row in per_row:
        for u in row:
            u.startr = rank[u.start]
        row.sort(key=lambda u: (u.pdb, -u.oshare, u.top, -u.startr, u.key))


def _bucket_pow2(n: int, minimum: int = 1) -> int:
    n = max(n, minimum)
    return 1 << (n - 1).bit_length()


def build_victim_tables(pod: Pod,
                        candidates: Sequence[Tuple[str, NodeInfo]],
                        infos: Dict[str, NodeInfo], pdbs,
                        unit_cache: Optional[dict] = None,
                        overshare: Optional[Dict[str, int]] = None
                        ) -> Optional[VictimTables]:
    """Single-preemptor tables: one row per candidate node.

    `unit_cache` amortizes the host tensorize across a preemption storm:
    per-node unit lists are keyed by (node, NodeInfo.generation,
    preemptor priority) — generations bump on every pod add/remove, so
    an eviction invalidates exactly its node. Nodes carrying GROUP units
    are never cached (a sibling eviction on another node changes their
    cluster-wide expansion without touching this node's generation).
    Callers must serialize access (the shell holds _algo_lock)."""
    need = pod_resource(pod)
    res_names = _res_columns(need)
    prio = helpers.pod_priority(pod)
    group_bound = bound_group_index(infos)
    names: List[str] = []
    rows: List[List[_Unit]] = []
    free0_rows: List[np.ndarray] = []
    cfree0: List[float] = []
    res_key = tuple(res_names)
    # PDB budgets are not captured by node generations: fingerprint them
    # into the key so a DisruptionController update invalidates wholesale
    pdb_key = tuple(sorted(
        (p.metadata.key(), p.status.disruptions_allowed) for p in pdbs))
    # cached unit lists bake the DRF pricing term in: fingerprint the
    # over-share ranks so a share shift invalidates rather than reuses
    os_key = tuple(sorted(overshare.items())) if overshare else ()
    for name, ni in candidates:
        key = (name, ni.generation, prio, res_key, pdb_key, os_key)
        units = unit_cache.get(key) if unit_cache is not None else None
        if units is None:
            units, cacheable = _node_units(prio, ni, pdbs, group_bound,
                                           res_names, overshare=overshare)
            # gang members key CLUSTER-WIDE state: a sibling binding (or
            # a remote member's priority putting its group off-limits)
            # changes this node's units without touching this node's
            # generation — any gang member among the potential victims
            # makes the list uncacheable, even when no group unit
            # survived the off-limits filter
            if unit_cache is not None and cacheable:
                if len(unit_cache) > 8192:
                    unit_cache.clear()
                unit_cache[key] = units
        if not units:
            continue
        names.append(name)
        rows.append(units)
        free0_rows.append(_res_row(ni.allocatable, res_names)
                          - _res_row(ni.requested, res_names))
        cfree0.append(float(ni.allocatable.allowed_pod_number
                            - len(ni.pods)))
    if not names:
        return None
    _rank_and_sort(rows)
    N = _bucket_pow2(len(names))
    V = _bucket_pow2(max(len(r) for r in rows))
    R = len(res_names)
    t = VictimTables(names=names, units=rows, res_names=res_names)
    a = t.arrays
    a["free0"] = np.zeros((N, R), np.float32)
    a["cfree0"] = np.zeros((N,), np.float32)
    a["need"] = _res_row(need, res_names)
    a["need_cnt"] = np.float32(1.0)
    a["freed"] = np.zeros((N, V, R), np.float32)
    a["fcnt"] = np.zeros((N, V), np.float32)
    a["valid"] = np.zeros((N, V), bool)
    a["pdb"] = np.zeros((N, V), bool)
    a["top"] = np.full((N, V), INT32_MIN, np.int32)
    a["psum"] = np.zeros((N, V), np.float32)
    a["gcnt"] = np.zeros((N, V), np.int32)
    a["startr"] = np.full((N, V), -1, np.int32)
    a["row_valid"] = np.zeros((N,), bool)
    for i, units in enumerate(rows):
        a["free0"][i] = free0_rows[i]
        a["cfree0"][i] = cfree0[i]
        a["row_valid"][i] = True
        for v, u in enumerate(units):
            a["freed"][i, v] = u.freed
            a["fcnt"][i, v] = u.fcnt
            a["valid"][i, v] = True
            a["pdb"][i, v] = u.pdb
            a["top"][i, v] = u.top
            a["psum"][i, v] = u.psum
            a["gcnt"][i, v] = u.gcnt
            a["startr"][i, v] = u.startr
    return t


# ---------------------------------------------------------------- kernels

def _lexi_winner(feasible, crits):
    """Lexicographic argmin: narrow the feasible mask criterion by
    criterion (each `crits` entry is minimized; negate to maximize),
    then take the FIRST remaining row — exactly
    pickOneNodeForPreemption's narrowing loop as masked reductions."""
    m = feasible
    for vals in crits:
        if vals.dtype == jnp.float32:
            big = jnp.float32(np.inf)
        else:
            big = jnp.asarray(INT32_MAX, vals.dtype)
        best = jnp.min(jnp.where(m, vals, big))
        m = m & (vals == best)
    return jnp.where(m.any(), jnp.argmax(m), -1).astype(jnp.int32)


def _prefix_costs(chosen, pdb, top, psum, gcnt, startr):
    """Per-row cost vector of the chosen victim prefix."""
    nviol = (chosen & pdb).sum(axis=1).astype(jnp.int32)
    topv = jnp.max(jnp.where(chosen, top, INT32_MIN), axis=1)
    psumv = jnp.sum(jnp.where(chosen, psum, 0.0), axis=1)
    cntv = jnp.sum(jnp.where(chosen, gcnt, 0), axis=1).astype(jnp.int32)
    startv = jnp.max(jnp.where(chosen & (top == topv[:, None]), startr, -1),
                     axis=1).astype(jnp.int32)
    return nviol, topv, psumv, cntv, startv


@jax.jit
def price_nodes(free0, cfree0, need, need_cnt, freed, fcnt, valid, pdb,
                top, psum, gcnt, startr, row_valid):
    """[N, V] single-preemptor pricing. Returns (winner row or -1,
    chosen [N, V], k [N] victims-unit count, nviol [N])."""
    V = valid.shape[1]
    cumfreed = jnp.cumsum(freed, axis=1)
    cumcnt = jnp.cumsum(fcnt, axis=1)
    fit0 = (free0 >= need).all(axis=1) & (cfree0 >= need_cnt)
    fitk = ((free0[:, None, :] + cumfreed) >= need).all(axis=2) \
        & ((cfree0[:, None] + cumcnt) >= need_cnt)
    elig = fitk & valid
    # first fitting prefix; a node the preemptor ALREADY fits is not a
    # preemption candidate (scheduling should have placed it — the
    # serial path's everything-reprieved None)
    kidx = jnp.argmax(elig, axis=1)
    feasible = elig.any(axis=1) & ~fit0 & row_valid
    chosen = valid & (jnp.arange(V)[None, :] <= kidx[:, None]) \
        & feasible[:, None]
    nviol, topv, psumv, cntv, startv = _prefix_costs(
        chosen, pdb, top, psum, gcnt, startr)
    winner = _lexi_winner(feasible, (nviol, topv, psumv, cntv, -startv))
    return winner, chosen, (kidx + 1).astype(jnp.int32), nviol


def price_nodes_reference(a: Dict[str, np.ndarray]):
    """Numpy mirror of price_nodes — same op order, f32 throughout."""
    free0, cfree0 = a["free0"], a["cfree0"]
    need, need_cnt = a["need"], a["need_cnt"]
    freed, fcnt, valid = a["freed"], a["fcnt"], a["valid"]
    pdb, top, psum = a["pdb"], a["top"], a["psum"]
    gcnt, startr, row_valid = a["gcnt"], a["startr"], a["row_valid"]
    N, V = valid.shape
    cumfreed = np.cumsum(freed, axis=1, dtype=np.float32)
    cumcnt = np.cumsum(fcnt, axis=1, dtype=np.float32)
    fit0 = (free0 >= need).all(axis=1) & (cfree0 >= need_cnt)
    fitk = ((free0[:, None, :] + cumfreed) >= need).all(axis=2) \
        & ((cfree0[:, None] + cumcnt) >= need_cnt)
    elig = fitk & valid
    kidx = np.argmax(elig, axis=1)
    feasible = elig.any(axis=1) & ~fit0 & row_valid
    chosen = valid & (np.arange(V)[None, :] <= kidx[:, None]) \
        & feasible[:, None]
    nviol = (chosen & pdb).sum(axis=1).astype(np.int32)
    topv = np.max(np.where(chosen, top, INT32_MIN), axis=1)
    psumv = np.sum(np.where(chosen, psum, np.float32(0.0)), axis=1,
                   dtype=np.float32)
    cntv = np.sum(np.where(chosen, gcnt, 0), axis=1).astype(np.int32)
    startv = np.max(np.where(chosen & (top == topv[:, None]), startr, -1),
                    axis=1).astype(np.int32)
    m = feasible.copy()
    for vals in (nviol, topv, psumv, cntv, -startv):
        big = np.float32(np.inf) if vals.dtype == np.float32 \
            else np.array(INT32_MAX, vals.dtype)
        if not m.any():
            break
        best = np.min(np.where(m, vals, big))
        m = m & (vals == best)
    winner = np.int32(np.argmax(m)) if m.any() else np.int32(-1)
    return winner, chosen, (kidx + 1).astype(np.int32), nviol


# ------------------------------------------------- whole-gang (domains)

@dataclass
class DomainTables:
    """price_domains input + metadata: one row per ICI domain, units
    merged across the domain's nodes in band order; per-node slot
    curves for the post-winner member spread."""

    domains: List[str]
    #: domain -> [(node name, slot curve [len(units)+1])]
    nodes: Dict[str, List[Tuple[str, np.ndarray]]]
    #: per-domain merged unit stream [(unit, node name, per-node j)]
    units: List[List[Tuple[_Unit, str, int]]]
    res_names: List[str]
    arrays: Dict[str, np.ndarray] = field(default_factory=dict)

    def expand(self, row: int, chosen: np.ndarray) -> List[Pod]:
        out: List[Pod] = []
        for v, (unit, _n, _j) in enumerate(self.units[row]):
            if v < len(chosen) and chosen[v]:
                out.extend(sorted(unit.evict,
                                  key=lambda p: p.metadata.key()))
        return out

    def node_slots(self, row: int, chosen: np.ndarray
                   ) -> List[Tuple[str, int]]:
        """Member slots per node of the winner domain AFTER the chosen
        evictions, in sorted node order — the nomination spread."""
        evicted: Dict[str, int] = {}
        for v, (_u, node, j) in enumerate(self.units[row]):
            if v < len(chosen) and chosen[v]:
                evicted[node] = max(evicted.get(node, 0), j + 1)
        out = []
        for node, curve in self.nodes[self.domains[row]]:
            out.append((node, int(curve[evicted.get(node, 0)])))
        return out


def _slot_curve(free0: np.ndarray, cfree0: float, units: List[_Unit],
                q: np.ndarray, qmask: np.ndarray) -> np.ndarray:
    """[len(units)+1] member-slots on one node after evicting the first
    j units: min over requested resources of floor(free / q), capped by
    freed pod-count slots; monotone non-decreasing in j."""
    curves = np.zeros((len(units) + 1,), np.int64)
    free = free0.astype(np.float32).copy()
    cfree = np.float32(cfree0)
    for j in range(len(units) + 1):
        if j > 0:
            free = free + units[j - 1].freed
            cfree = cfree + np.float32(units[j - 1].fcnt)
        per_res = np.where(qmask, np.floor(free / np.maximum(q, 1e-9)),
                           np.float32(np.inf))
        slots = min(float(per_res.min()), float(np.floor(cfree)))
        curves[j] = max(0, int(slots))
    # eviction only frees capacity; enforce monotonicity against any
    # f32 floor jitter so merged per-domain deltas stay non-negative
    np.maximum.accumulate(curves, out=curves)
    return curves


def build_domain_tables(members: Sequence[Pod],
                        candidates: Sequence[Tuple[str, NodeInfo, str]],
                        infos: Dict[str, NodeInfo], pdbs,
                        min_member: int,
                        overshare: Optional[Dict[str, int]] = None
                        ) -> Optional[DomainTables]:
    """Whole-gang tables: `candidates` are (node, info, domain value)
    triples of screen-passing nodes carrying the gang's topology label.
    The member request is the elementwise MAX over members (a slot that
    holds the largest member holds any member), the fit threshold
    `min_member` slots inside ONE domain."""
    if not members or not candidates:
        return None
    need = pod_resource(members[0]).clone()
    for m in members[1:]:
        r = pod_resource(m)
        need.milli_cpu = max(need.milli_cpu, r.milli_cpu)
        need.memory = max(need.memory, r.memory)
        for k, v in r.scalar_resources.items():
            need.scalar_resources[k] = max(need.scalar_resources.get(k, 0),
                                           v)
    res_names = _res_columns(need)
    q = _res_row(need, res_names)
    qmask = q > 0
    # victims must sit strictly below EVERY member's priority
    prio = min(helpers.pod_priority(m) for m in members)
    group_bound = bound_group_index(infos)
    gkey = pod_group_key(members[0])
    per_dom: Dict[str, List[Tuple[str, NodeInfo]]] = {}
    for name, ni, dom in candidates:
        per_dom.setdefault(dom, []).append((name, ni))
    domains = sorted(per_dom)
    all_rows: List[List[_Unit]] = []
    node_units: Dict[str, List[_Unit]] = {}
    for dom in domains:
        for name, ni in sorted(per_dom[dom]):
            units, _cacheable = _node_units(prio, ni, pdbs, group_bound,
                                            res_names, overshare=overshare)
            # the preemptor gang itself may already hold bound members
            # (a partially-recovered slice): never price them as victims
            if gkey is not None:
                units = [u for u in units if u.key != f"group:{gkey}"]
            node_units[name] = units
            all_rows.append(units)
    _rank_and_sort(all_rows)
    t = DomainTables(domains=domains, nodes={}, units=[],
                     res_names=res_names)
    base: List[float] = []
    merged_rows: List[List[Tuple[_Unit, str, int]]] = []
    for dom in domains:
        slots0 = 0.0
        merged: List[Tuple[_Unit, str, int]] = []
        t.nodes[dom] = []
        for name, ni in sorted(per_dom[dom]):
            units = node_units[name]
            curve = _slot_curve(
                _res_row(ni.allocatable, res_names)
                - _res_row(ni.requested, res_names),
                float(ni.allocatable.allowed_pod_number - len(ni.pods)),
                units, q, qmask)
            t.nodes[dom].append((name, curve))
            slots0 += float(curve[0])
            for j, u in enumerate(units):
                merged.append((u, name, j))
        # cross-node merge in the shared band order; per-node unit order
        # is preserved (same sort key), so slot deltas stay additive
        merged.sort(key=lambda e: (e[0].pdb, -e[0].oshare, e[0].top,
                                   -e[0].startr, e[0].key, e[1]))
        merged_rows.append(merged)
        base.append(slots0)
    D = _bucket_pow2(len(domains))
    U = _bucket_pow2(max((len(m) for m in merged_rows), default=1))
    t.units = merged_rows
    a = t.arrays
    a["base"] = np.zeros((D,), np.float32)
    a["need"] = np.float32(min_member)
    a["dslots"] = np.zeros((D, U), np.float32)
    a["valid"] = np.zeros((D, U), bool)
    a["pdb"] = np.zeros((D, U), bool)
    a["top"] = np.full((D, U), INT32_MIN, np.int32)
    a["psum"] = np.zeros((D, U), np.float32)
    a["gcnt"] = np.zeros((D, U), np.int32)
    a["startr"] = np.full((D, U), -1, np.int32)
    a["row_valid"] = np.zeros((D,), bool)
    for i, dom in enumerate(domains):
        a["base"][i] = base[i]
        a["row_valid"][i] = True
        curves = dict(t.nodes[dom])
        for v, (u, name, j) in enumerate(merged_rows[i]):
            curve = curves[name]
            a["dslots"][i, v] = float(curve[j + 1] - curve[j])
            a["valid"][i, v] = True
            a["pdb"][i, v] = u.pdb
            a["top"][i, v] = u.top
            a["psum"][i, v] = u.psum
            a["gcnt"][i, v] = u.gcnt
            a["startr"][i, v] = u.startr
    return t


@jax.jit
def price_domains(base, need, dslots, valid, pdb, top, psum, gcnt,
                  startr, row_valid):
    """[D, U] whole-gang pricing: fit = minMember member-slots in one
    domain. k=0 (no eviction) is allowed — a domain already holding the
    slots wins for free. Returns (winner row or -1, chosen [D, U],
    nviol [D])."""
    U = valid.shape[1]
    cums = base[:, None] + jnp.cumsum(jnp.where(valid, dslots, 0.0),
                                      axis=1)
    fit0 = base >= need
    fitk = (cums >= need) & valid
    kidx = jnp.argmax(fitk, axis=1)
    feasible = (fitk.any(axis=1) | fit0) & row_valid
    chosen = valid & (jnp.arange(U)[None, :] <= kidx[:, None]) \
        & (~fit0)[:, None] & feasible[:, None]
    nviol, topv, psumv, cntv, startv = _prefix_costs(
        chosen, pdb, top, psum, gcnt, startr)
    winner = _lexi_winner(feasible, (nviol, topv, psumv, cntv, -startv))
    return winner, chosen, nviol


def price_domains_reference(a: Dict[str, np.ndarray]):
    """Numpy mirror of price_domains."""
    base, need = a["base"], a["need"]
    dslots, valid = a["dslots"], a["valid"]
    pdb, top, psum = a["pdb"], a["top"], a["psum"]
    gcnt, startr, row_valid = a["gcnt"], a["startr"], a["row_valid"]
    D, U = valid.shape
    cums = base[:, None] + np.cumsum(
        np.where(valid, dslots, np.float32(0.0)), axis=1, dtype=np.float32)
    fit0 = base >= need
    fitk = (cums >= need) & valid
    kidx = np.argmax(fitk, axis=1)
    feasible = (fitk.any(axis=1) | fit0) & row_valid
    chosen = valid & (np.arange(U)[None, :] <= kidx[:, None]) \
        & (~fit0)[:, None] & feasible[:, None]
    nviol = (chosen & pdb).sum(axis=1).astype(np.int32)
    topv = np.max(np.where(chosen, top, INT32_MIN), axis=1)
    psumv = np.sum(np.where(chosen, psum, np.float32(0.0)), axis=1,
                   dtype=np.float32)
    cntv = np.sum(np.where(chosen, gcnt, 0), axis=1).astype(np.int32)
    startv = np.max(np.where(chosen & (top == topv[:, None]), startr, -1),
                    axis=1).astype(np.int32)
    m = feasible.copy()
    for vals in (nviol, topv, psumv, cntv, -startv):
        big = np.float32(np.inf) if vals.dtype == np.float32 \
            else np.array(INT32_MAX, vals.dtype)
        if not m.any():
            break
        best = np.min(np.where(m, vals, big))
        m = m & (vals == best)
    winner = np.int32(np.argmax(m)) if m.any() else np.int32(-1)
    return winner, chosen, nviol
