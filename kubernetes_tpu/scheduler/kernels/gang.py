"""All-or-nothing gang assignment on device.

Extends the batched Filter+Score+Assign kernel (batch.py schedule_batch)
with the gang-scheduling contract: a PodGroup's members either ALL place —
each against the running usage, all inside one ICI topology domain — or
NONE do. Placing 3 of 4 workers of a v4-32 slice wedges the slice and
deadlocks against other partial gangs, so partial placement is strictly
worse than no placement.

Layout: the batch's placement units (gangs, and every singleton as a gang
of one) are FLATTENED into one member-entry stream, so the scan length is
O(total members) regardless of gang sizes — a 512-member slice costs the
same HLO as 512 singletons, where a per-gang scan with the max gang size
unrolled in its step would blow up compilation:

    pod_idx [T] int32   pod-axis index of the entry (-1 = padding)
    start   [T] bool    first entry of its gang (opens a trial window)
    end     [T] bool    last entry of its gang (commit-or-rollback point)
    gang_id [T] int32   unit id, for the post-scan all-or-nothing mask
    dom_idx [T] int32   row into dom_tab (-1 = no topology constraint)
    pin_dom [T] int32   pre-pinned domain id (-1 = free): a gang whose
                        EARLIER batches already reserved in a domain seeds
                        the carry with it, so stragglers can only join
                        that slice
    dom_tab [K, N] int32  node row -> topology-domain id (-1 = label absent)

The scan carry holds TWO usage states: `committed` (last gang boundary)
and `trial` (running placements of the open gang). A gang start copies
committed into trial; each member places greedily against trial exactly
like schedule_batch's step (same feasibility, same resource scores, same
(row, seq) tie-break hash — a singleton-only batch is bit-identical to
schedule_batch modulo the spread/topology in-scan extras, which gang
batches do not carry); the gang's end either folds trial into committed or
drops it. The first placed member of a topology-constrained gang pins the
gang's domain; every later member's mask is restricted to that domain.

Members that individually placed inside a gang that later failed are
masked to -1 AFTER the scan via the per-gang ok vector — the usage they
touched only ever lived in the discarded trial, so no rollback scatter is
needed.

The same isolation is what makes gang batches CHAINABLE in the pipelined
drain (core.schedule_launch): the returned usage holds exactly the
committed gangs' placements — every one of which the commit path assumes
into the cache (bind or permit-gate reservation) — so a successor batch
may take it as its usage input before the host commit lands, with losses
surfacing through the ordinary phantom/epoch machinery.

`gang_schedule_reference` is the host numpy mirror (same op order, f32
throughout) — the parity oracle for tests/test_gang.py's randomized
instances, in the same role predicates.py/priorities.py play for the
plain batch kernel.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .batch import (COL_CPU, COL_MEM, NEG, _pod_feasible, _pod_score,
                    _soft_raw, _soft_score, _soft_tables, _soft_write,
                    _split_batch, _tie_penalized, unpack_inputs)

#: entries per scan step (unrolled inside, same op sequence — see
#: batch.py's step grouping); must divide the bucketed T (a power of two)
_STEP_GROUP_GANG = 8


@jax.jit
def gang_feasible(fits: jnp.ndarray, members: jnp.ndarray) -> jnp.ndarray:
    """[G] bool per-gang static-feasibility reduction over the pods x nodes
    mask (filter_score output): False when some member fits NOWHERE even
    on the empty batch-start snapshot — such a gang can never place, so a
    caller may reject it without paying the assignment scan. A reduction,
    not a placement: True only means "not provably impossible". NOT yet
    routed by core.schedule_launch (the scan subsumes it); kept as the
    building block for a cheap pre-reject / gang-aware autoscaling signal
    (ROADMAP), exercised by tests/test_gang.py.

    members: [G, M] int32 pod rows, -1 padded."""
    ok_pod = fits.any(axis=1)                       # [P]
    valid = members >= 0                            # [G, M]
    ok_m = ok_pod[jnp.maximum(members, 0)]          # [G, M]
    return (ok_m | ~valid).all(axis=1)


@jax.jit
def gang_schedule_batch(node_cfg: dict, usage: dict, pod_batch: dict,
                        gang_tab: dict, nom: dict = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Serial-semantics greedy assignment with per-gang atomicity.

    Same signature/returns as batch.schedule_batch — (assign [P] int32,
    chosen_score [P] f32, new_usage) — so core.BatchScheduler's
    launch/finish plumbing (pack_results, usage adoption) is shared.
    new_usage reflects only COMMITTED gangs. Gang batches never carry the
    in-scan spread/topology tables (the core refuses those combinations
    before routing here), but soft inter-pod credit tables DO ride: the
    per-(term, domain) accumulators live in the trial/committed usage
    dicts, so a rejected gang's credit writes vanish with its trial —
    which is what let core drop the gang SOFT_SCORE_CHUNK sub-batching.
    `nom` is the same phantom nominated-reservation overlay
    schedule_batch takes — a mixed batch's singletons must not steal a
    preemptor's freed space just because a gang member rode along.
    """
    pod_batch, gang_tab = unpack_inputs(pod_batch), unpack_inputs(gang_tab)
    per_pod, unique_masks, unique_scores, rw = _split_batch(pod_batch)
    N = node_cfg["alloc"].shape[0]
    P = per_pod["seq"].shape[0]
    dom_tab = gang_tab["dom_tab"]
    rows = jnp.arange(N, dtype=jnp.int32)
    # capacity-aware per-domain feasibility (gang_tab need/greq present):
    # at each gang boundary, a domain is ELIGIBLE only when its nodes'
    # member-slots against committed usage cover the whole gang — the
    # first placed member can no longer pin the gang into a domain that
    # cannot hold everyone. Absent keys keep the greedy-pin behavior
    # (hand-built fixtures, older callers).
    has_cap = "need" in gang_tab
    soft = _soft_tables(pod_batch)
    has_soft = soft is not None
    if has_soft:
        soft_dom, soft_cnt0, soft_base, soft_w = soft
    if nom is None:
        nom = {"used": jnp.zeros_like(usage["used"]),
               "count": jnp.zeros_like(usage["pod_count"])}

    def one_entry(carry, e):
        committed, trial, gang_dom, gang_ok, gang_elig = carry
        # gang boundary: open a fresh trial window over committed state
        fresh = e["start"]
        trial = {k: jnp.where(fresh, committed[k], trial[k])
                 for k in trial}
        gang_dom = jnp.where(fresh, e["pin_dom"], gang_dom)
        gang_ok = jnp.where(fresh, True, gang_ok)

        valid = e["pod_idx"] >= 0
        i = jnp.maximum(e["pod_idx"], 0)
        pod = {k: v[i] for k, v in per_pod.items()}
        mask = unique_masks[pod["mask_idx"]]
        static = unique_scores[pod["score_idx"]]
        # ICI-domain restriction: members of a constrained gang must land
        # where the topology label EXISTS, and — once the first member
        # pinned a domain — inside that domain
        constrained = e["dom_idx"] >= 0
        dom_row = dom_tab[jnp.maximum(e["dom_idx"], 0)]
        if has_cap:
            # per-node member-slots against COMMITTED usage (f32 floors,
            # mirrored by the oracle), summed per domain; eligibility =
            # the domain holds the whole gang. Applied at the boundary of
            # constrained, un-pinned gangs; when NO domain passes, fall
            # back to the greedy pin so feasibility never regresses.
            greq = e["greq"]
            qmask = greq > 0
            # the nominated phantom overlay counts here exactly like the
            # per-member fit (eff_used below): a domain whose free space
            # is shielded by preemptors' reservations cannot hold this
            # gang. (A gang holding its OWN nominations may see its
            # reserved domain as full — the any-eligible fallback, or an
            # honestly eligible other domain, still places it, and its
            # per-member self-credit applies at fit time.)
            free = node_cfg["alloc"] - (committed["used"] + nom["used"])
            per = jnp.where(
                qmask[None, :],
                jnp.floor(free / jnp.maximum(greq, jnp.float32(1e-9))
                          [None, :]),
                jnp.float32(jnp.inf))
            slots = jnp.minimum(
                per.min(axis=1),
                jnp.floor(node_cfg["max_pods"]
                          - (committed["pod_count"] + nom["count"])))
            slots = jnp.maximum(slots, jnp.float32(0.0))
            ok_node = node_cfg["node_ok"] & node_cfg["valid"] \
                & (dom_row >= 0)
            slots = jnp.where(ok_node, slots, jnp.float32(0.0))
            domcap = jnp.zeros((N,), jnp.float32).at[
                jnp.where(dom_row >= 0, dom_row, N)].add(
                    slots, mode="drop")
            elig = (domcap[jnp.maximum(dom_row, 0)] >= e["need"]) \
                & (dom_row >= 0)
            apply_f = constrained & (e["pin_dom"] < 0) \
                & (e["need"] > 0) & elig.any()
            gang_elig = jnp.where(fresh,
                                  jnp.where(apply_f, elig, True),
                                  gang_elig)
        dmask = jnp.where(constrained,
                          (dom_row >= 0) & ((gang_dom < 0)
                                            | (dom_row == gang_dom))
                          & gang_elig,
                          True)
        # phantom nominated usage shields preemption's freed space, minus
        # the pod's own reservation at its nominated row (batch.py's
        # schedule_batch semantics)
        self_oh = rows == pod.get("nom_row", jnp.int32(-1))
        eff_used = trial["used"] + nom["used"] - \
            jnp.where(self_oh[:, None], pod["req"][None, :], 0.0)
        eff_count = trial["pod_count"] + nom["count"] \
            - self_oh.astype(jnp.float32)
        fits = _pod_feasible(node_cfg, eff_used, eff_count,
                             pod, mask & dmask)
        score = _pod_score(node_cfg, trial["nonzero_used"], pod, static, rw)
        if has_soft:
            # credits read from the TRIAL accumulators: an open gang's
            # earlier members are visible, a rejected gang's never were
            raw = _soft_raw(soft_dom, trial["soft_cnt"], soft_base, pod)
            score = score + jnp.where(
                pod["soft_base_idx"] >= 0,
                _soft_score(raw, fits, soft_w), 0.0)
        masked = jnp.where(fits, score, NEG)
        # identical tie-break to schedule_batch (selectHost rotation)
        best = jnp.argmax(_tie_penalized(masked, rows, pod["seq"])) \
            .astype(jnp.int32)
        ok = fits[best] & pod["active"] & valid
        oh_f = ((rows == best) & ok).astype(jnp.float32)
        new_trial = {
            "used": trial["used"] + oh_f[:, None] * pod["req"][None, :],
            "nonzero_used": trial["nonzero_used"]
            + oh_f[:, None] * pod["nonzero_req"][None, :],
            "pod_count": trial["pod_count"] + oh_f,
        }
        if has_soft:
            new_trial["soft_cnt"] = _soft_write(
                soft_dom, trial["soft_cnt"], pod, best, ok)
        trial = new_trial
        gang_dom = jnp.where(valid & ok & constrained & (gang_dom < 0),
                             dom_row[best], gang_dom)
        # a padding entry never vetoes its (padding) gang
        gang_ok = gang_ok & (ok | ~valid)
        # gang end: fold the trial into committed state, or drop it whole
        closing = e["end"]
        commit = closing & gang_ok
        committed = {k: jnp.where(commit, trial[k], committed[k])
                     for k in committed}
        assign = jnp.where(ok, best, jnp.int32(-1))
        return ((committed, trial, gang_dom, gang_ok, gang_elig),
                (assign, masked[best], gang_ok))

    usage0 = {"used": usage["used"], "nonzero_used": usage["nonzero_used"],
              "pod_count": usage["pod_count"]}
    if has_soft:
        # chained launches seed from the predecessor's committed finals
        sc0 = usage.get("soft_cnt")
        usage0["soft_cnt"] = sc0 if sc0 is not None else soft_cnt0
    carry0 = (usage0, usage0, jnp.int32(-1), jnp.bool_(True),
              jnp.ones((N,), bool))
    entries = {"pod_idx": gang_tab["pod_idx"], "start": gang_tab["start"],
               "end": gang_tab["end"], "dom_idx": gang_tab["entry_dom_idx"],
               "pin_dom": gang_tab["pin_dom"]}
    if has_cap:
        entries["need"] = gang_tab["need"]
        entries["greq"] = gang_tab["greq"]
    T = entries["pod_idx"].shape[0]
    G = min(_STEP_GROUP_GANG, T)

    def step(carry, eg):
        outs = []
        for g in range(G):
            e = {k: v[g] for k, v in eg.items()}
            carry, out = one_entry(carry, e)
            outs.append(out)
        return carry, tuple(jnp.stack([o[j] for o in outs])
                            for j in range(3))

    entries_g = {k: v.reshape((T // G, G) + v.shape[1:])
                 for k, v in entries.items()}
    (committed, _, _, _, _), (assign_e, score_e, ok_e) = lax.scan(
        step, carry0, entries_g)
    assign_e = assign_e.reshape(T)
    score_e = score_e.reshape(T)
    ok_e = ok_e.reshape(T)

    # all-or-nothing mask: each gang's verdict is the carry's gang_ok AT
    # ITS END ENTRY; scatter it over the gang's ids, gather per entry
    # (unit ids are entry-stream positions, so T bounds them statically)
    gang_id = gang_tab["gang_id"]
    n_units = T
    end = gang_tab["end"]
    ok_units = jnp.zeros((n_units,), bool).at[
        jnp.where(end, gang_id, n_units)].set(ok_e, mode="drop")
    entry_ok = ok_units[jnp.minimum(gang_id, n_units - 1)]
    assign_e = jnp.where(entry_ok, assign_e, jnp.int32(-1))

    # entry axis -> pod axis
    pod_idx = gang_tab["pod_idx"]
    tgt = jnp.where(pod_idx >= 0, pod_idx, P)
    assign = jnp.full((P,), -1, jnp.int32).at[tgt].set(
        assign_e, mode="drop")
    scores = jnp.full((P,), NEG, jnp.float32).at[tgt].set(
        score_e, mode="drop")
    return assign, scores, committed


# ----------------------------------------------------------------- oracle

def gang_schedule_reference(node_cfg: Dict[str, np.ndarray],
                            usage: Dict[str, np.ndarray],
                            pod_batch: Dict[str, np.ndarray],
                            gang_tab: Dict[str, np.ndarray],
                            nom: Dict[str, np.ndarray] = None
                            ) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Host numpy mirror of gang_schedule_batch — same greedy order, same
    f32 arithmetic, same tie-break — the parity oracle. Deliberately
    written as the obvious nested loop over gangs and members."""
    alloc = np.asarray(node_cfg["alloc"], np.float32)
    max_pods = np.asarray(node_cfg["max_pods"], np.float32)
    node_ok = np.asarray(node_cfg["node_ok"], bool)
    node_valid = np.asarray(node_cfg["valid"], bool)
    mem_pressure = np.asarray(node_cfg["mem_pressure"], bool)
    N = alloc.shape[0]
    P = np.asarray(pod_batch["req"]).shape[0]
    used = np.asarray(usage["used"], np.float32).copy()
    nz = np.asarray(usage["nonzero_used"], np.float32).copy()
    cnt = np.asarray(usage["pod_count"], np.float32).copy()
    reqs = np.asarray(pod_batch["req"], np.float32)
    nzreqs = np.asarray(pod_batch["nonzero_req"], np.float32)
    blocked = np.asarray(pod_batch["mem_pressure_blocked"], bool)
    active = np.asarray(pod_batch["active"], bool)
    seq = np.asarray(pod_batch["seq"], np.int64)
    mask_idx = np.asarray(pod_batch["mask_idx"], np.int64)
    score_idx = np.asarray(pod_batch["score_idx"], np.int64)
    unique_masks = np.asarray(pod_batch["unique_masks"], bool)
    unique_scores = np.asarray(pod_batch["unique_scores"], np.float32)
    rw = np.asarray(pod_batch["resource_weights"], np.float32)
    dom_tab = np.asarray(gang_tab["dom_tab"], np.int32)
    cap_cpu = alloc[:, COL_CPU]
    cap_mem = alloc[:, COL_MEM]
    safe_cpu = np.maximum(cap_cpu, np.float32(1.0))
    safe_mem = np.maximum(cap_mem, np.float32(1.0))
    rows64 = np.arange(N, dtype=np.int64)
    NEG32 = np.float32(NEG)
    if nom is None:
        nom_used = np.zeros_like(used)
        nom_cnt = np.zeros_like(cnt)
    else:
        nom_used = np.asarray(nom["used"], np.float32)
        nom_cnt = np.asarray(nom["count"], np.float32)
    nom_row = np.asarray(pod_batch["nom_row"], np.int64)
    # soft inter-pod credit tables (same trial/commit life as usage)
    has_soft = pod_batch.get("soft_dom") is not None
    if has_soft:
        soft_dom = np.asarray(pod_batch["soft_dom"], np.int64)
        soft_cnt = np.asarray(pod_batch["soft_cnt0"], np.float32).copy()
        soft_base = np.asarray(pod_batch["soft_base"], np.float32)
        soft_bidx = np.asarray(pod_batch["soft_base_idx"], np.int64)
        soft_rt = np.asarray(pod_batch["soft_read_tids"], np.int64)
        soft_rw = np.asarray(pod_batch["soft_read_w"], np.float32)
        soft_wt = np.asarray(pod_batch["soft_write_tids"], np.int64)
        soft_ww = np.asarray(pod_batch["soft_write_w"], np.float32)
        soft_w = np.float32(pod_batch["soft_weight"])

    assign = np.full((P,), -1, np.int32)
    scores = np.full((P,), NEG32, np.float32)

    # regroup the flattened entry stream back into units (keeping each
    # unit's start-entry index for the capacity-feasibility inputs)
    units: list = []
    gid = np.asarray(gang_tab["gang_id"])
    pod_idx = np.asarray(gang_tab["pod_idx"])
    entry_dom = np.asarray(gang_tab["entry_dom_idx"])
    pin_dom = np.asarray(gang_tab["pin_dom"])
    for t in range(len(pod_idx)):
        if gang_tab["start"][t]:
            units.append(([], int(entry_dom[t]), int(pin_dom[t]),
                          int(gid[t]), t))
        units[-1][0].append(int(pod_idx[t]))
    has_cap = "need" in gang_tab
    if has_cap:
        cap_need = np.asarray(gang_tab["need"], np.float32)
        cap_greq = np.asarray(gang_tab["greq"], np.float32)

    for members, dom_idx, pin, _, t_start in units:
        trial_used = used.copy()
        trial_nz = nz.copy()
        trial_cnt = cnt.copy()
        trial_soft = soft_cnt.copy() if has_soft else None
        gang_dom = pin
        gang_ok = True
        placed: list = []
        dom_row = dom_tab[max(dom_idx, 0)]
        gang_elig = np.ones((N,), bool)
        if has_cap and dom_idx >= 0 and pin < 0 \
                and cap_need[t_start] > 0:
            # capacity-aware per-domain feasibility — the kernel's
            # boundary reduction, same f32 op order
            greq = cap_greq[t_start]
            qmask = greq > 0
            free = alloc - (used + nom_used)
            per = np.where(qmask[None, :],
                           np.floor(free / np.maximum(
                               greq, np.float32(1e-9))[None, :]),
                           np.float32(np.inf))
            slots = np.minimum(per.min(axis=1),
                               np.floor(max_pods - (cnt + nom_cnt)))
            slots = np.maximum(slots, np.float32(0.0))
            ok_node = node_ok & node_valid & (dom_row >= 0)
            slots = np.where(ok_node, slots, np.float32(0.0))
            domcap = np.zeros((N,), np.float32)
            np.add.at(domcap, dom_row[dom_row >= 0],
                      slots[dom_row >= 0])
            elig = (domcap[np.maximum(dom_row, 0)] >= cap_need[t_start]) \
                & (dom_row >= 0)
            if elig.any():
                gang_elig = elig
        for i in members:
            if i < 0:
                continue
            if dom_idx >= 0:
                dmask = (dom_row >= 0) & ((gang_dom < 0)
                                          | (dom_row == gang_dom)) \
                    & gang_elig
            else:
                dmask = np.ones((N,), bool)
            eff_used = trial_used + nom_used
            eff_cnt = trial_cnt + nom_cnt
            if nom_row[i] >= 0:
                eff_used = eff_used.copy()
                eff_cnt = eff_cnt.copy()
                eff_used[nom_row[i]] -= reqs[i]
                eff_cnt[nom_row[i]] -= np.float32(1.0)
            fits = unique_masks[mask_idx[i]] & dmask & node_ok & node_valid
            fits &= (reqs[i][None, :] + eff_used <= alloc).all(axis=1)
            fits &= eff_cnt + np.float32(1.0) <= max_pods
            if blocked[i]:
                fits &= ~mem_pressure
            # resource priorities, f32 like the kernel
            req_cpu = trial_nz[:, 0] + nzreqs[i, 0]
            req_mem = trial_nz[:, 1] + nzreqs[i, 1]
            lr_c = np.where((cap_cpu > 0) & (req_cpu <= cap_cpu),
                            np.floor((cap_cpu - req_cpu) * np.float32(10.0)
                                     / safe_cpu), np.float32(0.0))
            lr_m = np.where((cap_mem > 0) & (req_mem <= cap_mem),
                            np.floor((cap_mem - req_mem) * np.float32(10.0)
                                     / safe_mem), np.float32(0.0))
            lr = np.floor((lr_c + lr_m) / np.float32(2.0))
            cpu_frac = np.where(cap_cpu > 0, req_cpu / safe_cpu,
                                np.float32(1.0))
            mem_frac = np.where(cap_mem > 0, req_mem / safe_mem,
                                np.float32(1.0))
            ba = np.floor((np.float32(1.0) - np.abs(cpu_frac - mem_frac))
                          * np.float32(10.0) + np.float32(4e-6))
            ba = np.where((cpu_frac >= 1.0) | (mem_frac >= 1.0),
                          np.float32(0.0), ba)
            score = rw[0] * lr + rw[1] * ba + unique_scores[score_idx[i]]
            if has_soft and soft_bidx[i] >= 0:
                # _soft_raw / _soft_score in f32, same op order
                rt = soft_rt[i]
                t = np.maximum(rt, 0)
                drow = soft_dom[t]                          # [Ks, N]
                at = np.take_along_axis(trial_soft[t],
                                        np.maximum(drow, 0), axis=1)
                valid_r = (rt[:, None] >= 0) & (drow >= 0)
                raw = soft_base[max(int(soft_bidx[i]), 0)] + \
                    (soft_rw[i][:, None]
                     * np.where(valid_r, at, np.float32(0.0))).sum(axis=0)
                mn = min(np.min(np.where(fits, raw, np.float32(np.inf))),
                         np.float32(0.0))
                mx = max(np.max(np.where(fits, raw, np.float32(-np.inf))),
                         np.float32(0.0))
                if mx > mn:
                    norm = np.floor(
                        np.float32(10.0) * (raw - mn)
                        / np.maximum(mx - mn, np.float32(1e-30))
                        + np.float32(4e-6))
                    score = score + soft_w * norm
            masked = np.where(fits, score, NEG32)
            h = ((rows64 * -1640531527 + int(seq[i]) * 40503)
                 & 0xFFFF).astype(np.float32)
            best = int(np.argmax(masked - h * np.float32(0.5 / 65536.0)))
            ok = bool(fits[best]) and bool(active[i])
            scores[i] = masked[best]
            if ok:
                placed.append((i, best))
                trial_used[best] += reqs[i]
                trial_nz[best] += nzreqs[i]
                trial_cnt[best] += np.float32(1.0)
                if has_soft:
                    wt = soft_wt[i]
                    wtc = np.maximum(wt, 0)
                    wd = soft_dom[wtc, best]
                    wval = np.where((wt >= 0) & (wd >= 0), soft_ww[i],
                                    np.float32(0.0))
                    np.add.at(trial_soft, (wtc, np.maximum(wd, 0)), wval)
                if dom_idx >= 0 and gang_dom < 0:
                    gang_dom = int(dom_row[best])
            else:
                gang_ok = False
        if gang_ok:
            used, nz, cnt = trial_used, trial_nz, trial_cnt
            if has_soft:
                soft_cnt = trial_soft
            for i, best in placed:
                assign[i] = best
    new_usage = {"used": used, "nonzero_used": nz, "pod_count": cnt}
    if has_soft:
        new_usage["soft_cnt"] = soft_cnt
    return assign, scores, new_usage
