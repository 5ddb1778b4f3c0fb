"""Speculative cohort assignment over the class-indexed scan.

The class scan (kernels/batch.py) is pod-serial by construction: each
scan step assigns ONE pod against the running usage, so the pod axis of
the (pods x nodes) problem never parallelizes in production mode — the
step latency, not the per-step FLOPs, bounds the drain rate (the
BENCH_r08/r12 observation, and the gap ROADMAP direction 2 names).

This kernel breaks the serialism SPECULATIVELY, with bit-exact serial
equivalence as the contract rather than a best-effort approximation:

  1. COHORTS — the batch is processed in fixed-width cohorts of K pods
     (KTPU_SPEC_COHORT, power of two, default 16) in the exact lexsorted
     drain order the serial scan uses. Each cohort is assigned in ONE
     vmapped shot against the carry's frozen [C, N] masked-score table:
     a [K, N] row gather + tie-penalized argmax, riding the same class
     tables and winner-column machinery as the serial scan.

  2. COLLISION DETECTION — a cohort's speculative picks are valid only
     where the serial scan, replaying the same pods one by one, would
     have made the identical picks. Three exact checks:

       - structure: pods that READ carry-dependent terms (required
         (anti-)affinity or waived-affinity term lists, spread groups,
         soft inter-pod credit channels, nominated self-exemption rows)
         can observe an earlier cohort member's write, so they are never
         speculated on (`spec_plain`, computed host-side from the term
         tables the batch already ships — core.BatchScheduler). DRF
         ordering is host-side (tenancy/drf.py runs before tensorize),
         so tenant fair-share never interacts in-kernel.
       - type 1: two cohort members picked the SAME node — the later
         pick would have seen the earlier winner's usage on that row.
       - type 2: an earlier member j's write perturbs a later member
         i's comparison at j's chosen node. The perturbed value is
         recomputed EXACTLY — a vmapped `_class_col` of each winner's
         post-assignment column (the same f32 op order as the serial
         winner-column refresh), tie-penalized with i's seq — and i
         collides iff that value could reach i's frozen argmax value
         (>=, conservatively: ties re-rank by node id).

     Everything a pod could observe lives behind those checks: its own
     chosen column is untouched (type 1), unchanged columns lose to its
     frozen first-max by argmax semantics, and changed columns are
     checked exactly (type 2). Infeasible and inactive pods are inert:
     usage only grows, so frozen-infeasible stays serially infeasible.

  3. REPAIR — on the first colliding pod the WHOLE cohort re-runs the
     serial scan step (`_class_pod_step`, the one shared copy), inside
     the untaken `lax.cond` branch: the accepted prefix provably makes
     identical decisions either way, and the colliding suffix gets the
     serial semantics by construction. Repair is total per cohort —
     cohort width is the speculation granularity, so a clean cohort
     costs ONE fat vectorized step and a dirty cohort costs exactly the
     serial scan it replaced (plus the rejected speculation's checks).

Decisions are therefore bit-identical to `_schedule_batch_classes` on
EVERY batch — not just cohort-friendly ones — and the divergence
counter (scheduler_speculative_divergences_total) exists to prove that
claim in production, not to bound an accepted error: the
`speculative_reference` oracle replays the serial kernel on the same
inputs and any mismatch is attributed per pod + cohort by
`divergence_report`.

Accepted-cohort writes reuse the serial arithmetic exactly: usage
scatters add the same `okf * class_req` terms at distinct rows, the
winner columns were already recomputed by the SAME vmapped `_class_col`
the type-2 check used, and topo/soft counter writes run the shared
per-pod helpers (`_topo_scatter`/`_soft_write`) unrolled in pod order so
non-integer f32 accumulation order cannot drift from the serial scan
(spread counts are integer-valued f32 at distinct columns, so their
vectorized scatter is exact).
"""

from __future__ import annotations

import os as _os

import jax
import jax.numpy as jnp
from jax import lax

from .batch import (NEG, _NEG_THRESHOLD, _class_col, _class_ctx,
                    _class_pod_step, _class_usage_out, _soft_write,
                    _tie_penalized, _topo_scatter, schedule_batch,
                    unpack_inputs)

#: pods per speculative cohort (power of two; clamped to the pod-bucket
#: size). Wider cohorts amortize more step latency when clean but make
#: type-1 node contention — and therefore whole-cohort repair — more
#: likely; 16 wins on the uniform/multi-class shapes the bench measures.
_SPEC_COHORT = int(_os.environ.get("KTPU_SPEC_COHORT", "16"))
#: cohorts unrolled per scan step. Kept as an escape hatch, but the
#: measured default is 1: once the per-cohort argmax was replaced with
#: the vectorized first-max idiom the scan stopped being step-latency
#: bound, and extra unrolling only buys compile time (G=1 beat G=4 at
#: the default width in the r14 probes).
_SPEC_GROUP = int(_os.environ.get("KTPU_SPEC_GROUP", "1"))
#: minimum fraction of PLAIN pods (tensorize.set_speculative) among a
#: batch's active pods for the speculative route to engage. Non-plain
#: pods trip the structural fence, so a batch that is mostly topology/
#: spread/soft-coupled repairs every cohort and the election + exact
#: collision checks become pure overhead (r14 measured 0.42x end-to-end
#: on the pure-anti-affinity mix) — such batches route to the serial
#: scan at launch. 0 forces speculation on (the bench's forced legs).
_SPEC_MIN_PLAIN = float(_os.environ.get("KTPU_SPEC_MIN_PLAIN", "0.25"))


def cohort_width(P: int) -> int:
    """The effective cohort width for a P-pod batch: the knob rounded
    down to a power of two and clamped to P (P is always a power of two
    >= 8 via tensorize._bucket, so the reshape divides exactly)."""
    want = max(1, _SPEC_COHORT)
    return min(1 << (want.bit_length() - 1), P)


def _spec_chunk(ctx, carry, podg, K):
    """One cohort: speculate K pods against the frozen carry, detect
    collisions exactly, and either apply the whole cohort vectorized or
    replay it with the serial per-pod step. Returns
    (carry', (assign [K], chosen [K], accepted scalar, first scalar))
    where `first` is the first colliding pod index (K when clean)."""
    cls = ctx["cls"]
    rows, N = ctx["rows"], ctx["N"]
    nom = ctx["nom"]
    u = podg["class_idx"]                                       # [K]
    base = carry["ms"][u]                                       # [K, N]
    fits = base > _NEG_THRESHOLD
    masked = jnp.where(fits, base, NEG)
    pen = _tie_penalized(masked, rows[None, :], podg["seq"][:, None])
    # first-max argmax as max + where + min: XLA CPU lowers the variadic
    # argmax reduce to a scalar loop (~70us per [K, N] call — it IS the
    # serial scan's latency floor), while these three reduce/select ops
    # vectorize. Semantics are argmax's exactly: vbest is the same f32
    # max element, and min over the positions equal to it is the first
    # occurrence (pen is never NaN: scores are finite, NEG = -1e30).
    vbest = jnp.max(pen, axis=1)                                # [K]
    best = jnp.min(jnp.where(pen == vbest[:, None], rows[None, :],
                             jnp.int32(N)), axis=1)             # [K]
    chosen = jnp.take_along_axis(masked, best[:, None], axis=1)[:, 0]
    ok = (chosen > _NEG_THRESHOLD) & podg["active"]
    okf = jnp.where(ok, 1.0, 0.0)
    # each winner's post-assignment row state — the serial column
    # refresh's inputs, in its exact f32 op order (carry + okf*req, then
    # + nom overlay), vmapped over the cohort. Doubles as the refreshed
    # winner columns for the accepted branch: winners sit on DISTINCT
    # nodes there (type 1), so each column depends only on its own
    # pod's write.
    used_b = carry["used"][best] + okf[:, None] * cls["class_req"][u]
    nz_b = carry["nz_used"][best] + okf[:, None] * cls["class_nz"][u]
    cnt_b = carry["pod_count"][best] + okf
    if ctx["has_nom"]:
        col_used = used_b + nom["used"][best]
        col_cnt = cnt_b + nom["count"][best]
    else:
        col_used, col_cnt = used_b, cnt_b
    node_cfg, um, us, rw = (ctx["node_cfg"], ctx["unique_masks"],
                            ctx["unique_scores"], ctx["rw"])
    cols = jax.vmap(
        lambda ub, nb, cb, bb: _class_col(node_cfg, cls, um, us, rw,
                                          ub, nb, cb, bb)
    )(col_used, nz_b, col_cnt, best)                            # [K, C]
    # type-2: pod i's value at winner j's node AFTER j's write
    afterval = cols[:, u]                                       # [K_j, K_i]
    pen_after = _tie_penalized(afterval, best[:, None],
                               podg["seq"][None, :])
    idx = jnp.arange(K, dtype=jnp.int32)
    earlier = idx[:, None] < idx[None, :]                       # j < i
    wj = ok[:, None]
    t1 = jnp.any(earlier & wj & (best[:, None] == best[None, :]), axis=0)
    t2 = jnp.any(earlier & wj & (pen_after >= vbest[None, :]), axis=0)
    collide = ((t1 | t2) & ok) | (~podg["spec_plain"] & podg["active"])
    first = jnp.min(jnp.where(collide, idx, jnp.int32(K)))
    accept = first >= jnp.int32(K)

    def _apply_cohort(carry):
        bw = jnp.where(ok, best, jnp.int32(N))  # drop losers' writes
        used = carry["used"].at[bw].add(okf[:, None] * cls["class_req"][u],
                                        mode="drop")
        nz_used = carry["nz_used"].at[bw].add(
            okf[:, None] * cls["class_nz"][u], mode="drop")
        pod_count = carry["pod_count"].at[bw].add(okf, mode="drop")
        out = {"used": used, "nz_used": nz_used, "pod_count": pod_count,
               "ms": carry["ms"].at[:, bw].set(cols.T, mode="drop")}
        if ctx["has_spread"]:
            sm = podg.get("spread_match")
            if sm is None:
                sm = jnp.zeros((K, carry["spread"].shape[0]), jnp.float32)
            # integer-valued counts at distinct columns: exact
            out["spread"] = carry["spread"].at[:, bw].add(
                sm.T * okf[None, :], mode="drop")
        if ctx["has_topo"]:
            # plain pods never READ topo state but may WRITE it (they can
            # match someone else's term); unroll the shared scatter in
            # pod order so the counter arithmetic is the serial scan's
            tc = {k: carry[k] for k in ("topo_cnt", "topo_tot",
                                        "topo_carry") if k in carry}
            for g in range(K):
                pod = {k: v[g] for k, v in podg.items()}
                tc.update(_topo_scatter(ctx["anti_dom"], tc, pod,
                                        best[g], ok[g], ctx["has_dir2"]))
            out.update(tc)
        if ctx["has_soft"]:
            # soft write weights are arbitrary f32: pod-order unroll
            # keeps the accumulation order bit-identical to serial
            sc = carry["soft_cnt"]
            for g in range(K):
                pod = {k: v[g] for k, v in podg.items()}
                sc = _soft_write(ctx["soft"][0], sc, pod, best[g], ok[g])
            out["soft_cnt"] = sc
        return out, (jnp.where(ok, best, jnp.int32(-1)), chosen)

    def _repair_cohort(carry):
        outs = []
        for g in range(K):
            pod = {k: v[g] for k, v in podg.items()}
            carry, o = _class_pod_step(ctx, carry, pod)
            outs.append(o)
        return carry, (jnp.stack([o[0] for o in outs]),
                       jnp.stack([o[1] for o in outs]))

    carry2, (assign, scores) = lax.cond(accept, _apply_cohort,
                                        _repair_cohort, carry)
    return carry2, (assign, scores, accept.astype(jnp.int32), first)


from functools import partial


@partial(jax.jit, static_argnames=("width",))
def schedule_batch_speculative(node_cfg: dict, usage: dict,
                               pod_batch: dict, nom: dict = None,
                               width: int = 16):
    """Drop-in for schedule_batch on class-table batches carrying a
    `spec_plain` vector (core.BatchScheduler attaches it when
    KTPU_SPECULATIVE=1): same (assign, scores, new_usage) plus a
    [P/K, 2] int32 stats array of (accepted, first_collision) per
    cohort, from which core.schedule_finish derives the
    scheduler_speculative_* counters. Usage chains identically to the
    serial scan (spread/soft finals ride new_usage), so pipelined-drain
    chaining across speculative batches needs no special casing.

    `width` is STATIC (callers pass cohort_width(P)): the cohort width
    is part of the compiled scan's shape, and threading it as a traced
    value would silently reuse whichever width compiled first."""
    pod_batch = unpack_inputs(pod_batch)
    ctx, carry0, per_pod = _class_ctx(node_cfg, usage, pod_batch, nom)
    P = per_pod["seq"].shape[0]
    K = min(max(1, width), P)
    n_chunks = P // K
    G = min(1 << (max(1, _SPEC_GROUP).bit_length() - 1), n_chunks)

    def step(carry, podgg):
        outs = []
        for g in range(G):
            podg = {k: v[g] for k, v in podgg.items()}
            carry, o = _spec_chunk(ctx, carry, podg, K)
            outs.append(o)
        return carry, tuple(jnp.stack([o[i] for o in outs])
                            for i in range(4))

    per_pod_g = {k: v.reshape((n_chunks // G, G, K) + v.shape[1:])
                 for k, v in per_pod.items()}
    final, (assign_g, scores_g, acc, first) = lax.scan(step, carry0,
                                                       per_pod_g)
    stats = jnp.stack([acc.reshape(n_chunks), first.reshape(n_chunks)],
                      axis=1)
    return (assign_g.reshape(P), scores_g.reshape(P),
            _class_usage_out(ctx, final), stats)


def speculative_reference(node_cfg: dict, usage: dict, pod_batch: dict,
                          nom: dict = None):
    """The divergence oracle: replay the SAME inputs through the serial
    class scan and fetch to host numpy. The serial kernel is the one
    copy of the decision arithmetic (the repo's bit-identity contract —
    a hand-rolled numpy replica would be a second copy free to drift),
    so any speculative/serial mismatch is a real divergence, not oracle
    noise. Returns (assign [P], scores [P]) as numpy arrays."""
    import numpy as np
    assign, scores, _ = schedule_batch(node_cfg, usage, pod_batch, nom)
    return np.asarray(assign), np.asarray(scores)


def divergence_report(spec_assign, ref_assign, width: int):
    """Attribute oracle mismatches: one dict per diverging pod with its
    cohort id (pod index // cohort width — cohorts are contiguous in
    drain order), the speculative pick, and the serial pick. Empty list
    == bit-identical, the expected steady state."""
    import numpy as np
    sa = np.asarray(spec_assign)
    ra = np.asarray(ref_assign)
    return [{"pod": int(i), "cohort": int(i // max(width, 1)),
             "speculative": int(sa[i]), "serial": int(ra[i])}
            for i in np.nonzero(sa != ra)[0]]
