"""Batched Filter+Score+Assign on device.

Replaces the reference's per-pod hot loop (pkg/scheduler/core/
generic_scheduler.go — findNodesThatFit :457 with 16 goroutines,
PrioritizeNodes :672, selectHost :286) with device kernels over a frozen
node snapshot:

  filter_score(node_cfg, usage, pod_batch) -> (fits[P,N] bool, score[P,N])
    the full pods x nodes feasibility mask and score matrix — one fused XLA
    computation, no sampling (vs numFeasibleNodesToFind's 50% shortcut,
    generic_scheduler.go:434-453).

  schedule_batch(node_cfg, usage, pod_batch) -> (assign[P], score[P], usage')
    a lax.scan over the pod axis that reproduces the reference's SERIAL
    semantics exactly — each pod sees node usage updated by every earlier
    bind (the reference achieves this with cache.AssumePod between
    iterations, scheduler.go:514) — but never leaves the device: per step it
    recomputes resource feasibility + resource scores against the running
    usage, combines the batch-invariant mask/score terms, argmaxes, and
    scatter-adds the winner's requests onto the usage tensors.

State layout (host mirror: tensorize.TensorMirror):
  node_cfg — bind-invariant per-node config: alloc [N,R], max_pods [N],
    node_ok/mem_pressure/valid [N] bool. Only informer events change it.
  usage    — bind-varying per-node accounting: used [N,R],
    nonzero_used [N,2], pod_count [N]. schedule_batch returns the
    post-batch value so consecutive batches can chain ON DEVICE without a
    host round trip (core.BatchScheduler's drain fast path).

Transfer discipline (host -> device bytes cost on any host-attached
chip, and every batch pays them before its scan can start): the pod batch
never ships [P, N] matrices. The batch-invariant mask and score
terms are deduplicated host-side — pods sharing constraint terms (one
Deployment's pods share selectors/tolerations) share a row:
    unique_masks  [U, N] bool   +  mask_idx  [P] int32
    unique_scores [S, N] f32    +  score_idx [P] int32
U and S are typically 1-8 where P is thousands, so per-batch upload is
O(P*R + U*N), a few hundred KB instead of the dense O(P*N) hundreds of MB.
And every transfer has a fixed price however few bytes it carries, so the
small replicated arrays of a launch cross in ONE buffer (pack_inputs /
unpack_inputs, the mirror image of pack_results), not one transfer each.

Scores follow the reference's integer arithmetic (LeastRequested
least_requested.go:53, BalancedAllocation balanced_resource_allocation.go:77)
via f32 floor; priorities.py is the parity oracle.

Tie-break: a sub-integer pseudo-random penalty keyed on (node row, pod seq)
rotates uniformly among max-score ties, mirroring selectHost's round-robin
intent (:286-296); parity fixtures compare score classes, not tie order.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

MAX_PRIORITY = 10.0
NEG = jnp.float32(-1e30)
#: pods per scan step (unrolled inside the step, exact serial semantics);
#: the scan is latency-bound so fewer, fatter steps win — see
#: schedule_batch. A power of two, the minimum pod bucket (8): it divides
#: every P that tensorize._bucket makes.
#: Topology-carrying batches on the CLASSIC path have their own: the
#: in-step (anti-)affinity gathers/scatters chain through the carry, so
#: fat steps buy less there (measured r05: uniform 7.7k->9.7k at G=8;
#: anti 2.3k->2.1k). The CLASS-INDEXED path (below) made the whole step
#: cheap enough that the one fat step covers topology batches too.
_STEP_GROUP = 8
_STEP_GROUP_TOPO = 1

# column layout (keep in sync with tensorize.py)
COL_CPU = 0
COL_MEM = 1


def _floor_tenths(num: jnp.ndarray, den: jnp.ndarray) -> jnp.ndarray:
    """floor(10 * num / den) for integer-valued f32 0 <= num <= den,
    den > 0 — the reference's integer division, WITHOUT a divide: the
    count of k in 1..10 with 10*num >= k*den. Multiplies and compares
    are exact on integer-valued f32 and the same on every backend; a
    divide is not: the TPU's f32 divide is not correctly rounded (about
    half its quotients differ from IEEE's), and for divisors whose
    reciprocal rounds down — 3900 among them, any node with reserved
    CPU — EVERY exact multiple came out a hair under its integer, so
    floor() scored one point low exactly on the boundaries the oracle
    hits (measured on a v5e, PR 21). The fake node's 4000m happens to
    be a benign divisor, which is why the bench fixtures never saw it."""
    ten_num = num * MAX_PRIORITY
    return sum((ten_num >= k * den).astype(jnp.float32)
               for k in range(1, int(MAX_PRIORITY) + 1))


def _unused_tenths(cap: jnp.ndarray, req: jnp.ndarray) -> jnp.ndarray:
    """One resource's LeastRequested score, (cap-req)*10 // cap (0 when
    over capacity) — the ONE copy under _least_requested and
    _class_resource_score."""
    return jnp.where((cap > 0) & (req <= cap),
                     _floor_tenths(cap - req, jnp.maximum(cap, 1.0)), 0.0)


def _div_exact(num: jnp.ndarray, den: jnp.ndarray) -> jnp.ndarray:
    """num / den that returns the exact quotient whenever it is an
    integer (k * den == num): on the TPU x / x is not 1 and 30 / 5 not 6
    for every divisor — the same not-correctly-rounded divide as in
    _floor_tenths — and a floor() downstream then loses a whole point.
    Quotients that are not integers keep the backend's rounding; the
    floors that consume those carry their own 4e-6 epsilon."""
    q = num / den
    k = jnp.round(q)
    return jnp.where(k * den == num, k, q)


def _least_requested(nz_used: jnp.ndarray, nz_req: jnp.ndarray,
                     cap_cpu: jnp.ndarray, cap_mem: jnp.ndarray) -> jnp.ndarray:
    """least_requested.go:53 — ((cap-req)*10/cap int div, avg of cpu+mem)."""
    req_cpu = nz_used[:, 0] + nz_req[0]
    req_mem = nz_used[:, 1] + nz_req[1]
    return jnp.floor((_unused_tenths(cap_cpu, req_cpu)
                      + _unused_tenths(cap_mem, req_mem)) / 2.0)


def _balanced_allocation(nz_used: jnp.ndarray, nz_req: jnp.ndarray,
                         cap_cpu: jnp.ndarray, cap_mem: jnp.ndarray) -> jnp.ndarray:
    """balanced_resource_allocation.go:77 — 10 - |cpuFrac-memFrac|*10."""
    req_cpu = nz_used[:, 0] + nz_req[0]
    req_mem = nz_used[:, 1] + nz_req[1]
    cpu_frac = jnp.where(cap_cpu > 0, req_cpu / jnp.maximum(cap_cpu, 1.0), 1.0)
    mem_frac = jnp.where(cap_mem > 0, req_mem / jnp.maximum(cap_mem, 1.0), 1.0)
    diff = jnp.abs(cpu_frac - mem_frac)
    # epsilon-floor: when (1-diff)*10 is EXACTLY an integer in exact math
    # (e.g. cpuFrac .7875, memFrac .1875 -> 4.0), f32 rounding can land a
    # hair below it while the f64 reference truncation lands at it — a
    # one-point score flip that permutes whole assignment windows (the
    # r04/r05 pod-affinity parity gap, stuck at 0.961). The nudge is far
    # above f32 error (~1e-6 at this magnitude) and far below the spacing
    # of distinct achievable scores near a boundary.
    score = jnp.floor((1.0 - diff) * MAX_PRIORITY + 4e-6)
    return jnp.where(_full(req_cpu, cap_cpu) | _full(req_mem, cap_mem),
                     0.0, score)


def _full(req: jnp.ndarray, cap: jnp.ndarray) -> jnp.ndarray:
    """fraction >= 1 — decided on the integers, not on the quotient: the
    TPU's divide returns cap / cap just under 1 for some capacities
    (3900 among them), and a full node then scored 1 instead of 0."""
    return (cap <= 0) | (req >= cap)


def _pod_feasible(node_cfg: dict, used, pod_count, pod: dict,
                  mask: jnp.ndarray) -> jnp.ndarray:
    """One pod's [N] feasibility against running usage."""
    fits_res = jnp.all(pod["req"][None, :] + used <= node_cfg["alloc"], axis=1)
    fits_count = pod_count + 1.0 <= node_cfg["max_pods"]
    blocked = pod["mem_pressure_blocked"] & node_cfg["mem_pressure"]
    return (fits_res & fits_count & node_cfg["node_ok"] &
            node_cfg["valid"] & mask & ~blocked)


def _pod_score(node_cfg: dict, nz_used, pod: dict,
               static_score: jnp.ndarray,
               rw: jnp.ndarray) -> jnp.ndarray:
    """One pod's [N] batch-varying score (resource priorities, weighted by
    rw = [LeastRequested, BalancedAllocation] from the Policy) plus the
    host-precomputed batch-invariant terms (its unique_scores row)."""
    cap_cpu = node_cfg["alloc"][:, COL_CPU]
    cap_mem = node_cfg["alloc"][:, COL_MEM]
    score = rw[0] * _least_requested(nz_used, pod["nonzero_req"],
                                     cap_cpu, cap_mem)
    score = score + rw[1] * _balanced_allocation(nz_used, pod["nonzero_req"],
                                                 cap_cpu, cap_mem)
    return score + static_score


#: SelectorSpread zone blend weight (selector_spreading.go zoneWeighting)
ZONE_WEIGHTING = 2.0 / 3.0

_BATCH_INVARIANT = ("unique_masks", "unique_scores", "resource_weights",
                    "spread_slots", "spread_nz", "spread_tab",
                    "spread_zone", "spread_zinit",
                    "spread_weight", "anti_dom", "anti_cnt0",
                    "class_req", "class_nz", "class_blocked",
                    "class_mask_idx", "class_score_idx",
                    "soft_dom", "soft_cnt0", "soft_base", "soft_weight")


def _zone_onehot(zone_of: jnp.ndarray, zinit: jnp.ndarray) -> jnp.ndarray:
    """[Z, N] f32 one-hot of the zone-id vector, built ONCE per kernel
    call: the per-step zone sums become a matvec (_zone_sums) instead of
    a scatter-add — XLA CPU serializes scatters, and the scan pays that
    cost per step."""
    z_idx = jnp.arange(zinit.shape[0], dtype=zone_of.dtype)
    return (zone_of[None, :] == z_idx[:, None]).astype(jnp.float32)


def _zone_sums(zoh: jnp.ndarray, cf: jnp.ndarray) -> jnp.ndarray:
    """[Z] per-zone sums of the [N] count vector. Counts are
    integer-valued f32, so the sum order cannot change the result — but
    only at full precision: the TPU's default matmul rounds f32 inputs
    to bf16 first (exact for integers up to 256 only), while HIGHEST
    multiplies every bf16 piece of a count by the one-hot's exact 0/1,
    which keeps the product exact (bit-identical to the scatter)."""
    return jnp.matmul(zoh, cf, precision=lax.Precision.HIGHEST)


#: bit of a spread_round_table entry that says the unblended score
#: 10 * (a / maxN) lands under its whole number in float64
_PLAIN_BIT = 11


@lru_cache(maxsize=4)
def spread_round_table(m: int):
    """[m + 1, m + 1] int32, the host-made float64 part of _spread_exact.

    Upstream computes SelectorSpread's reduce in float64
    (selector_spreading.go CalculateSpreadPriorityReduce):

        fScore    = 10 * (float64(maxN - n) / float64(maxN))
        zoneScore = 10 * (float64(maxZ - z) / float64(maxZ))
        int(fScore * (1 - 2.0/3.0) + 2.0/3.0 * zoneScore)

    The chip has no float64. The exact rational value, floored in
    integers, is upstream's answer wherever that value is no whole
    number (the nearest whole number is 1 / (3 maxN maxZ) away, float64
    errs by 1e-14); where it IS a whole number k, float64 lands on k or a
    hair under it, and int() then gives k or k - 1. Which, is a function
    of the two quotients alone, because IEEE division rounds a quotient
    by its value and not by how it is written: p = (maxN - n) / maxN and
    q = (maxZ - z) / maxZ, and given p and k, q = (3k - 10p) / 20 is
    determined. So entry [maxN, a] (a = maxN - n) holds one bit for each
    k in 0..10: float64 lands under k. Bit _PLAIN_BIT says the same of
    the unblended int(10 * (a / maxN)) that a cluster without zone labels
    gets. m is the largest count a node can hold (its pod limit)."""
    import numpy as np
    d = np.arange(m + 1, dtype=np.int64)[:, None]
    a = np.arange(m + 1, dtype=np.int64)[None, :]
    live = (d > 0) & (a <= d)
    w2 = 2.0 / 3.0
    w1 = 1.0 - w2
    with np.errstate(divide="ignore", invalid="ignore"):
        f = 10.0 * (a.astype(np.float64) / d.astype(np.float64))
        bits = np.zeros((m + 1, m + 1), np.int64)
        for k in range(11):
            qn = 3 * k * d - 10 * a
            qd = 20 * d
            zone = 10.0 * (qn.astype(np.float64) / qd.astype(np.float64))
            under = live & (qn >= 0) & (qn <= qd) & (f * w1 + w2 * zone < k)
            bits |= under.astype(np.int64) << k
        whole = live & ((10 * a) % np.maximum(d, 1) == 0)
        plain = whole & (f < (10 * a) // np.maximum(d, 1))
        bits |= plain.astype(np.int64) << _PLAIN_BIT
    return bits.astype(np.int32)


def _count_ge(x: jnp.ndarray, step: jnp.ndarray, upto: int) -> jnp.ndarray:
    """floor(x / step) for 0 <= x <= upto * step, in int32 compares: the
    count of k in 1..upto with x >= k * step (no divide: _floor_tenths)."""
    return sum((x >= k * step).astype(jnp.int32) for k in range(1, upto + 1))


def _spread_exact(n, max_n, z, max_z, zoned, have_zones, tab):
    """Upstream's int() of its float64 blend (spread_round_table has the
    expression) from integers: n [N] the group's count on the node, max_n
    the largest over the fitting nodes, z [N] the count of the node's
    zone, max_z the largest zone's, zoned [N] the node has a zone label,
    have_zones some fitting node has. All counts are integer-valued;
    max_n == 0 scores every node 10 (p = 1) and a zone without a label or
    max_z == 0 keeps the zone score 10 (q = 1), as upstream initialises
    them. The value is 10p/3 + 20q/3: its floor and whether it is whole
    come from int32 compares (quotient and remainder of each term, then
    the carry of the two remainders); a whole value takes the table's
    bit. Exact while 6 * max_n * max_z < 2**31 (core counts a batch
    that could pass it)."""
    i32 = jnp.int32
    m = tab.shape[0] - 1
    mn = max_n.astype(i32)
    d_n = jnp.maximum(mn, 1)
    a = jnp.clip(jnp.where(mn > 0, mn - n.astype(i32), 1), 0, d_n)
    mz = max_z.astype(i32)
    d_z = jnp.maximum(mz, 1)
    b = jnp.clip(jnp.where(zoned & (mz > 0), mz - z.astype(i32), d_z),
                 0, d_z)
    ten_a = 10 * a
    q_a = _count_ge(ten_a, 3 * d_n, 3)
    r_a = ten_a - 3 * d_n * q_a
    q_b = _count_ge(20 * b, 3 * d_z, 6)
    r_b = 20 * b - 3 * d_z * q_b
    t = r_a * d_z + r_b * d_n
    t3 = 3 * d_n * d_z
    e = q_a + q_b + (t >= t3).astype(i32)
    bits = tab[jnp.minimum(d_n, m), jnp.minimum(a, m)]
    blended = e - jnp.where((t == 0) | (t == t3), (bits >> e) & 1, 0)
    e0 = _count_ge(ten_a, d_n, 10)
    plain = e0 - jnp.where(ten_a == e0 * d_n, (bits >> _PLAIN_BIT) & 1, 0)
    return jnp.where(have_zones, blended, plain).astype(jnp.float32)


def _spread_score(cnt_g: jnp.ndarray, fits: jnp.ndarray,
                  zone_of: jnp.ndarray, zinit: jnp.ndarray,
                  zoh: jnp.ndarray, tab: jnp.ndarray) -> jnp.ndarray:
    """One pod's [N] SelectorSpread score from running group counts —
    the serial reduce (priorities.selector_spread_reduce /
    selector_spreading.go): invert node counts to 0-10 normalized over the
    FEASIBLE set, blend zone-level counts at weight 2/3; zone id 0 means
    'no zone label' (keeps the MaxPriority zone default, excluded from the
    zone max). The arithmetic is upstream's float64 int(), exactly
    (_spread_exact)."""
    cf = jnp.where(fits, cnt_g, 0.0)
    maxc = jnp.max(cf)
    zs = zinit + _zone_sums(zoh, cf)
    z_idx = jnp.arange(zs.shape[0])
    maxz = jnp.max(jnp.where(z_idx > 0, zs, 0.0))
    # f32 max, not jnp.any: a boolean reduce over the mesh-sharded node
    # axis lowers to a pred all-reduce, which the CPU collective backend
    # rejects (the pre-PR test_multichip XLA failures); the f32 form is
    # semantically identical and reduces everywhere
    have_zones = jnp.max(jnp.where(fits & (zone_of > 0), 1.0, 0.0)) > 0
    return _spread_exact(cnt_g, maxc, zs[zone_of], maxz, zone_of > 0,
                         have_zones, tab)


def _split_batch(pod_batch: dict):
    """(per-pod scanned arrays, unique_masks, unique_scores, rw)."""
    per_pod = {k: v for k, v in pod_batch.items()
               if k not in _BATCH_INVARIANT}
    rw = pod_batch.get("resource_weights")
    if rw is None:
        rw = jnp.ones((2,), jnp.float32)
    return per_pod, pod_batch["unique_masks"], pod_batch["unique_scores"], rw


def _spread_tables(pod_batch: dict, N: int, offset=0):
    """(base [G,N], zone_of [N], zinit [Z], weight scalar, round table)
    with inert defaults for batches without spread groups. The host ships
    what is non-zero of the base counts, spread_nz [3, S] int32 (group,
    node row, count; padding names group G and is dropped), and the
    device scatters it into zeros: spread_slots [G] is there for its
    shape. `offset` is the first row of this shard's node slice."""
    slots = pod_batch.get("spread_slots")
    if slots is None:
        return (jnp.zeros((1, N), jnp.float32),
                jnp.zeros((N,), jnp.int32),
                jnp.zeros((1,), jnp.float32),
                jnp.float32(0.0),
                jnp.zeros((1, 1), jnp.int32))
    g, row, cnt = pod_batch["spread_nz"]
    row = row - offset
    row = jnp.where((row >= 0) & (row < N), row, N)
    base = jnp.zeros((slots.shape[0], N), jnp.float32).at[g, row].add(
        cnt.astype(jnp.float32), mode="drop")
    return (base, pod_batch["spread_zone"], pod_batch["spread_zinit"],
            pod_batch["spread_weight"], pod_batch["spread_tab"])


def _spread_bump(spread, pod, col, ok_f, **scatter):
    """The winner's count: +1 at its node for EVERY group whose selectors
    match it (spread_mg [K], -1 padded: its own group and those that
    overlap it), as the serial re-count would see it."""
    mg = pod.get("spread_mg")
    if mg is None:
        return spread
    return spread.at[jnp.maximum(mg, 0), col].add(
        jnp.where(mg >= 0, ok_f, 0.0), **scatter)


@jax.jit
def filter_score(node_cfg: dict, usage: dict, pod_batch: dict
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The full pods x nodes mask + score matrix against the frozen snapshot
    (no in-batch usage updates). vmap over the pod axis."""
    pod_batch = unpack_inputs(pod_batch)
    per_pod, unique_masks, unique_scores, rw = _split_batch(pod_batch)
    N = node_cfg["alloc"].shape[0]
    spread_base, zone_of, zinit, spread_w, tab = _spread_tables(pod_batch, N)
    zoh = _zone_onehot(zone_of, zinit)

    def one(pod):
        mask = unique_masks[pod["mask_idx"]]
        static = unique_scores[pod["score_idx"]]
        fits = _pod_feasible(node_cfg, usage["used"], usage["pod_count"],
                             pod, mask)
        score = _pod_score(node_cfg, usage["nonzero_used"], pod, static, rw)
        g = pod.get("spread_gidx", jnp.int32(-1))
        use_spread = jnp.where(g >= 0, 1.0, 0.0)
        score = score + spread_w * use_spread * _spread_score(
            spread_base[jnp.maximum(g, 0)], fits, zone_of, zinit, zoh, tab)
        return fits, jnp.where(fits, score, NEG)
    return jax.vmap(one)(per_pod)


def _soft_tables(pod_batch: dict):
    """(soft_dom [Ts,N], soft_cnt0 [Ts,Ds], soft_base [Sb,N], weight) or
    None — the in-scan preferred inter-pod (anti-)affinity credit tables
    (core._assign_soft_terms)."""
    dom = pod_batch.get("soft_dom")
    if dom is None:
        return None
    return (dom, pod_batch["soft_cnt0"], pod_batch["soft_base"],
            pod_batch["soft_weight"])


def _soft_raw(soft_dom, scnt, soft_base, pod):
    """One pod's [N] raw inter-pod affinity score from the frozen base row
    plus the running per-(term, domain) in-batch credit accumulators —
    the serial reference's per-pod re-count (interpod_affinity.go) over
    batch winners, in the scan carry."""
    rt = pod["soft_read_tids"]                       # [Ks], -1 padded
    t = jnp.maximum(rt, 0)
    drow = soft_dom[t]                               # [Ks, N]
    at = jnp.take_along_axis(scnt[t], jnp.maximum(drow, 0), axis=1)
    valid = (rt[:, None] >= 0) & (drow >= 0)
    delta = (pod["soft_read_w"][:, None]
             * jnp.where(valid, at, 0.0)).sum(axis=0)
    return soft_base[jnp.maximum(pod["soft_base_idx"], 0)] + delta


def _soft_score(raw, fits, weight):
    """minmax_normalize over the CURRENT feasible set (the oracle's
    domain: prioritize_nodes normalizes over filtered nodes), min and max
    from 0 as upstream starts them, floored with the same 4e-6 epsilon as
    _balanced_allocation (f32 vs the oracle's f64 can land a hair under
    an exact-integer boundary)."""
    mn = jnp.minimum(jnp.min(jnp.where(fits, raw, jnp.inf)), 0.0)
    mx = jnp.maximum(jnp.max(jnp.where(fits, raw, -jnp.inf)), 0.0)
    span_ok = mx > mn
    norm = jnp.floor(MAX_PRIORITY * (raw - mn)
                     / jnp.maximum(mx - mn, jnp.float32(1e-30)) + 4e-6)
    return jnp.where(span_ok, weight * norm, 0.0)


def _class_resource_score(cap_cpu, cap_mem, req_cpu, req_mem, rw):
    """LeastRequested + BalancedAllocation over pre-broadcast class/node
    axes — the ONE copy of the f32 arithmetic (cap guards, floors, the
    4e-6 boundary epsilon) shared by _class_col (one node row) and
    _class_ms_init (all rows). Elementwise mirror of _least_requested /
    _balanced_allocation, so class-path decisions stay bit-identical to
    the classic per-pod path."""
    lr = jnp.floor((_unused_tenths(cap_cpu, req_cpu)
                    + _unused_tenths(cap_mem, req_mem)) / 2.0)
    cpu_frac = jnp.where(cap_cpu > 0, req_cpu / jnp.maximum(cap_cpu, 1.0),
                         1.0)
    mem_frac = jnp.where(cap_mem > 0, req_mem / jnp.maximum(cap_mem, 1.0),
                         1.0)
    ba = jnp.floor((1.0 - jnp.abs(cpu_frac - mem_frac)) * MAX_PRIORITY
                   + 4e-6)
    ba = jnp.where(_full(req_cpu, cap_cpu) | _full(req_mem, cap_mem),
                   0.0, ba)
    return rw[0] * lr + rw[1] * ba


def _class_col(node_cfg: dict, cls: dict, unique_masks, unique_scores, rw,
               used_b, nz_b, cnt_b, b):
    """Recompute every template class's masked score at ONE node row `b`
    (the only row a winner's bind changes) — [C] f32, NEG where
    infeasible. Same elementwise f32 arithmetic as the classic per-pod
    path, so decisions are bit-identical."""
    alloc_b = node_cfg["alloc"][b]                                 # [R]
    fits = jnp.all(cls["class_req"] + used_b[None, :]
                   <= alloc_b[None, :], axis=1)                    # [C]
    fits &= cnt_b + 1.0 <= node_cfg["max_pods"][b]
    fits &= ~(cls["class_blocked"] & node_cfg["mem_pressure"][b])
    fits &= node_cfg["node_ok"][b] & node_cfg["valid"][b]
    fits &= unique_masks[cls["class_mask_idx"], b]
    score = _class_resource_score(
        alloc_b[COL_CPU], alloc_b[COL_MEM],
        nz_b[0] + cls["class_nz"][:, 0],
        nz_b[1] + cls["class_nz"][:, 1], rw) \
        + unique_scores[cls["class_score_idx"], b]
    return jnp.where(fits, score, NEG)


def _class_ms_init(node_cfg: dict, usage: dict, cls: dict,
                   unique_masks, unique_scores, rw):
    """[C, N] masked-score table at batch start — the same arithmetic as
    _class_col, vectorized over the node axis (computed once per batch;
    the scan then refreshes one COLUMN per winner instead of recomputing
    [N, R] feasibility + scores per pod)."""
    used = usage["used"]                                           # [N, R]
    nz = usage["nonzero_used"]                                     # [N, 2]
    cnt = usage["pod_count"]                                       # [N]
    alloc = node_cfg["alloc"]
    C = cls["class_req"].shape[0]
    R = alloc.shape[1]
    fits = jnp.ones((C, alloc.shape[0]), bool)
    for r in range(R):  # static unroll: no [C, N, R] intermediate
        fits &= cls["class_req"][:, r][:, None] + used[None, :, r] \
            <= alloc[None, :, r]
    fits &= (cnt + 1.0 <= node_cfg["max_pods"])[None, :]
    fits &= ~(cls["class_blocked"][:, None]
              & node_cfg["mem_pressure"][None, :])
    fits &= (node_cfg["node_ok"] & node_cfg["valid"])[None, :]
    fits &= unique_masks[cls["class_mask_idx"]]
    score = _class_resource_score(
        alloc[:, COL_CPU][None, :], alloc[:, COL_MEM][None, :],
        nz[:, 0][None, :] + cls["class_nz"][:, 0][:, None],
        nz[:, 1][None, :] + cls["class_nz"][:, 1][:, None], rw) \
        + unique_scores[cls["class_score_idx"]]
    return jnp.where(fits, score, NEG)


def _term_hits(anti_dom, table, tids):
    """[K,N] bool: node's domain holds an in-batch hit for term tids[k]
    in `table` (-1 = padding, never hits)."""
    t = jnp.maximum(tids, 0)                          # [K]
    drow = anti_dom[t]                                # [K,N]
    at = jnp.take_along_axis(
        table[t], jnp.maximum(drow, 0), axis=1)       # [K,N]
    return (tids[:, None] >= 0) & (drow >= 0) & (at > 0.0)


def _topo_bad(anti_dom, carry, pod, has_dir2):
    """[N] bool: nodes this pod may NOT take because of in-batch winners'
    required (anti-)affinity — direction 1 (pod CARRIES an anti term, a
    winner MATCHES it in the domain), direction 2 (pod MATCHES a term a
    winner CARRIES, when the carry table ships), and waived required
    affinity (once ANY winner matches the term, later carriers must
    co-locate into its domain). ONE copy for the classic and
    class-indexed kernels: their contract is bit-identical decisions, so
    this mask arithmetic must never diverge between them."""
    bad = _term_hits(anti_dom, carry["topo_cnt"],
                     pod["anti_tids"]).any(axis=0)
    if has_dir2:
        bad = bad | _term_hits(anti_dom, carry["topo_carry"],
                               pod["cmatch_tids"]).any(axis=0)
    atids = pod["aff_tids"]
    need = (atids >= 0) & (carry["topo_tot"][jnp.maximum(atids, 0)] > 0.0)
    bad = bad | (need[:, None] & ~_term_hits(
        anti_dom, carry["topo_cnt"], atids)).any(axis=0)
    return bad


def _topo_scatter(anti_dom, carry, pod, best, ok, has_dir2):
    """The winner's (term, domain) counter updates: one [K]-vector
    scatter-add per table instead of K chained scatters (duplicate padded
    indices add 0, .at accumulates safely). Shared by both kernels for
    the same bit-identity reason as _topo_bad."""
    mtids = pod["match_tids"]                         # [K]
    mt = jnp.maximum(mtids, 0)
    md = anti_dom[mt, best]                           # [K]
    val = ((mtids >= 0) & (md >= 0) & ok).astype(jnp.float32)
    out = {"topo_cnt": carry["topo_cnt"].at[
               mt, jnp.maximum(md, 0)].add(val),
           "topo_tot": carry["topo_tot"].at[mt].add(val)}
    if has_dir2:
        atids2 = pod["canti_tids"]
        at2 = jnp.maximum(atids2, 0)
        ad = anti_dom[at2, best]
        aval = ((atids2 >= 0) & (ad >= 0) & ok).astype(jnp.float32)
        out["topo_carry"] = carry["topo_carry"].at[
            at2, jnp.maximum(ad, 0)].add(aval)
    return out


#: "was feasible" threshold for the class path: real masked scores are
#: small-magnitude; NEG marks infeasible. Strictly between them.
_NEG_THRESHOLD = jnp.float32(-1e29)


def _tie_penalized(masked, rows, seq):
    """selectHost rotates among max-score ties across cycles (:286-296):
    sub-integer hash penalty keyed on (node row, pod seq). Base scores
    are integers spaced >= 1 and the penalty is < 0.5, so cross-class
    ranking is intact. ONE copy for the classic, class-indexed, gang,
    and sharded kernels — the hash is part of the DECISION, so it must
    never diverge between them (the sharded kernel feeds GLOBAL row ids,
    making its penalties match the single-device kernel bit for bit);
    the host replicas (core._RepairReassigner, the gang oracle, bench's
    parity oracle) mirror the same constants in int64+mask form."""
    h = jnp.bitwise_and(rows * jnp.int32(-1640531527) +
                        seq * jnp.int32(40503), 0xFFFF)
    return masked - h.astype(jnp.float32) * jnp.float32(0.5 / 65536.0)


def _soft_write(soft_dom, soft_cnt, pod, best, ok):
    """The winner's soft-credit writes: +1 per matched read channel,
    +weight per carried preferred/required-affinity channel, at the
    chosen node's domains. ONE copy for the classic, class-indexed, and
    gang kernels (bit-identity contract, like _topo_scatter)."""
    wtids = pod["soft_write_tids"]                    # [Ks]
    wt = jnp.maximum(wtids, 0)
    wd = soft_dom[wt, best]                           # [Ks]
    wval = jnp.where((wtids >= 0) & (wd >= 0) & ok,
                     pod["soft_write_w"], 0.0)
    return soft_cnt.at[wt, jnp.maximum(wd, 0)].add(wval)


def _nom_feas_usage(usage: dict, nom: dict) -> dict:
    """Usage with the phantom nominated reservations folded into the
    FEASIBILITY columns (used/pod_count) only — scores stay on real usage
    (nonzero_used), matching PrioritizeNodes ranking against the snapshot
    and the classic kernel's eff_used/eff_count arithmetic."""
    return {"used": usage["used"] + nom["used"],
            "nonzero_used": usage["nonzero_used"],
            "pod_count": usage["pod_count"] + nom["count"]}


def _class_ctx(node_cfg: dict, usage: dict, pod_batch: dict, nom: dict):
    """Setup of the class-indexed scan: split the batch, resolve the
    optional term tables, build the [C, N] masked-score table and the
    initial carry. Returns (ctx, carry0, per_pod)."""
    per_pod, unique_masks, unique_scores, rw = _split_batch(pod_batch)
    N = node_cfg["alloc"].shape[0]
    cls = {k: pod_batch[k] for k in ("class_req", "class_nz",
                                     "class_blocked", "class_mask_idx",
                                     "class_score_idx")}
    anti_dom = pod_batch.get("anti_dom")
    has_topo = anti_dom is not None
    has_dir2 = has_topo and "cmatch_tids" in pod_batch
    has_spread = pod_batch.get("spread_slots") is not None
    spread_base, zone_of, zinit, spread_w, tab = _spread_tables(pod_batch, N)
    zoh = _zone_onehot(zone_of, zinit)
    soft = _soft_tables(pod_batch)
    has_soft = soft is not None
    has_nom = nom is not None
    ms0 = _class_ms_init(node_cfg,
                         _nom_feas_usage(usage, nom) if has_nom else usage,
                         cls, unique_masks, unique_scores, rw)
    ctx = {"node_cfg": node_cfg, "cls": cls, "unique_masks": unique_masks,
           "unique_scores": unique_scores, "rw": rw,
           "rows": jnp.arange(N, dtype=jnp.int32), "N": N,
           "anti_dom": anti_dom, "has_topo": has_topo,
           "has_dir2": has_dir2, "has_spread": has_spread,
           "spread_w": spread_w, "zone_of": zone_of, "zinit": zinit,
           "zoh": zoh, "spread_tab": tab, "soft": soft, "has_soft": has_soft,
           "has_nom": has_nom, "nom": nom}
    carry0 = {"used": usage["used"], "nz_used": usage["nonzero_used"],
              "pod_count": usage["pod_count"], "ms": ms0}
    if has_topo:
        carry0["topo_cnt"] = pod_batch["anti_cnt0"]
        carry0["topo_tot"] = jnp.zeros((anti_dom.shape[0],), jnp.float32)
        if has_dir2:
            carry0["topo_carry"] = jnp.zeros_like(pod_batch["anti_cnt0"])
    if has_spread:
        # chained launches seed the spread/soft carries from the
        # predecessor's finals (same contract as the classic path)
        sp0 = usage.get("spread")
        carry0["spread"] = sp0 if sp0 is not None else spread_base
    if has_soft:
        sc0 = usage.get("soft_cnt")
        carry0["soft_cnt"] = sc0 if sc0 is not None else soft[1]
    return ctx, carry0, per_pod


def _class_pod_step(ctx, carry, pod):
    """One pod's serial class-scan step: gather its class's masked-score
    row, apply the carry-dependent terms, argmax, scatter the winner's
    usage and refresh the winner's COLUMN across all classes."""
    node_cfg = ctx["node_cfg"]
    cls = ctx["cls"]
    unique_masks, unique_scores = ctx["unique_masks"], ctx["unique_scores"]
    rw, rows, N = ctx["rw"], ctx["rows"], ctx["N"]
    nom = ctx["nom"]
    u = pod["class_idx"]
    base = carry["ms"][u]                                      # [N]
    if ctx["has_nom"]:
        # self-exemption: the pod's own nominated row is recomputed
        # with eff = (used + nom) - own req / count - 1 — the same
        # f32 op order as the classic kernel's self_oh subtraction
        r = pod.get("nom_row", jnp.int32(-1))
        rc = jnp.clip(r, 0, N - 1)
        corr = _class_col(
            node_cfg, cls, unique_masks, unique_scores, rw,
            carry["used"][rc] + nom["used"][rc] - cls["class_req"][u],
            carry["nz_used"][rc],
            carry["pod_count"][rc] + nom["count"][rc] - 1.0, rc)[u]
        base = jnp.where((r >= 0) & (rows == r), corr, base)
    fits = base > _NEG_THRESHOLD
    if ctx["has_topo"]:
        # both (anti-)affinity directions + waived co-location, from
        # the running counters (_topo_bad — shared with the classic
        # kernel so the mask arithmetic can't diverge)
        fits = fits & ~_topo_bad(ctx["anti_dom"], carry, pod,
                                 ctx["has_dir2"])
    score = base
    if ctx["has_soft"]:
        soft_dom, _, soft_base, soft_w = ctx["soft"]
        raw = _soft_raw(soft_dom, carry["soft_cnt"], soft_base, pod)
        score = score + jnp.where(pod["soft_base_idx"] >= 0,
                                  _soft_score(raw, fits, soft_w), 0.0)
    if ctx["has_spread"]:
        g = pod.get("spread_gidx", jnp.int32(-1))
        use_spread = jnp.where(g >= 0, 1.0, 0.0)
        score = score + ctx["spread_w"] * use_spread * _spread_score(
            carry["spread"][jnp.maximum(g, 0)], fits, ctx["zone_of"],
            ctx["zinit"], ctx["zoh"], ctx["spread_tab"])
    masked = jnp.where(fits, score, NEG)
    best = jnp.argmax(_tie_penalized(masked, rows, pod["seq"])) \
        .astype(jnp.int32)
    chosen = masked[best]
    ok = (chosen > _NEG_THRESHOLD) & pod["active"]
    ok_f = jnp.where(ok, 1.0, 0.0)
    used = carry["used"].at[best].add(ok_f * cls["class_req"][u])
    nz_used = carry["nz_used"].at[best].add(ok_f * cls["class_nz"][u])
    pod_count = carry["pod_count"].at[best].add(ok_f)
    if ctx["has_nom"]:
        col = _class_col(node_cfg, cls, unique_masks, unique_scores,
                         rw, used[best] + nom["used"][best],
                         nz_used[best],
                         pod_count[best] + nom["count"][best], best)
    else:
        col = _class_col(node_cfg, cls, unique_masks, unique_scores,
                         rw, used[best], nz_used[best],
                         pod_count[best], best)
    out = {"used": used, "nz_used": nz_used, "pod_count": pod_count,
           "ms": carry["ms"].at[:, best].set(col)}
    if ctx["has_spread"]:
        out["spread"] = _spread_bump(carry["spread"], pod, best, ok_f)
    if ctx["has_topo"]:
        out.update(_topo_scatter(ctx["anti_dom"], carry, pod, best, ok,
                                 ctx["has_dir2"]))
    if ctx["has_soft"]:
        soft_dom = ctx["soft"][0]
        out["soft_cnt"] = _soft_write(soft_dom, carry["soft_cnt"],
                                      pod, best, ok)
    assign = jnp.where(ok, best, jnp.int32(-1))
    return out, (assign, chosen)


def _class_usage_out(ctx, final) -> dict:
    """The post-batch usage dict from a class-scan carry final (spread/
    soft carry finals ride along for the next chained launch)."""
    new_usage = {"used": final["used"],
                 "nonzero_used": final["nz_used"],
                 "pod_count": final["pod_count"]}
    if ctx["has_spread"]:
        new_usage["spread"] = final["spread"]
    if ctx["has_soft"]:
        new_usage["soft_cnt"] = final["soft_cnt"]
    return new_usage


def _schedule_batch_classes(node_cfg: dict, usage: dict, pod_batch: dict,
                            nom: dict = None):
    """The class-indexed incremental scan: pods sharing a (template,
    score-row) class share a precomputed masked-score ROW; a scan step
    gathers its pod's row, argmaxes, and refreshes only the winner's
    COLUMN across all classes (the single node whose usage changed).
    Per-step cost drops from O(N*R) feasibility+score recompute to
    O(N + C*R) — the change that lets topology batches run fat scan
    steps instead of the r05 alignment-split workaround.

    Semantics and f32 arithmetic are bit-identical to the classic path
    (tests/test_topo_cache.py + tests/test_class_fastpath.py pin
    decisions). Every non-gang batch shape rides here now:

      - spread groups: per-group running counts in the carry, the
        winner bumping every matching group (_spread_bump) —
        identical to the classic kernel's in-scan spread.
      - soft inter-pod credits: the per-(term, domain) channel
        accumulators in the carry, read/written per pod.
      - nominated reservations: the phantom {used, count} overlay is
        folded into the masked-score table's FEASIBILITY at build time
        and at every winner-column refresh; a pod's own reservation at
        its nominated row is re-credited by recomputing that ONE column
        with the self-subtracted overlay (the classic kernel's self_oh
        arithmetic, so the f32 ops match bit for bit).

    A chained launch seeds the spread/soft carries from the predecessor's
    finals (usage["spread"] / usage["soft_cnt"], riding the same device
    handle as the chained usage — core.schedule_launch gates this on the
    anchor's base tables still applying).

    The per-pod step lives in _class_pod_step and the setup in
    _class_ctx."""
    ctx, carry0, per_pod = _class_ctx(node_cfg, usage, pod_batch, nom)
    P = per_pod["seq"].shape[0]
    G = min(_STEP_GROUP, P)

    def step(carry, podg):
        outs = []
        for g in range(G):
            pod = {k: v[g] for k, v in podg.items()}
            carry, out = _class_pod_step(ctx, carry, pod)
            outs.append(out)
        return carry, (jnp.stack([o[0] for o in outs]),
                       jnp.stack([o[1] for o in outs]))

    per_pod_g = {k: v.reshape((P // G, G) + v.shape[1:])
                 for k, v in per_pod.items()}
    final, (assign_g, scores_g) = lax.scan(step, carry0, per_pod_g)
    return assign_g.reshape(P), scores_g.reshape(P), \
        _class_usage_out(ctx, final)


@jax.jit
def schedule_batch(node_cfg: dict, usage: dict, pod_batch: dict,
                   nom: dict = None):
    """Serial-semantics greedy assignment, fully on device.

    Returns (assign [P] int32 node row or -1, chosen_score [P] f32,
    new_usage dict). new_usage chains into the next batch's call during a
    queue drain (core.BatchScheduler fast path) so N batches cost N device
    dispatches and zero usage re-uploads; the cache remains the source of
    truth between drains (assume/forget -> mirror dirty rows).

    `nom` carries aggregated nominated-pod reservations (preemption's
    freed space, scheduler.go:292-380): used [N,R], nz [N,2], count [N].
    Feasibility treats them as phantom usage so no pod steals a nominated
    node's space, except the nominee itself — each pod's own contribution
    is subtracted at its `nom_row` (its nominated node's row, -1 if none).
    Deviation from the reference's two-pass nominated check
    (generic_scheduler.go:598-664): the reservation shields against ALL
    other pods, not just lower-priority ones — strictly more conservative;
    a higher-priority pod pushed off a full nominated node preempts
    instead. Scores stay on real usage (matching PrioritizeNodes, which
    ranks against the snapshot).

    Dispatch (trace-time, by pytree structure): batches carrying class
    tables (tensorize.PodBatchTensors.enable_class_scan) route to the
    incremental class-indexed scan — spread groups, soft in-scan
    credits, and nominated reservations now ride it as carried state.
    The classic per-pod recompute below remains as the one-source parity
    control (`class_scan` off, hand-built batches in tests)."""
    pod_batch = unpack_inputs(pod_batch)
    if "class_req" in pod_batch:
        return _schedule_batch_classes(node_cfg, usage, pod_batch, nom)
    per_pod, unique_masks, unique_scores, rw = _split_batch(pod_batch)
    N = node_cfg["alloc"].shape[0]
    spread_base, zone_of, zinit, spread_w, tab = _spread_tables(pod_batch, N)
    zoh = _zone_onehot(zone_of, zinit)
    soft = _soft_tables(pod_batch)
    has_soft = soft is not None
    if has_soft:
        soft_dom, soft_cnt0, soft_base, soft_w = soft
    #: in-scan required (anti-)affinity: per-term node->domain rows plus
    #: running (term, domain) match counters — the BatchOverlay's
    #: serial-winner visibility, ON DEVICE, so the kernel's picks already
    #: respect earlier same-batch winners instead of being repaired after
    anti_dom = pod_batch.get("anti_dom")        # [T, N] int32, -1=no label
    has_topo = anti_dom is not None
    has_dir2 = has_topo and "cmatch_tids" in pod_batch
    rows = jnp.arange(N, dtype=jnp.int32)
    if nom is None:
        nom = {"used": jnp.zeros_like(usage["used"]),
               "count": jnp.zeros_like(usage["pod_count"])}

    def one_pod(carry, pod):
        mask = unique_masks[pod["mask_idx"]]
        static = unique_scores[pod["score_idx"]]
        self_oh = rows == pod.get("nom_row", jnp.int32(-1))
        eff_used = carry["used"] + nom["used"] - \
            jnp.where(self_oh[:, None], pod["req"][None, :], 0.0)
        eff_count = carry["pod_count"] + nom["count"] \
            - self_oh.astype(jnp.float32)
        fits = _pod_feasible(node_cfg, eff_used, eff_count, pod, mask)
        if has_topo:
            # per-pod term lists ([K] tids, -1 padded) keep this O(K*N)
            # per step instead of O(T*N): a pod carries/matches only a
            # handful of terms, while the batch's union can be hundreds.
            # The K axis is VECTORIZED — one [K,N] gather + one reduce —
            # not a Python loop: K unrolled iterations serialize K
            # dependent gathers in the scan's HLO (the r04 anti-affinity
            # regression, 2.5k -> 1.7k pods/s). _topo_bad is shared with
            # the class-indexed kernel (bit-identity contract).
            fits = fits & ~_topo_bad(anti_dom, carry, pod, has_dir2)
        score = _pod_score(node_cfg, carry["nz_used"], pod, static, rw)
        if has_soft:
            # preferred inter-pod (anti-)affinity runs IN-SCAN from running
            # per-(term, domain) credit accumulators — the serial
            # reference's per-pod re-score via assume-between-iterations,
            # which SOFT_SCORE_CHUNK sub-batching used to approximate
            raw = _soft_raw(soft_dom, carry["soft_cnt"], soft_base, pod)
            score = score + jnp.where(
                pod["soft_base_idx"] >= 0,
                _soft_score(raw, fits, soft_w), 0.0)
        # SelectorSpread runs IN-SCAN from running group counts — the
        # serial reference recounts per pod via assume-between-iterations
        # (selector_spreading.go:277); a frozen batch-start score would
        # clump one controller's pods onto the same "least loaded" nodes
        g = pod.get("spread_gidx", jnp.int32(-1))
        gi = jnp.maximum(g, 0)
        use_spread = jnp.where(g >= 0, 1.0, 0.0)
        score = score + spread_w * use_spread * _spread_score(
            carry["spread"][gi], fits, zone_of, zinit, zoh, tab)
        masked = jnp.where(fits, score, NEG)
        best = jnp.argmax(_tie_penalized(masked, rows, pod["seq"])) \
            .astype(jnp.int32)
        ok = fits[best] & pod["active"]
        onehot = (rows == best) & ok
        oh_f = onehot.astype(jnp.float32)
        ok_f = jnp.where(ok, 1.0, 0.0)
        out = {
            "used": carry["used"] + oh_f[:, None] * pod["req"][None, :],
            "nz_used": carry["nz_used"]
            + oh_f[:, None] * pod["nonzero_req"][None, :],
            "pod_count": carry["pod_count"] + oh_f,
            # a winner bumps EVERY spread group whose selectors match
            # it, not only its own (_spread_bump)
            "spread": _spread_bump(carry["spread"], pod, best, ok_f),
        }
        if has_topo:
            out.update(_topo_scatter(anti_dom, carry, pod, best, ok,
                                     has_dir2))
        if has_soft:
            # the winner's credit writes: +1 per matched read channel,
            # +weight per carried preferred/required-affinity channel
            out["soft_cnt"] = _soft_write(soft_dom, carry["soft_cnt"],
                                          pod, best, ok)
        assign = jnp.where(ok, best, jnp.int32(-1))
        return out, (assign, masked[best])

    # chained launches seed the spread/soft carries from the
    # predecessor's finals (same contract as the class-indexed path)
    sp0 = usage.get("spread")
    carry0 = {"used": usage["used"], "nz_used": usage["nonzero_used"],
              "pod_count": usage["pod_count"],
              "spread": sp0 if sp0 is not None else spread_base}
    if has_topo:
        carry0["topo_cnt"] = pod_batch["anti_cnt0"]
        carry0["topo_tot"] = jnp.zeros((anti_dom.shape[0],), jnp.float32)
        if has_dir2:
            carry0["topo_carry"] = jnp.zeros_like(pod_batch["anti_cnt0"])
    if has_soft:
        sc0 = usage.get("soft_cnt")
        carry0["soft_cnt"] = sc0 if sc0 is not None else soft_cnt0
    # STEP GROUPING: the scan is latency-bound — each step's compute
    # ([N]-vector ops) is tiny next to the per-step sequencing overhead,
    # so a P-step scan costs ~P * step_latency regardless of N. Packing G
    # pods per step (unrolled inside, SAME op sequence -> bit-identical
    # results) cuts the step count G-fold. P is always a power of two
    # >= 8 (tensorize._bucket), so G=8 divides it exactly.
    P = per_pod["seq"].shape[0]
    G = min(_STEP_GROUP_TOPO if has_topo else _STEP_GROUP, P)

    def step(carry, podg):
        outs = []
        for g in range(G):
            pod = {k: v[g] for k, v in podg.items()}
            carry, out = one_pod(carry, pod)
            outs.append(out)
        return carry, (jnp.stack([o[0] for o in outs]),
                       jnp.stack([o[1] for o in outs]))

    per_pod_g = {k: v.reshape((P // G, G) + v.shape[1:])
                 for k, v in per_pod.items()}
    final, (assign_g, scores_g) = lax.scan(step, carry0, per_pod_g)
    new_usage = {"used": final["used"],
                 "nonzero_used": final["nz_used"],
                 "pod_count": final["pod_count"]}
    if pod_batch.get("spread_slots") is not None:
        new_usage["spread"] = final["spread"]
    if has_soft:
        new_usage["soft_cnt"] = final["soft_cnt"]
    return assign_g.reshape(P), scores_g.reshape(P), new_usage


# ------------------------------------------------------------- sharded scan
#
# The class-indexed scan under jax.shard_map over a 1-D
# "nodes" mesh axis (sharding.py owns the axis name and the name-keyed
# partition rules). Each shard holds its node slice of the mirror
# (cfg/usage rows), the mask/score tables' node columns, and the [C, N]
# masked-score carry; a scan step runs filter+score over the LOCAL slice
# and reduces to a winner with a cross-shard argmax over (penalized
# score, global node id):
#
#     per shard:   local max + first-max row of (masked - tie_penalty)
#     cross-shard: pmax(score)  -> the global max
#                  pmin(row where local max == global max) -> the winner
#
# f32 max is exact and ties resolve to the LOWEST global row — precisely
# jnp.argmax's first-max-index semantics on one device, so decisions are
# bit-identical to _schedule_batch_classes (the parity-1.0 and chaos
# determinism contracts survive sharding). The winner's column refresh
# and usage scatter stay local to the owning shard (non-owners write
# through an out-of-range index with mode="drop"); the winner's masked
# score and its (anti-)affinity domain ids are broadcast from the owner
# (re-deriving the score from the penalized max would re-round).
#
# GSPMD (plain jit over sharded inputs) remains the path for gang
# batches and for KTPU_SHARD_MAP=0 (the pjit-vs-shard_map selection
# knob). Spread groups, soft credits, and nominated reservations ride
# the shard_map kernel as carried/overlaid state:
#
#   spread — group counts replicate? No: the [G, N] count rows shard on
#     the node axis (the scattered base counts); the per-step normalization needs
#     the GLOBAL max count and zone sums, which are one pmax + one psum
#     of integer-valued f32 (exact in any order, so bit-identical).
#   soft — the [Ts, Ds] channel accumulators replicate; the winner's
#     domain ids broadcast from the owning shard (pmax over -1 padding,
#     the _topo_scatter_sharded recipe), so every shard applies the
#     identical scatter-add. Min-max normalization is a pmin/pmax pair.
#   nominated — the phantom overlay shards with the mirror rows
#     (P("nodes")); the self-exemption column recomputes on the owning
#     shard and drops everywhere else.

_INT32_MAX = jnp.int32(2147483647)


def _spread_score_sharded(cnt_g, fits, zone_of, zinit, zoh, tab):
    """_spread_score under shard_map: cnt_g/fits/zone_of/zoh are the
    LOCAL node slice; the max count, zone sums, and zone presence reduce
    across shards. All reduced values are integer-valued f32 (counts),
    so psum/pmax are order-insensitive and the result is bit-identical
    to the single-device reduce."""
    from ..sharding import NODE_AXIS
    cf = jnp.where(fits, cnt_g, 0.0)
    maxc = lax.pmax(jnp.max(cf), NODE_AXIS)
    zs = zinit + lax.psum(_zone_sums(zoh, cf), NODE_AXIS)
    z_idx = jnp.arange(zs.shape[0])
    maxz = jnp.max(jnp.where(z_idx > 0, zs, 0.0))
    have_zones = lax.pmax(
        jnp.max(jnp.where(fits & (zone_of > 0), 1.0, 0.0)), NODE_AXIS) > 0
    return _spread_exact(cnt_g, maxc, zs[zone_of], maxz, zone_of > 0,
                         have_zones, tab)


def _soft_score_sharded(raw, fits, weight):
    """_soft_score with the min-max normalization domain reduced across
    shards (f32 min/max are exact, so bit-identical)."""
    from ..sharding import NODE_AXIS
    mn = jnp.minimum(
        lax.pmin(jnp.min(jnp.where(fits, raw, jnp.inf)), NODE_AXIS), 0.0)
    mx = jnp.maximum(
        lax.pmax(jnp.max(jnp.where(fits, raw, -jnp.inf)), NODE_AXIS), 0.0)
    span_ok = mx > mn
    norm = jnp.floor(MAX_PRIORITY * (raw - mn)
                     / jnp.maximum(mx - mn, jnp.float32(1e-30)) + 4e-6)
    return jnp.where(span_ok, weight * norm, 0.0)


def _sharded_class_scan(node_cfg: dict, usage: dict, pod_batch: dict,
                        nom: dict = None):
    """shard_map body: every node-axis array here is the LOCAL shard."""
    from ..sharding import NODE_AXIS
    per_pod, unique_masks, unique_scores, rw = _split_batch(pod_batch)
    Nl = node_cfg["alloc"].shape[0]
    offset = lax.axis_index(NODE_AXIS).astype(jnp.int32) * Nl
    rows_g = offset + jnp.arange(Nl, dtype=jnp.int32)
    cls = {k: pod_batch[k] for k in ("class_req", "class_nz",
                                     "class_blocked", "class_mask_idx",
                                     "class_score_idx")}
    anti_dom = pod_batch.get("anti_dom")
    has_topo = anti_dom is not None
    has_dir2 = has_topo and "cmatch_tids" in pod_batch
    has_spread = pod_batch.get("spread_slots") is not None
    spread_base, zone_of, zinit, spread_w, tab = _spread_tables(
        pod_batch, Nl, offset)
    zoh = _zone_onehot(zone_of, zinit)
    soft = _soft_tables(pod_batch)
    has_soft = soft is not None
    if has_soft:
        soft_dom, soft_cnt0, soft_base, soft_w = soft
    has_nom = nom is not None
    ms0 = _class_ms_init(node_cfg,
                         _nom_feas_usage(usage, nom) if has_nom else usage,
                         cls, unique_masks, unique_scores, rw)

    def one_pod(carry, pod):
        u = pod["class_idx"]
        base = carry["ms"][u]                                      # [Nl]
        if has_nom:
            # self-exemption column on the owning shard only (nom_row is
            # a GLOBAL row id); other shards drop the write
            r = pod.get("nom_row", jnp.int32(-1))
            lrn = r - offset
            own_n = (r >= 0) & (lrn >= 0) & (lrn < Nl)
            lrc = jnp.clip(lrn, 0, Nl - 1)
            corr = _class_col(
                node_cfg, cls, unique_masks, unique_scores, rw,
                carry["used"][lrc] + nom["used"][lrc]
                - cls["class_req"][u],
                carry["nz_used"][lrc],
                carry["pod_count"][lrc] + nom["count"][lrc] - 1.0, lrc)[u]
            base = base.at[jnp.where(own_n, lrn, Nl)].set(corr,
                                                          mode="drop")
        fits = base > _NEG_THRESHOLD
        if has_topo:
            fits = fits & ~_topo_bad(anti_dom, carry, pod, has_dir2)
        score = base
        if has_soft:
            raw = _soft_raw(soft_dom, carry["soft_cnt"], soft_base, pod)
            score = score + jnp.where(
                pod["soft_base_idx"] >= 0,
                _soft_score_sharded(raw, fits, soft_w), 0.0)
        if has_spread:
            g = pod.get("spread_gidx", jnp.int32(-1))
            use_spread = jnp.where(g >= 0, 1.0, 0.0)
            score = score + spread_w * use_spread * _spread_score_sharded(
                carry["spread"][jnp.maximum(g, 0)], fits, zone_of, zinit,
                zoh, tab)
        masked = jnp.where(fits, score, NEG)
        # tie-break hash on the GLOBAL row id — identical inputs to the
        # single-device kernel's (row, seq) penalty
        penalized = _tie_penalized(masked, rows_g, pod["seq"])
        lmax = jnp.max(penalized)
        lbest = jnp.argmax(penalized).astype(jnp.int32)  # first max, local
        gmax = lax.pmax(lmax, NODE_AXIS)
        best = lax.pmin(jnp.where(lmax == gmax, offset + lbest,
                                  _INT32_MAX), NODE_AXIS)
        lb = best - offset
        owner = (lb >= 0) & (lb < Nl)
        lbc = jnp.clip(lb, 0, Nl - 1)
        chosen = lax.pmax(jnp.where(owner, masked[lbc], NEG), NODE_AXIS)
        ok = (chosen > _NEG_THRESHOLD) & pod["active"]
        ok_f = jnp.where(ok, 1.0, 0.0)
        lb_w = jnp.where(owner, lb, Nl)      # out of range off-shard
        used = carry["used"].at[lb_w].add(ok_f * cls["class_req"][u],
                                          mode="drop")
        nz_used = carry["nz_used"].at[lb_w].add(ok_f * cls["class_nz"][u],
                                                mode="drop")
        pod_count = carry["pod_count"].at[lb_w].add(ok_f, mode="drop")
        # winner-column refresh, owner-local (non-owners compute a
        # garbage column from the clamped row and drop the write)
        if has_nom:
            col = _class_col(node_cfg, cls, unique_masks, unique_scores,
                             rw, used[lbc] + nom["used"][lbc],
                             nz_used[lbc],
                             pod_count[lbc] + nom["count"][lbc], lbc)
        else:
            col = _class_col(node_cfg, cls, unique_masks, unique_scores,
                             rw, used[lbc], nz_used[lbc], pod_count[lbc],
                             lbc)
        out = {"used": used, "nz_used": nz_used, "pod_count": pod_count,
               "ms": carry["ms"].at[:, lb_w].set(col, mode="drop")}
        if has_spread:
            out["spread"] = _spread_bump(carry["spread"], pod, lb_w, ok_f,
                                         mode="drop")
        if has_topo:
            out.update(_topo_scatter_sharded(anti_dom, carry, pod, lbc,
                                             owner, ok, has_dir2))
        if has_soft:
            # the winner's domain ids live on the owning shard: one pmax
            # broadcast (-1 padding loses to any real dom id), then every
            # shard applies the identical replicated scatter-add
            wtids = pod["soft_write_tids"]
            wt = jnp.maximum(wtids, 0)
            wd = lax.pmax(jnp.where(owner, soft_dom[wt, lbc],
                                    jnp.int32(-1)), NODE_AXIS)
            wval = jnp.where((wtids >= 0) & (wd >= 0) & ok,
                             pod["soft_write_w"], 0.0)
            out["soft_cnt"] = carry["soft_cnt"].at[
                wt, jnp.maximum(wd, 0)].add(wval)
        assign = jnp.where(ok, best, jnp.int32(-1))
        return out, (assign, chosen)

    carry0 = {"used": usage["used"], "nz_used": usage["nonzero_used"],
              "pod_count": usage["pod_count"], "ms": ms0}
    if has_topo:
        carry0["topo_cnt"] = pod_batch["anti_cnt0"]
        carry0["topo_tot"] = jnp.zeros((anti_dom.shape[0],), jnp.float32)
        if has_dir2:
            carry0["topo_carry"] = jnp.zeros_like(pod_batch["anti_cnt0"])
    if has_spread:
        sp0 = usage.get("spread")
        carry0["spread"] = sp0 if sp0 is not None else spread_base
    if has_soft:
        sc0 = usage.get("soft_cnt")
        carry0["soft_cnt"] = sc0 if sc0 is not None else soft_cnt0
    P = per_pod["seq"].shape[0]
    G = min(_STEP_GROUP, P)

    def step(carry, podg):
        outs = []
        for g in range(G):
            pod = {k: v[g] for k, v in podg.items()}
            carry, out = one_pod(carry, pod)
            outs.append(out)
        return carry, (jnp.stack([o[0] for o in outs]),
                       jnp.stack([o[1] for o in outs]))

    per_pod_g = {k: v.reshape((P // G, G) + v.shape[1:])
                 for k, v in per_pod.items()}
    final, (assign_g, scores_g) = lax.scan(step, carry0, per_pod_g)
    new_usage = {"used": final["used"],
                 "nonzero_used": final["nz_used"],
                 "pod_count": final["pod_count"]}
    if has_spread:
        new_usage["spread"] = final["spread"]
    if has_soft:
        new_usage["soft_cnt"] = final["soft_cnt"]
    return assign_g.reshape(P), scores_g.reshape(P), new_usage


def _topo_scatter_sharded(anti_dom, carry, pod, lbc, owner, ok, has_dir2):
    """_topo_scatter under shard_map: the dom ids at the winner's column
    live on the owning shard, so each table's [K] dom vector is broadcast
    with one pmax (non-owners contribute -1, the 'no label' value, and
    real dom ids are >= 0 — pmax recovers the owner's exact vector); the
    replicated counters then apply the identical scatter-add on every
    shard, keeping the carry in sync without further communication."""
    from ..sharding import NODE_AXIS
    mtids = pod["match_tids"]
    mt = jnp.maximum(mtids, 0)
    md = lax.pmax(jnp.where(owner, anti_dom[mt, lbc], jnp.int32(-1)),
                  NODE_AXIS)
    val = ((mtids >= 0) & (md >= 0) & ok).astype(jnp.float32)
    out = {"topo_cnt": carry["topo_cnt"].at[
               mt, jnp.maximum(md, 0)].add(val),
           "topo_tot": carry["topo_tot"].at[mt].add(val)}
    if has_dir2:
        atids2 = pod["canti_tids"]
        at2 = jnp.maximum(atids2, 0)
        ad = lax.pmax(jnp.where(owner, anti_dom[at2, lbc], jnp.int32(-1)),
                      NODE_AXIS)
        aval = ((atids2 >= 0) & (ad >= 0) & ok).astype(jnp.float32)
        out["topo_carry"] = carry["topo_carry"].at[
            at2, jnp.maximum(ad, 0)].add(aval)
    return out


@partial(jax.jit, static_argnums=(0,))
def schedule_batch_sharded(mesh, node_cfg: dict, usage: dict,
                           pod_batch: dict, nom: dict = None):
    """schedule_batch for class-table batches on a 1-D "nodes" mesh:
    the shard-mapped scan above, with every input placed by the
    name-keyed partition rules (sharding.spec_for). Same returns as
    schedule_batch; decisions bit-identical (tier-1 CPU-sharded smoke +
    the bench's sharded parity fixtures pin this). `nom` is the phantom
    nominated-reservation overlay, sharded with the mirror rows."""
    from jax.sharding import PartitionSpec as P
    from ..sharding import NODE_AXIS, spec_for
    pod_batch = unpack_inputs(pod_batch)
    cfg_specs = {k: spec_for(k, jnp.ndim(v)) for k, v in node_cfg.items()}
    usage_specs = {k: spec_for(k, jnp.ndim(v)) for k, v in usage.items()}
    batch_specs = {k: spec_for(k, jnp.ndim(v)) for k, v in pod_batch.items()}
    usage_out = {"used": P(NODE_AXIS, None),
                 "nonzero_used": P(NODE_AXIS, None),
                 "pod_count": P(NODE_AXIS)}
    if "spread_slots" in pod_batch:
        usage_out["spread"] = P(None, NODE_AXIS)
    if "soft_dom" in pod_batch:
        usage_out["soft_cnt"] = P()   # replicated accumulators
    out_specs = (P(), P(), usage_out)
    # check_vma off: the replicated outputs (assign, scores, soft_cnt)
    # are replicated by construction — every shard applies the same
    # pmax/pmin-broadcast winner — which the varying-axes check cannot see
    if nom is None:
        fn = jax.shard_map(lambda c, u, b: _sharded_class_scan(c, u, b),
                           mesh=mesh,
                           in_specs=(cfg_specs, usage_specs, batch_specs),
                           out_specs=out_specs, check_vma=False)
        return fn(node_cfg, usage, pod_batch)
    nom_specs = {k: spec_for(k, jnp.ndim(v)) for k, v in nom.items()}
    fn = jax.shard_map(_sharded_class_scan, mesh=mesh,
                       in_specs=(cfg_specs, usage_specs, batch_specs,
                                 nom_specs),
                       out_specs=out_specs, check_vma=False)
    return fn(node_cfg, usage, pod_batch, nom)


@partial(jax.jit, donate_argnums=(0, 1))
def apply_dirty(node_cfg: dict, usage: dict, rows) -> Tuple[dict, dict]:
    """Scatter O(delta) dirty rows (cache.go:210-246's generation scan,
    shipped as one packed upload) into the device-resident state. `rows`
    is the PackedInputs (or plain dict) of "idx" [D] plus one [D, ...]
    row block "<key>_rows" per key of node_cfg and usage (row blocks are
    replicated, not node-axis data, so no partition rule may match their
    names). Padded slots carry an OUT-OF-RANGE row index (the mirror
    pads with `capacity`, one past the last row) and are dropped by the
    scatter's mode="drop" — a pad row must never alias row 0 or clamp
    onto the last real row (covered by tests/test_pipeline.py's pad-row
    fixture)."""
    rows = unpack_inputs(rows)
    idx = rows["idx"]
    new_cfg = {k: node_cfg[k].at[idx].set(rows[k + "_rows"], mode="drop")
               for k in node_cfg}
    new_usage = {k: usage[k].at[idx].set(rows[k + "_rows"], mode="drop")
                 for k in usage}
    return new_cfg, new_usage


@jax.jit
def pack_results(assign: jnp.ndarray, scores: jnp.ndarray) -> jnp.ndarray:
    """[2, P] int32 — assign and bitcast scores in ONE fetchable buffer so a
    batch costs a single device->host round trip."""
    return jnp.stack([assign, lax.bitcast_convert_type(scores, jnp.int32)])


def unpack_results(packed) -> Tuple[jnp.ndarray, jnp.ndarray]:
    import numpy as np
    arr = np.asarray(packed)
    return arr[0], arr[1].view(np.float32)


#: segments of the packed buffer start on a multiple of this many 32-bit
#: words (the chip's 128 lanes), so the static slices that cut it apart
#: again start on a lane-row edge
_PACK_ALIGN = 128
#: a host array larger than this ships on its own: copying it into the
#: buffer would cost the host more than the transfer it saves
PACK_MAX_BYTES = 1 << 20
#: what the buffer can carry, and the letter the layout keeps for it
_PACK_KINDS = {"float32": "f", "int32": "i", "bool": "b"}


@jax.tree_util.register_pytree_with_keys_class
class PackedInputs:
    """The host inputs of one launch — the mirror image of pack_results:
    every small replicated array in ONE int32 buffer (`words`) so a
    launch costs one host->device transfer for them, not one an array,
    plus `rest`, the arrays that crossed on their own or were on the
    device already. `layout` is ((name, kind, shape, offset), ...) of the
    buffer's segments: static, a function of the arrays' names, dtypes
    and shapes alone, so it keys the jitted programs exactly as the dict
    of arrays it replaces did. The kernels never see this class: each
    jitted entry point calls unpack_inputs first and reads the dict."""

    def __init__(self, words, rest: dict, layout: tuple):
        self.words, self.rest, self.layout = words, rest, layout

    def tree_flatten_with_keys(self):
        return ((jax.tree_util.GetAttrKey("words"), self.words),
                (jax.tree_util.GetAttrKey("rest"), self.rest)), self.layout

    def tree_flatten(self):
        return (self.words, self.rest), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(*children, layout)


def pack_inputs(put, arrays: dict) -> PackedInputs:
    """Ship `arrays` to the device in a bounded number of transfers.
    `put(name, host_array)` issues one transfer, placed by the name-keyed
    partition rules (TensorMirror.put_named). What goes where is read off
    each array, never off a flag:

      - already on the device (a jax.Array: the epoch-cached anti_dom
        table, chained state): passed through;
      - placed on the node axis by a rule of sharding.spec_for
        (unique_masks, unique_scores, spread_zone, anti_dom,
        soft_dom, soft_base, dom_tab): its own transfer, as before — it
        is large ([U, N]) and sharded under a mesh;
      - replicated, float32 / int32 / bool and at most PACK_MAX_BYTES:
        laid into the one int32 buffer (float32 by bit pattern, bool one
        word an element), which crosses once and replicates under a mesh
        exactly as each of its arrays did;
      - anything else replicated: its own transfer."""
    import numpy as np
    from jax.sharding import PartitionSpec
    from ..sharding import spec_for
    rest, layout, segments, total = {}, [], [], 0
    for name, a in arrays.items():
        if isinstance(a, jax.Array):
            rest[name] = a
            continue
        a = np.asarray(a)
        kind = _PACK_KINDS.get(a.dtype.name)
        if kind is None or a.nbytes > PACK_MAX_BYTES \
                or spec_for(name, a.ndim) != PartitionSpec():
            rest[name] = put(name, a)
            continue
        layout.append((name, kind, a.shape, total))
        segments.append(a)
        total += -(-a.size // _PACK_ALIGN) * _PACK_ALIGN
    words = np.zeros((total,), np.int32)
    for (_, kind, _, off), a in zip(layout, segments):
        flat = a.ravel()
        words[off:off + flat.size] = flat if kind == "b" \
            else flat.view(np.int32)
    return PackedInputs(put("packed_inputs", words), rest, tuple(layout))


def unpack_inputs(inputs) -> dict:
    """The dict of arrays a kernel reads: PackedInputs cut back into its
    names by static slices (same dtype, shape and bits as the host
    arrays that went in), INSIDE the jitted program that consumes them.
    A plain dict (hand-built batches in tests, bench fixtures) passes
    through."""
    if not isinstance(inputs, PackedInputs):
        return inputs
    out = dict(inputs.rest)
    for name, kind, shape, off in inputs.layout:
        seg = lax.slice(inputs.words, (off,),
                        (off + math.prod(shape),)).reshape(shape)
        out[name] = seg if kind == "i" else seg != 0 if kind == "b" \
            else lax.bitcast_convert_type(seg, jnp.float32)
    return out
