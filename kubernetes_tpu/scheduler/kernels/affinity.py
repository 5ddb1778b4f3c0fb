"""Inter-pod (anti-)affinity template evaluation as device matmuls.

The M3 kernel (SURVEY §7.4): the reference's topologyPairsMaps lookups
(pkg/scheduler/algorithm/predicates/metadata.go:71-94 consumed per-node in
predicates.go InterPodAffinityMatches) become, for a whole batch of
constraint templates at once,

    viol[u, n] = sel_dom[u]     · (1 - has_dom[:, n])   # aff terms need the
                                                        # topology key
               + sel_present[u] · (1 - present[:, n])   # non-waived affinity
                                                        # needs a match
               + sel_absent[u]  · present[:, n]         # anti-affinity
                                                        # forbids a match
    mask[u, n] = viol[u, n] == 0

three [U, T] × [T, N] matmuls that land on the MXU — at full precision:
the operands are integer-valued f32 (0/1 selectors, weights up to 100,
counts that reach thousands in a zone) and the TPU's default matmul
would round them to bf16 first, exact only up to 256. The topology index
(scheduler/topology.py) maintains the sparse counts incrementally and
routes evaluation here when U·T·N is large; small batches stay on host
numpy (identical arithmetic — tests/test_topology.py asserts equality).

Shapes are bucketed to powers of two so XLA compiles one kernel per bucket
pair, not one per batch.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _bucket(n: int, minimum: int = 8) -> int:
    return max(minimum, 1 << max(0, math.ceil(math.log2(max(1, n)))))


def _matmul(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@jax.jit
def _affinity_masks_jit(has_dom, present, sel_dom, sel_present, sel_absent):
    hd = has_dom.astype(jnp.float32)
    pr = (present & has_dom).astype(jnp.float32)
    viol = (_matmul(sel_dom, 1.0 - hd) + _matmul(sel_present, 1.0 - pr)
            + _matmul(sel_absent, pr))
    return viol == 0.0


@jax.jit
def _affinity_scores_jit(weights, counts):
    """[U, T] preferred-term weights × [T, N] match/carry counts — the
    segment-reduction form of interpod_affinity.go's pair-weight
    accumulation."""
    return _matmul(weights, counts)


def affinity_masks(has_dom: np.ndarray, present: np.ndarray,
                   sel_dom: np.ndarray, sel_present: np.ndarray,
                   sel_absent: np.ndarray) -> np.ndarray:
    """Bucket-padded wrapper; returns the unpadded [U, N] bool mask."""
    T, N = has_dom.shape
    U = sel_dom.shape[0]
    Tb, Ub = _bucket(T), _bucket(U)
    hd = np.zeros((Tb, N), bool)
    hd[:T] = has_dom
    pr = np.zeros((Tb, N), bool)
    pr[:T] = present
    sd = np.zeros((Ub, Tb), np.float32)
    sd[:U, :T] = sel_dom
    sp = np.zeros((Ub, Tb), np.float32)
    sp[:U, :T] = sel_present
    sa = np.zeros((Ub, Tb), np.float32)
    sa[:U, :T] = sel_absent
    out = _affinity_masks_jit(jnp.asarray(hd), jnp.asarray(pr),
                              jnp.asarray(sd), jnp.asarray(sp),
                              jnp.asarray(sa))
    return np.asarray(out)[:U]


def affinity_scores(weights: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Bucket-padded [U, T] @ [T, N] preferred-affinity score accumulation."""
    U, T = weights.shape
    N = counts.shape[1]
    Tb, Ub = _bucket(T), _bucket(U)
    w = np.zeros((Ub, Tb), np.float32)
    w[:U, :T] = weights
    c = np.zeros((Tb, N), np.float32)
    c[:T] = counts
    return np.asarray(_affinity_scores_jit(jnp.asarray(w),
                                           jnp.asarray(c)))[:U]
