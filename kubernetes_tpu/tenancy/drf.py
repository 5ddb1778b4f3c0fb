"""Dominant-resource fair share (DRF) across tenants, on the device scan.

Ghodsi et al.'s DRF assigns each tenant a *dominant share* — the maximum,
over resource kinds, of the tenant's usage divided by cluster capacity —
and a work-conserving fair scheduler serves the tenant with the LOWEST
dominant share first. This module carries that computation the way the
repo carries every scheduling decision: a per-tenant usage tensor
``[T, R]`` updated at each winner commit (one more carried tensor, like
the spread group counts that ride the class carry), a jitted kernel that
turns it into dominant shares and a drain ordering, and a serial numpy
mirror (``dominant_shares_reference`` / ``drf_order_reference``) in the
same parity-oracle role ``price_nodes_reference`` plays for preemption.

The account feeds two consumers:

  - **drain batch ordering** (``order_batch``): a popped batch is
    reordered (priority desc, dominant share asc, pop position) so
    pods of tenants furthest BELOW fair share tensorize first and win
    in-batch contention — priority still dominates (the express-lane
    contract is untouched), DRF only arbitrates within a band. The
    permutation is computed on device and is bit-identical to the
    numpy mirror (f32 arithmetic, same op order, position as the
    unique final sort key).
  - **preemption pricing** (``overshare_ranks``): tenants above fair
    share (1/T of every resource) get a quantized over-share rank; the
    victim tables sort those tenants' pods into a cheaper band, so a
    gang storm's own pods are priced first when capacity must be
    reclaimed.

``KTPU_DRF=0`` disables both consumers — today's priority-then-FIFO
drain and tenant-blind pricing stay byte-identical as the measured
control.

Charging is idempotent by pod key (charge at assume/bind, release at
terminal/delete/bind-failure), so replays and informer echoes can never
double-count a tenant.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..api import helpers
from ..api.core import Pod

#: the label a workload generator stamps tenants with; pods without it
#: fall back to their namespace (the reference's tenancy boundary)
TENANT_LABEL = "serving.ktpu/tenant"

#: resource columns of the usage tensor: cpu (milli), memory (bytes),
#: TPU devices (summed over tpu-suffixed extended resources)
RESOURCES: Tuple[str, ...] = ("cpu", "memory", "tpu")


def drf_enabled() -> bool:
    """KTPU_DRF=0 pins the drain to priority-then-FIFO and preemption
    to tenant-blind pricing — the measured control."""
    return os.environ.get("KTPU_DRF", "1") != "0"


def tenant_of(pod: Pod) -> str:
    """The pod's tenant: the explicit label, else its namespace."""
    return pod.metadata.labels.get(TENANT_LABEL) \
        or pod.metadata.namespace or "default"


def _pod_vec(pod: Pod) -> np.ndarray:
    """[R] f32 usage row for one pod (requests; max with init
    containers is immaterial at this granularity — the scan's own
    nodeinfo accounting stays the placement truth)."""
    from ..scheduler.nodeinfo import pod_resource
    r = pod_resource(pod)
    tpu = sum(v for k, v in r.scalar_resources.items()
              if k.endswith("tpu") or "/tpu" in k)
    return np.array([r.milli_cpu, r.memory, tpu], np.float32)


# ------------------------------------------------------------- kernels

#: jitted wrappers, cached per underlying function — a fresh jax.jit()
#: per call would recompile every invocation
_JITTED: dict = {}


def _jit(fn):
    j = _JITTED.get(fn)
    if j is None:
        import jax
        j = jax.jit(fn)
        _JITTED[fn] = j
    return j


def _dominant_kernel(usage, cap):
    """[T, R] usage + [R] capacity -> [T] dominant shares (jitted on
    first use; f32 divide then max, the reference mirror's op order)."""
    import jax.numpy as jnp
    shares = usage / jnp.maximum(cap, jnp.float32(1.0))
    return jnp.max(shares, axis=1)


def dominant_shares_reference(usage: np.ndarray,
                              cap: np.ndarray) -> np.ndarray:
    """Numpy mirror of the dominant-share kernel — same op order, f32
    throughout (the parity oracle)."""
    shares = usage.astype(np.float32) \
        / np.maximum(cap.astype(np.float32), np.float32(1.0))
    return np.max(shares, axis=1)


def _order_kernel(usage, cap, tidx, prio, n):
    """[T, R] usage + [R] capacity + [P] per-pod tenant rows + [P]
    priorities, the first n of P real -> permutation: priority desc,
    dominant share asc, pop position asc, the pad slots last (their
    own leading key, so no real pod can tie with one). Position is the
    unique final key, so the permutation never depends on sort
    stability. ONE program per (T capacity, P bucket): shares, the
    per-pod gather and the sort ride one dispatch, and a pop of any
    size inside the bucket reuses it — on the chip a sort compiles in
    seconds, which an unbucketed pod axis would pay per pop size."""
    import jax.numpy as jnp
    pos = jnp.arange(prio.shape[0], dtype=jnp.int32)
    share = _dominant_kernel(usage, cap)[tidx]
    return jnp.lexsort((pos, share, -prio, pos >= n))


def drf_order_reference(prio: np.ndarray, share: np.ndarray,
                        pos: np.ndarray) -> np.ndarray:
    """Numpy mirror of the drain-order kernel (np.lexsort: last key is
    primary, identical key tuple)."""
    return np.lexsort((pos, share.astype(np.float32), -prio))


class DRFAccount:
    """The per-tenant usage ledger and its device-resident carry.

    Tenants are registered on first sight (index order is first-charge
    order, which is deterministic under the harnesses' sorted-key
    stepping); the usage tensor grows by doubling so the jitted kernels
    recompile O(log T) times. All mutation is under one lock — charges
    come from the commit path, releases from informer event handlers.
    """

    def __init__(self, mesh=None):
        self._lock = threading.Lock()
        self.mesh = mesh
        self._idx: Dict[str, int] = {}
        self._names: List[str] = []
        self._usage = np.zeros((4, len(RESOURCES)), np.float32)
        #: pod key -> (tenant index, charged [R] vector): idempotence
        #: and exact-release bookkeeping in one map
        self._charged: Dict[str, Tuple[int, np.ndarray]] = {}
        self._capacity = np.ones((len(RESOURCES),), np.float32)
        self._cap_nodes = -1  # node-count fingerprint of _capacity

    # ------------------------------------------------------- registry

    def tenant_index(self, tenant: str) -> int:
        i = self._idx.get(tenant)
        if i is None:
            i = len(self._names)
            self._idx[tenant] = i
            self._names.append(tenant)
            if i >= self._usage.shape[0]:
                grown = np.zeros((self._usage.shape[0] * 2,
                                  len(RESOURCES)), np.float32)
                grown[:self._usage.shape[0]] = self._usage
                self._usage = grown
        return i

    @property
    def tenants(self) -> List[str]:
        with self._lock:
            return list(self._names)

    # ------------------------------------------------------- capacity

    def set_capacity(self, cap: Sequence[float]) -> None:
        with self._lock:
            self._capacity = np.asarray(cap, np.float32)
            self._cap_nodes = -2  # pinned: ensure_capacity won't overwrite

    def ensure_capacity(self, node_infos: Dict[str, object]) -> None:
        """Refresh cluster capacity from the snapshot's node set. Cheap
        re-entry guard: recompute only when the node COUNT changed
        (allocatable churn without add/remove is rare and self-corrects
        on the next topology change)."""
        with self._lock:
            if self._cap_nodes == -2 or len(node_infos) == self._cap_nodes:
                return
            cap = np.zeros((len(RESOURCES),), np.float32)
            for ni in node_infos.values():
                alloc = ni.allocatable
                cap[0] += alloc.milli_cpu
                cap[1] += alloc.memory
                cap[2] += sum(
                    v for k, v in alloc.scalar_resources.items()
                    if k.endswith("tpu") or "/tpu" in k)
            self._capacity = np.maximum(cap, np.float32(1.0))
            self._cap_nodes = len(node_infos)

    # ------------------------------------------------------ the ledger

    def charge(self, pod: Pod) -> None:
        """Winner commit: add the pod's vector to its tenant's row
        (no-op when this key is already charged)."""
        key = pod.metadata.key()
        with self._lock:
            if key in self._charged:
                return
            vec = _pod_vec(pod)
            t = self.tenant_index(tenant_of(pod))
            self._usage[t] += vec
            self._charged[key] = (t, vec)

    def release(self, pod: Pod) -> None:
        self.release_key(pod.metadata.key())

    def release_key(self, key: str) -> None:
        """Terminal phase / delete / failed bind: return the charged
        vector (exact — the vector that was charged, not a recompute)."""
        with self._lock:
            rec = self._charged.pop(key, None)
            if rec is None:
                return
            t, vec = rec
            self._usage[t] = np.maximum(
                self._usage[t] - vec, np.float32(0.0))

    # ------------------------------------------------------- consumers

    def _snapshot(self) -> Tuple[np.ndarray, np.ndarray, Dict[str, int]]:
        with self._lock:
            T = max(1, len(self._names))
            return (self._usage[:T].copy(), self._capacity.copy(),
                    dict(self._idx))

    def dominant_shares(self) -> np.ndarray:
        """[T] dominant shares via the device kernel (the usage carry is
        shipped under the 'tenant_usage' partition rule — replicated,
        tenant-leading; see scheduler/sharding.py)."""
        usage, cap, _ = self._snapshot()
        from ..scheduler import sharding
        u = sharding.put(self.mesh, "tenant_usage", usage)
        c = sharding.put(self.mesh, "tenant_capacity", cap)
        return np.asarray(_jit(_dominant_kernel)(u, c))

    def share_of(self, tenant: str) -> float:
        usage, cap, idx = self._snapshot()
        i = idx.get(tenant)
        if i is None or i >= usage.shape[0]:
            return 0.0
        return float(dominant_shares_reference(usage, cap)[i])

    #: below this batch size the numpy mirror runs instead of the device
    #: kernel — the permutation is identical (the parity contract), and
    #: a device round-trip per tiny batch costs more than it parallelizes
    DEVICE_FLOOR = 64

    def order_batch(self, pods: List[Pod]) -> List[Pod]:
        """Reorder a popped batch: priority desc (the express-lane
        contract), dominant share asc (tenants furthest below fair
        share first), pop position as the unique tie-break. Bit-
        identical to order_batch_reference over the same inputs."""
        if len(pods) < 2:
            return list(pods)
        if len(pods) < self.DEVICE_FLOOR:
            return self.order_batch_reference(pods)
        n = len(pods)
        P = 1 << (n - 1).bit_length()    # power-of-two pod bucket
        tidx = np.zeros((P,), np.int32)
        prio = np.zeros((P,), np.int32)
        with self._lock:
            tidx[:n] = [self.tenant_index(tenant_of(p)) for p in pods]
            # the whole doubling-capacity ledger: rows past the last
            # tenant are zero and no tidx names them
            usage = self._usage.copy()
            cap = self._capacity.copy()
        prio[:n] = [helpers.pod_priority(p) for p in pods]
        from ..scheduler import sharding
        put = lambda name, arr: sharding.put(self.mesh, name, arr)
        perm = np.asarray(_jit(_order_kernel)(
            put("tenant_usage", usage), put("tenant_capacity", cap),
            put("tidx", tidx), put("prio", prio), np.int32(n)))
        return [pods[int(i)] for i in perm[:n]]

    def order_batch_reference(self, pods: List[Pod]) -> List[Pod]:
        """The serial numpy mirror of order_batch (parity surface)."""
        if len(pods) < 2:
            return list(pods)
        with self._lock:
            tidx = np.array([self.tenant_index(tenant_of(p))
                             for p in pods], np.int32)
            T = max(1, len(self._names))
            usage = self._usage[:T].copy()
            cap = self._capacity.copy()
        shares = dominant_shares_reference(usage, cap)[tidx]
        prio = np.array([helpers.pod_priority(p) for p in pods], np.int32)
        pos = np.arange(len(pods), dtype=np.int32)
        perm = drf_order_reference(prio, shares, pos)
        return [pods[int(i)] for i in perm]

    def overshare_ranks(self) -> Dict[str, int]:
        """tenant -> quantized rank ABOVE the equal fair share (1/T per
        resource); tenants at/below fair share are absent. The victim
        tables fold this into the eviction band order — integer
        quantization (1e6 steps) keeps the host sort exact."""
        usage, cap, idx = self._snapshot()
        if not idx:
            return {}
        shares = dominant_shares_reference(usage, cap)
        fair = np.float32(1.0) / np.float32(max(1, len(idx)))
        out: Dict[str, int] = {}
        for name, i in idx.items():
            q = int(round(float(shares[i] - fair) * 1_000_000))
            if q > 0:
                out[name] = q
        return out

    def report(self) -> dict:
        """Per-tenant usage/share snapshot for /debug/pending and the
        bench's isolation section."""
        usage, cap, idx = self._snapshot()
        shares = dominant_shares_reference(usage, cap)
        return {
            "capacity": {r: float(cap[i])
                         for i, r in enumerate(RESOURCES)},
            "tenants": {
                name: {
                    "dominant_share": round(float(shares[i]), 6),
                    "usage": {r: float(usage[i, j])
                              for j, r in enumerate(RESOURCES)},
                } for name, i in sorted(idx.items())},
        }
