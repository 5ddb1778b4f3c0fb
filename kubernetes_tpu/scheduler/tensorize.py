"""Tensorization: cluster state -> dense device tensors.

This is the layer the reference does not have — it replaces the per-(pod,node)
interface calls of findNodesThatFit/PrioritizeNodes
(pkg/scheduler/core/generic_scheduler.go:518,725) with three artifacts:

  1. TensorMirror — a row-per-node dense mirror of the scheduler cache's
     NodeInfo snapshot (column schema from nodeinfo.Resource, ref:
     pkg/scheduler/nodeinfo/node_info.go:139-148). Updated incrementally from
     the cache's generation-ordered dirty list (ref: cache.go:210-246), so a
     steady-state cycle ships O(delta) rows to HBM, not O(nodes). Device
     state is split into `cfg` (bind-invariant: alloc, flags) and `usage`
     (bind-varying: used, counts) so a queue drain can chain usage on device
     across batches while cfg stays put.

  2. TermCompiler — label selectors, taints/tolerations, host ports and
     hostname constraints compiled into cached per-node boolean vectors.
     String matching never reaches the device: every unique term is evaluated
     once per node and then again only for the rows whose inputs the
     mirror has written since (NodeVectorCache: the mirror stamps each row
     with the epoch of its last write and of its last node-side write, so
     a cycle that follows a bind re-evaluates the rows that took a pod for
     a host-port term and no row for a selector or a toleration; pods in
     one Deployment share selectors, so the cache hit rate is ~1).

  3. PodBatchTensors — the pod-axis arrays for one scheduling batch:
     requests, non-zero requests, flags, and the DEDUPLICATED static
     feasibility mask: unique_masks [U, N] + mask_idx [P]. Pods sharing
     constraint terms share a row, so per-batch host->device traffic is
     O(P*R + U*N) instead of O(P*N) — what keeps a batch's upload to a
     host-attached (PCIe) chip at a few hundred KB.

Padding: node, pod, and unique-row axes are padded to bucketed sizes (powers
of two) so XLA compiles one kernel per bucket instead of one per cluster size.
"""

from __future__ import annotations

import math
import threading
from collections import OrderedDict
from contextlib import nullcontext
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..api import helpers, wellknown
from ..api.core import Pod
from ..utils.metrics import Counter
from .cache import Snapshot
from .nodeinfo import NodeInfo
from .predicates import _pod_qos, _pressure_taint

# fixed resource columns; extended/scalar resources take columns 3+
COL_CPU = 0      # milliCPU
COL_MEM = 1      # bytes
COL_EPH = 2      # bytes
N_FIXED_COLS = 3

CFG_KEYS = ("alloc", "max_pods", "node_ok", "mem_pressure", "valid")
USAGE_KEYS = ("used", "nonzero_used", "pod_count")

#: share of the live rows written since a cached node vector was last true
#: at or above which the vector is rebuilt by the full walk instead of
#: patched row by row (TensorMirror.rows_since). A patched row and a walked
#: row cost the same call of the same per-node function; past half the
#: cluster the walk's plain enumerate is no dearer than the patch's row
#: list, and it is what a resize or a start-up takes anyway
REBUILD_SHARE = 0.5
#: batches a cached node vector may go unused before NodeVectorCache drops
#: it: a key that has left the queue frees its vector, one that comes back
#: within this many batches (a short pop between full ones) is still true
NODE_VECTOR_IDLE_BATCHES = 64
#: bytes of cached node vectors a NodeVectorCache keeps past the vectors
#: of the batch in hand, least recently used going first: 8,192 bool
#: vectors or 2,048 f32 ones at 8,192 rows, 256 f32 ones at 65,536
NODE_VECTOR_CACHE_BYTES = 64 << 20


#: least rows of the scan's carried spread counts [G, N] and least
#: (group, row, count) triplets of its base a launch ships: a pop of a
#: rollout holds 5-200 groups and as many triplets as those groups have
#: bound pods, and every power of two in between would be a program of
#: its own (5-10 s of compiler a piece on the chip). 16 MiB of device
#: memory at 8,192 node rows; 48 KiB of the packed buffer
SPREAD_MIN_GROUPS = 512
SPREAD_MIN_ENTRIES = 4096


def _bucket(n: int, minimum: int = 128) -> int:
    """Next power-of-two capacity >= n (static shapes for XLA)."""
    return max(minimum, 1 << max(0, math.ceil(math.log2(max(1, n)))))


def _f32_floor(v) -> np.float32:
    """Largest float32 <= v. Applied to allocatable so the f32 tensor can
    only UNDER-state capacity: quantities beyond the 24-bit mantissa (memory
    > 16 GiB at byte granularity) round conservatively instead of allowing
    overcommit (the parity oracle in predicates.py stays exact int64).
    Residual: used-sums accumulate at most n_pods ulps of over-statement,
    also in the safe direction (requests are _f32_ceil'd)."""
    f = np.float32(v)
    if f > v:
        f = np.nextafter(f, np.float32(-np.inf))
    return f


def _f32_ceil(v) -> np.float32:
    """Smallest float32 >= v (pod requests round up — see _f32_floor)."""
    f = np.float32(v)
    if f < v:
        f = np.nextafter(f, np.float32(np.inf))
    return f


class ResourceVocab:
    """Interned scalar-resource names -> tensor columns."""

    def __init__(self, extra_capacity: int = 5):
        self._cols: Dict[str, int] = {}
        self.capacity = N_FIXED_COLS + extra_capacity

    def col(self, name: str) -> int:
        c = self._cols.get(name)
        if c is None:
            c = N_FIXED_COLS + len(self._cols)
            self._cols[name] = c
            if c >= self.capacity:
                self.capacity = _bucket(c + 1, minimum=8)
        return c

    @property
    def n_cols(self) -> int:
        return self.capacity


class NodeTensors:
    """Host-side numpy mirror of per-node state."""

    def __init__(self, capacity: int, n_cols: int):
        self.capacity = capacity
        self.n_cols = n_cols
        self.alloc = np.zeros((capacity, n_cols), np.float32)
        self.used = np.zeros((capacity, n_cols), np.float32)
        self.nonzero_used = np.zeros((capacity, 2), np.float32)  # cpu, mem
        self.pod_count = np.zeros((capacity,), np.float32)
        self.max_pods = np.zeros((capacity,), np.float32)
        self.node_ok = np.zeros((capacity,), bool)        # condition+schedulable
        self.mem_pressure = np.zeros((capacity,), bool)
        self.valid = np.zeros((capacity,), bool)

    def arrays(self) -> Dict[str, np.ndarray]:
        return {"alloc": self.alloc, "used": self.used,
                "nonzero_used": self.nonzero_used,
                "pod_count": self.pod_count, "max_pods": self.max_pods,
                "node_ok": self.node_ok, "mem_pressure": self.mem_pressure,
                "valid": self.valid}

    def cfg_arrays(self) -> Dict[str, np.ndarray]:
        return {k: getattr(self, k) for k in CFG_KEYS}

    def usage_arrays(self) -> Dict[str, np.ndarray]:
        return {k: getattr(self, k) for k in USAGE_KEYS}


class TensorMirror:
    """Name <-> row mapping plus incremental row updates from cache dirties."""

    def __init__(self, vocab: Optional[ResourceVocab] = None,
                 min_capacity: int = 128, mesh=None):
        from . import sharding
        #: jax.sharding.Mesh with a "nodes" axis, or None (single device).
        #: With a mesh, every tensor is placed by the name-keyed partition
        #: rules (sharding.spec_for) so the kernels' node axis rides ICI
        #: (the scaling-book recipe: annotate shardings, let the runtime
        #: insert the collectives); pod batches stay replicated.
        self.mesh = mesh
        #: shards on the node axis; the row capacity is always a multiple
        #: so per-shard slices are equal (shard_map requires it, and a
        #: ragged GSPMD pad would silently skew the argmax row space)
        self._shards = sharding.n_shards(mesh)
        #: rows the current capacity carries ONLY for shard divisibility
        #: (beyond the power-of-two bucket); surfaced as the
        #: scheduler_mirror_shard_pad_rows gauge — padding is visible,
        #: never a silent cap
        self.shard_pad_rows = 0
        self.vocab = vocab or ResourceVocab()
        self.t = NodeTensors(self._capacity_for(1, min_capacity),
                             self.vocab.n_cols)
        self.row_of: Dict[str, int] = {}
        self.name_of: Dict[int, str] = {}
        self._free: List[int] = list(range(self.t.capacity))
        # row-aligned NodeInfo refs for term compilation / host fallbacks
        self.infos: List[Optional[NodeInfo]] = [None] * self.t.capacity
        #: bumped by every non-empty apply(): "some node changed". The
        #: chain signatures, the nominated-reservation key and the
        #: extender encode read exactly that
        self.epoch = 0
        #: per row, the epoch of its last _write_row / _remove_row: "rows
        #: written since epoch e" is one compare (rows_since), which is
        #: how a cached node vector catches up by row
        self.row_epoch = np.zeros((self.t.capacity,), np.int64)
        #: per row, the epoch at which its node side last changed: what
        #: NodeInfo.set_node derives from the Node object (labels, fields,
        #: annotations, taints, images, allocatable, the pressures). The
        #: row was newly taken, removed or resized, or _write_row was
        #: handed a NodeInfo from a later set_node than the row last saw
        #: (_row_node_gen). A bind moves row_epoch and leaves this alone
        self.row_node_epoch = np.zeros((self.t.capacity,), np.int64)
        self._row_node_gen: List[int] = [0] * self.t.capacity
        #: the last epoch at which any row_node_epoch moved: a vector
        #: that reads the node side alone and is true for it is a hit
        #: without a look at the rows
        self.node_epoch = 0
        #: rows apply_chained wrote: stamped by the next apply(), when
        #: the epoch they were held back from moves
        self._chained_rows: set = set()
        self._dirty_rows: set = set()
        self._device_cfg: Optional[dict] = None
        self._device_usage: Optional[dict] = None
        #: bumped by invalidate_usage; pending batches launched before an
        #: invalidation must not adopt_usage their (phantom-carrying) output.
        #: _usage_lock makes the epoch check and the adopt/invalidate write
        #: ONE atomic step: the pipelined drain invalidates from the commit
        #: thread while the drain thread adopts, and a lost race would
        #: resurrect phantom usage that invalidation just dropped.
        self.usage_epoch = 0
        self._usage_lock = threading.Lock()
        #: host->device transfers put_named has issued. The shell installs
        #: SchedulerMetrics' scheduler_host_to_device_transfers_total
        #: here; a bare mirror counts on a counter of its own
        self.transfers = Counter("scheduler_host_to_device_transfers_total")
        #: rows of cached node vectors (TermCompiler's, ScoreCompiler's,
        #: its zone ids) recomputed, by patch or by full walk, and the
        #: full walks by cache ("terms" | "scores" | "zones"); installed
        #: by the shell like `transfers`
        self.vector_rows_recomputed = Counter(
            "scheduler_node_vector_rows_recomputed_total")
        self.vector_rebuilds = Counter("scheduler_node_vector_rebuilds_total")
        #: cached node vectors dropped, by disuse or by bytes, by cache
        #: ("terms" | "scores"); installed by the shell like `transfers`
        self.vector_evictions = Counter(
            "scheduler_node_vector_evictions_total")
        #: _write_row / _remove_row calls by what they changed: side="node"
        #: (the node side moved: a row taken, removed, or a later
        #: set_node) or "usage" (pods and their requests alone, a bind);
        #: installed by the shell like `transfers`
        self.row_writes = Counter("scheduler_mirror_row_writes_total")

    def _capacity_for(self, need: int, minimum: int = 128) -> int:
        """Row capacity for `need` nodes: the power-of-two bucket, padded
        up to a multiple of the mesh's shard count. Pad rows (valid=False,
        excluded from every kernel decision) are counted in
        shard_pad_rows, not silently absorbed."""
        from .sharding import shard_divisible
        bucket = _bucket(need, minimum)
        cap = shard_divisible(bucket, self._shards)
        self.shard_pad_rows = cap - bucket
        return cap

    # ------------------------------------------------------------ updates

    def apply(self, snapshot: Snapshot, dirty_names: Sequence[str]) -> None:
        """Apply the cache's dirty node list (update_snapshot output)."""
        if not dirty_names:
            return
        self.epoch += 1
        if self._chained_rows:
            self.row_epoch[list(self._chained_rows)] = self.epoch
            self._chained_rows.clear()
        need = len(snapshot.node_infos)
        if need > self.t.capacity:
            self._grow(self._capacity_for(need))
        self._write_rows(snapshot, dirty_names)

    def apply_chained(self, snapshot: Snapshot, dirty_names: Sequence[str]) -> None:
        """Host-row updates whose device effect already rides in a chained
        usage handle (the dirt is the pipelined drain's own assumes of
        residual-free pods: usage columns only — no label/taint/port/cfg
        changes, so the epoch survives and cached node vectors do not
        see these rows until the next apply() moves it and stamps them).
        Rows stay queued in _dirty_rows: the next non-chained
        device_cfg_usage scatter rewrites them with identical host-truth
        values (idempotent) or corrects any foreign mutation that slipped
        past the chain_seq guard."""
        self._chained_rows.update(self._write_rows(snapshot, dirty_names))

    def _write_rows(self, snapshot: Snapshot,
                    dirty_names: Sequence[str]) -> List[int]:
        rows = []
        for name in dirty_names:
            ni = snapshot.node_infos.get(name)
            if ni is None or ni.node is None:
                row = self._remove_row(name)
                if row is not None:
                    rows.append(row)
            else:
                rows.append(self._write_row(name, ni))
        return rows

    def stamp_epoch(self, reads: str) -> int:
        """The last epoch at which anything a reader of `reads` ("node":
        the node side alone; "pods": the node's pods as well) can see was
        written: a vector true for it or a later one has nothing to catch
        up with."""
        return self.node_epoch if reads == "node" else self.epoch

    def rows_since(self, epoch: int, reads: str) -> Optional[np.ndarray]:
        """The rows whose `reads` side ("node" | "pods", as stamp_epoch)
        was written or removed since `epoch`, ascending — or None when
        they are REBUILD_SHARE of the live rows or more, and the caller
        walks every row instead (after a resize every row is)."""
        stamps = self.row_node_epoch if reads == "node" else self.row_epoch
        rows = np.flatnonzero(stamps > epoch)
        if len(rows) >= REBUILD_SHARE * max(1, self.n_rows):
            return None
        return rows

    def device_ready(self) -> bool:
        """False after a capacity/column resize or invalidate_usage dropped
        device state (chaining callers must fall back to a full upload)."""
        return self._device_cfg is not None and self._device_usage is not None

    def device_cfg(self) -> dict:
        """The device cfg handle for a chained dispatch (device_ready() must
        be True; usage comes from the chain, not the mirror)."""
        assert self._device_cfg is not None
        return self._device_cfg

    def _grow(self, new_capacity: int) -> None:
        old = self.t
        # the vocab may have grown since the last write (PodBatchTensors
        # interns new extended resources), so copy column-aware
        t = NodeTensors(new_capacity, self.vocab.n_cols)
        n = old.capacity
        for k, arr in t.arrays().items():
            src = getattr(old, k)
            if arr.ndim == 2 and arr.shape[1] != src.shape[1]:
                arr[:n, :src.shape[1]] = src
            else:
                arr[:n] = src
        self.t = t
        self._free.extend(range(n, new_capacity))
        self.infos.extend([None] * (new_capacity - n))
        self._row_node_gen.extend([0] * (new_capacity - n))
        # every cached node vector has the old length: all rows are new
        self.row_epoch = np.full((new_capacity,), self.epoch, np.int64)
        self.row_node_epoch = self.row_epoch.copy()
        self.node_epoch = self.epoch
        self._device_cfg = None  # shapes changed; full re-upload
        self._device_usage = None
        self._dirty_rows.clear()

    def ensure_cols(self) -> None:
        """Resize the column axis after the vocab grew (callers: _write_row,
        PodBatchTensors before it sizes its request arrays)."""
        if self.vocab.n_cols > self.t.n_cols:
            t = NodeTensors(self.t.capacity, self.vocab.n_cols)
            for k, arr in t.arrays().items():
                src = getattr(self.t, k)
                if arr.ndim == 2 and arr.shape[1] != src.shape[1]:
                    arr[:, :src.shape[1]] = src
                else:
                    arr[...] = src
            self.t = t
            self._device_cfg = None
            self._device_usage = None
            self._dirty_rows.clear()

    def _write_row(self, name: str, ni: NodeInfo) -> int:
        row = self.row_of.get(name)
        taken = row is None
        if taken:
            row = self._free.pop()
            self.row_of[name] = row
            self.name_of[row] = name
        # resource columns
        for scalars in (ni.allocatable.scalar_resources, ni.requested.scalar_resources):
            for rname in scalars:
                self.vocab.col(rname)
        self.ensure_cols()
        t = self.t
        t.alloc[row, :] = 0.0
        t.alloc[row, COL_CPU] = _f32_floor(ni.allocatable.milli_cpu)
        t.alloc[row, COL_MEM] = _f32_floor(ni.allocatable.memory)
        t.alloc[row, COL_EPH] = _f32_floor(ni.allocatable.ephemeral_storage)
        for rname, v in ni.allocatable.scalar_resources.items():
            t.alloc[row, self.vocab.col(rname)] = _f32_floor(v)
        t.used[row, :] = 0.0
        t.used[row, COL_CPU] = _f32_ceil(ni.requested.milli_cpu)
        t.used[row, COL_MEM] = _f32_ceil(ni.requested.memory)
        t.used[row, COL_EPH] = _f32_ceil(ni.requested.ephemeral_storage)
        for rname, v in ni.requested.scalar_resources.items():
            t.used[row, self.vocab.col(rname)] = _f32_ceil(v)
        t.nonzero_used[row, 0] = ni.non_zero_requested.milli_cpu
        t.nonzero_used[row, 1] = ni.non_zero_requested.memory
        t.pod_count[row] = len(ni.pods)
        t.max_pods[row] = ni.allocatable.allowed_pod_number
        node = ni.node
        ok = node is not None and not node.spec.unschedulable \
            and not ni.disk_pressure and not ni.pid_pressure
        if ok:
            for cond in node.status.conditions:
                if cond.type == "Ready" and cond.status != "True":
                    ok = False
                elif cond.type == "NetworkUnavailable" and cond.status == "True":
                    ok = False
        t.node_ok[row] = ok
        t.mem_pressure[row] = ni.memory_pressure
        t.valid[row] = True
        self.infos[row] = ni
        self.row_epoch[row] = self.epoch
        if taken or self._row_node_gen[row] != ni.node_generation:
            self._row_node_gen[row] = ni.node_generation
            self._stamp_node_side(row)
        else:
            self.row_writes.inc(side="usage")
        self._dirty_rows.add(row)
        return row

    def _stamp_node_side(self, row: int) -> None:
        self.row_node_epoch[row] = self.node_epoch = self.epoch
        self.row_writes.inc(side="node")

    def _remove_row(self, name: str) -> Optional[int]:
        row = self.row_of.pop(name, None)
        if row is None:
            return None
        del self.name_of[row]
        self.infos[row] = None
        t = self.t
        t.valid[row] = False
        t.alloc[row, :] = 0.0
        t.used[row, :] = 0.0
        t.nonzero_used[row, :] = 0.0
        t.pod_count[row] = 0.0
        t.max_pods[row] = 0.0
        t.node_ok[row] = False
        t.mem_pressure[row] = False
        self._free.append(row)
        self.row_epoch[row] = self.epoch
        self._stamp_node_side(row)
        self._dirty_rows.add(row)
        return row

    # ------------------------------------------------------------- device

    def put_named(self, name: str, arr):
        """Host array -> device, placed by the name-keyed partition rules
        (sharding.spec_for) — plain transfer when no mesh is active; a
        name no rule matches replicates. The ONE place the launch path
        issues a host->device transfer, so the one place they are
        counted (`transfers`)."""
        from .sharding import put
        self.transfers.inc()
        return put(self.mesh, name, arr)

    def device_cfg_usage(self) -> Tuple[dict, dict]:
        """The (node_cfg, usage) pytrees on device. Dirty rows ship as ONE
        packed scatter (kernels.apply_dirty): the row index and the row
        block of every cfg and usage key cross in ONE buffer
        (kernels.batch.pack_inputs — all are small and replicated, so
        none is left to a transfer of its own) and are cut apart inside
        apply_dirty. Full upload, one transfer a key because each is
        sharded on the node axis, only after a capacity/column resize or
        invalidate_usage."""
        t = self.t
        if self._device_cfg is None or self._device_usage is None:
            # resize or invalidate_usage: both re-uploaded from host truth
            self._device_cfg = {k: self.put_named(k, v)
                                for k, v in t.cfg_arrays().items()}
            self._device_usage = {k: self.put_named(k, v)
                                  for k, v in t.usage_arrays().items()}
        elif self._dirty_rows:
            from .kernels.batch import apply_dirty, pack_inputs
            idx = np.fromiter(self._dirty_rows, dtype=np.int32,
                              count=len(self._dirty_rows))
            D = _bucket(len(idx), minimum=8)
            # pad with an out-of-range row; apply_dirty drops it
            rows = {"idx": np.full((D,), t.capacity, np.int32)}
            rows["idx"][:len(idx)] = idx
            for k, v in (*t.cfg_arrays().items(),
                         *t.usage_arrays().items()):
                rows[k + "_rows"] = _padded_rows(v, idx, D)
            self._device_cfg, self._device_usage = apply_dirty(
                self._device_cfg, self._device_usage,
                pack_inputs(self.put_named, rows))
        self._dirty_rows.clear()
        return self._device_cfg, self._device_usage

    def adopt_usage(self, usage: dict, epoch: Optional[int] = None) -> bool:
        """Adopt the kernel's post-batch usage (device-side chaining). Safe
        whenever every assignment in the batch was committed via assume_pod:
        the cache bumps those nodes' generations, so the next dirty scatter
        rewrites the same rows with identical host-truth values (idempotent);
        rows the host disagrees on (forgotten binds, node churn) are repaired
        by that same scatter. An assignment that never reaches assume_pod
        leaves no dirty row — callers must invalidate_usage() instead.

        `epoch` is the usage_epoch the batch launched at: the adopt is
        REFUSED (returns False) when an invalidation landed in between —
        checked and applied under one lock, so a commit-thread invalidation
        can never lose the race to a concurrent adopt."""
        with self._usage_lock:
            if epoch is not None and epoch != self.usage_epoch:
                return False
            self._device_usage = usage
            return True

    def invalidate_usage(self) -> None:
        """Drop adopted device usage; the next device_cfg_usage() re-uploads
        from host truth. Called when an assumed bind was dropped without a
        cache forget (no dirty row would repair the adopted tensors).
        Bumps usage_epoch so an in-flight PendingBatch whose usage input
        predates the invalidation cannot re-adopt phantom state."""
        with self._usage_lock:
            self._device_usage = None
            self.usage_epoch += 1

    @property
    def n_rows(self) -> int:
        return len(self.row_of)


def _padded_rows(arr: np.ndarray, idx: np.ndarray, D: int) -> np.ndarray:
    out = np.zeros((D,) + arr.shape[1:], arr.dtype)
    out[:len(idx)] = arr[idx]
    return out


# --------------------------------------------------------------- terms

def _canon_tolerations(pod: Pod) -> Tuple:
    return tuple(sorted((t.key, t.operator, t.value, t.effect or "")
                        for t in pod.spec.tolerations))


def _canon_node_selector(pod: Pod) -> Tuple:
    sel = tuple(sorted(pod.spec.node_selector.items()))
    aff = pod.spec.affinity
    terms: Tuple = ()
    if aff and aff.node_affinity and \
            aff.node_affinity.required_during_scheduling_ignored_during_execution is not None:
        ns = aff.node_affinity.required_during_scheduling_ignored_during_execution
        terms = tuple(
            (tuple((r.key, r.operator, tuple(r.values)) for r in t.match_expressions),
             tuple((r.key, r.operator, tuple(r.values)) for r in t.match_fields))
            for t in ns.node_selector_terms)
    return (sel, terms)


def precompute_pod_features(pod: Pod) -> Tuple:
    """Host-side per-pod feature extraction, cached on the pod object.

    Everything here depends only on the pod spec — not on the mirror,
    batch, or cluster state — so the scheduler's event handlers call it
    from the INFORMER thread as pods enter the queue, taking this work off
    the drain thread's critical path (the wire path's drain competes for
    the GIL with watch decode; every microsecond moved off it is wall
    time). PodBatchTensors reuses the signature; pods arriving without one
    (direct queue adds in tests) compute it inline.

    Cached on __dict__ under "_tsig"; a clone made via shallow_bind_clone
    carries the cache but bound clones never re-enter tensorization (the
    signature's node_name component would be stale there).
    """
    sig = pod.__dict__.get("_tsig")
    if sig is not None:
        return sig
    from .nodeinfo import pod_resource, pod_resource_nonzero
    reqs = helpers.pod_requests(pod)
    # warm the per-spec memos consumed by assume/add_pod on the commit path
    pod_resource(pod)
    pod_resource_nonzero(pod)
    helpers.pod_host_ports(pod)
    helpers.pod_requests_nonzero(pod)
    ckey0 = (_canon_tolerations(pod), _canon_node_selector(pod),
             tuple(sorted(helpers.pod_host_ports(pod))),
             pod.spec.node_name or "")
    qos_be = _pod_qos(pod) == "BestEffort"
    blocked = qos_be and not helpers.tolerates_taints(
        pod.spec.tolerations,
        [_pressure_taint(wellknown.TAINT_NODE_MEMORY_PRESSURE)],
        effects=["NoSchedule"])
    sig = (reqs, tuple(sorted(reqs.items())), qos_be, blocked, ckey0)
    pod.__dict__["_tsig"] = sig
    return sig


class _NodeVector:
    """One entry of a NodeVectorCache: the vector, the mirror epoch it is
    true for, the per-node function that builds a row of it, the side
    of its NodeInfo that function reads, and the batch that last used
    it."""

    __slots__ = ("vec", "epoch", "fn", "reads", "batch")

    def __init__(self):
        self.vec: Optional[np.ndarray] = None
        self.epoch = -1
        self.fn: Optional[Callable] = None
        self.reads = "pods"
        self.batch = 0


class NodeVectorCache:
    """key -> the [capacity] vector of fn(NodeInfo) over the mirror's rows
    (0 / False where a row holds no node), kept true by row: an entry
    remembers the epoch it is true for and the per-node function that
    built it, and on use at a later epoch recomputes the rows whose
    inputs the mirror has stamped since (TensorMirror.rows_since) and
    nothing else; at or past REBUILD_SHARE of the live rows, or after a
    resize, it takes the full walk: the same function over the same rows
    in the same order.

    PRECONDITION, in two halves. (1) fn reads its own NodeInfo alone and
    nothing of another node: whatever reduces over nodes happens later,
    on the finished vector (ScoreCompiler._compute_row). (2) The caller
    says which side of that NodeInfo fn reads, as a fact about fn:
    `reads="node"` for what set_node derives from the Node object and
    nothing else (`node` with its labels, fields and annotations,
    `taints`, `image_sizes`, `allocatable`, the pressures), so such an fn
    may read NOTHING a pod event changes; `reads="pods"` for an fn that
    also reads what the node's pods bring (`pods`, `used_ports`,
    `requested`). Every write an fn can see goes through _write_row /
    _remove_row of the row it sits in, which stamp row_epoch always and
    row_node_epoch when the node side moved, so a row unstamped on the
    side fn reads answers as it did: a "node" vector skips the rows a
    bind wrote, a "pods" vector recomputes them. What apply_chained
    writes (usage only, by construction) is seen by a "pods" vector when
    the next apply() stamps it, which is when the epoch it was held back
    from moves, and never by a "node" vector.

    Entries outlive an epoch, so the bound follows the queue: the caller
    opens each batch (`new_batch`), and a key the batch in hand has used
    is never dropped, however many keys the batch holds. A key unused for
    NODE_VECTOR_IDLE_BATCHES batches is dropped as the next batch opens,
    and past NODE_VECTOR_CACHE_BYTES the least recently used of the keys
    earlier batches used go; a dropped key is rebuilt on its next use.
    A deployment with a hundred node pools keeps its hundreds of `tol`
    and `sel` keys alive here at once: each is used by every batch and
    is a hit until a node event. Dropping decides only when a vector is
    rebuilt, never what it holds."""

    def __init__(self, mirror: TensorMirror, dtype, cache: str):
        self.mirror = mirror
        self.dtype = dtype
        #: the `cache` label of scheduler_node_vector_rebuilds_total and
        #: scheduler_node_vector_evictions_total
        self.cache = cache
        self._entries: "OrderedDict[Tuple, _NodeVector]" = OrderedDict()
        #: bytes of the vectors held
        self.nbytes = 0
        #: the batch in hand (new_batch)
        self._batch = 0

    def clear(self) -> None:
        self._entries.clear()
        self.nbytes = 0

    def new_batch(self) -> None:
        """A batch opens: the keys unused for NODE_VECTOR_IDLE_BATCHES
        batches go (the least recently used are first in line)."""
        self._batch += 1
        idle = self._batch - NODE_VECTOR_IDLE_BATCHES
        while self._entries and \
                next(iter(self._entries.values())).batch < idle:
            self._drop_oldest()

    def _drop_oldest(self) -> None:
        _, entry = self._entries.popitem(last=False)
        if entry.vec is not None:
            self.nbytes -= entry.vec.nbytes
        self.mirror.vector_evictions.inc(cache=self.cache)

    def _trim(self) -> None:
        """Past NODE_VECTOR_CACHE_BYTES, the least recently used keys go,
        down to the first that the batch in hand has used."""
        while self.nbytes > NODE_VECTOR_CACHE_BYTES and \
                next(iter(self._entries.values())).batch < self._batch:
            self._drop_oldest()

    def vector(self, key: Tuple, fn: Callable[[NodeInfo], object],
               reads: Optional[str] = None) -> np.ndarray:
        """`reads` is the side fn reads (PRECONDITION, 2). Left out, the
        key keeps the side it was last given; one never given a side
        follows every write, which is right for any fn."""
        m = self.mirror
        entry = self._entries.get(key)
        if entry is None:
            entry = self._entries[key] = _NodeVector()
        else:
            self._entries.move_to_end(key)
        entry.batch = self._batch
        entry.fn = fn
        if reads is not None:
            entry.reads = reads
        reads = entry.reads
        vec = entry.vec
        sized = vec is not None and len(vec) == m.t.capacity
        if sized and entry.epoch >= m.stamp_epoch(reads):
            return vec
        rows = m.rows_since(entry.epoch, reads) if sized else None
        infos = m.infos
        if rows is None:
            if vec is not None:
                self.nbytes -= vec.nbytes
            vec = entry.vec = np.zeros((m.t.capacity,), self.dtype)
            self.nbytes += vec.nbytes
            for row, ni in enumerate(infos):
                if ni is not None and ni.node is not None:
                    vec[row] = fn(ni)
            m.vector_rebuilds.inc(cache=self.cache)
            m.vector_rows_recomputed.inc(m.n_rows)
            self._trim()
        else:
            for row in rows.tolist():
                ni = infos[row]
                vec[row] = fn(ni) \
                    if ni is not None and ni.node is not None else 0
            m.vector_rows_recomputed.inc(len(rows))
        entry.epoch = m.epoch
        return vec


class TermCompiler:
    """Compiles pod-side constraint terms into cached [capacity] bool vectors
    over the mirror's rows. A cached vector catches up with the mirror by
    the rows written, on the side it reads, since it was last true
    (NodeVectorCache)."""

    def __init__(self, mirror: TensorMirror):
        self.mirror = mirror
        self._cache = NodeVectorCache(mirror, bool, "terms")

    def _vector(self, key: Tuple, fn, reads: str) -> np.ndarray:
        return self._cache.vector(key, fn, reads)

    def new_batch(self) -> None:
        """core.schedule_launch opens each batch (NodeVectorCache)."""
        self._cache.new_batch()

    def tolerations_vector(self, pod: Pod) -> np.ndarray:
        """PodToleratesNodeTaints as a node vector."""
        tols = pod.spec.tolerations
        return self._vector(
            ("tol", _canon_tolerations(pod)),
            lambda ni: helpers.tolerates_taints(
                tols, ni.taints, effects=["NoSchedule", "NoExecute"]),
            reads="node")

    def node_selector_vector(self, pod: Pod) -> np.ndarray:
        """PodMatchNodeSelector (nodeSelector + required node affinity)."""
        return self._vector(
            ("sel", _canon_node_selector(pod)),
            lambda ni: helpers.pod_matches_node_selector_and_affinity(pod, ni.node),
            reads="node")

    def host_ports_vector(self, pod: Pod) -> Optional[np.ndarray]:
        """True where the pod's host ports are free (PodFitsHostPorts).
        None when the pod wants no host ports (no constraint)."""
        wanted = helpers.pod_host_ports(pod)
        if not wanted:
            return None

        def free(ni: NodeInfo) -> bool:
            for proto, ip, port in wanted:
                for uproto, uip, uport in ni.used_ports:
                    if proto == uproto and port == uport and (
                            ip == uip or ip == "0.0.0.0" or uip == "0.0.0.0"):
                        return False
            return True
        return self._vector(("ports", tuple(sorted(wanted))), free,
                            reads="pods")

    def hostname_vector(self, pod: Pod) -> Optional[np.ndarray]:
        """PodFitsHost: spec.nodeName pins the pod to one row."""
        if not pod.spec.node_name:
            return None
        vec = np.zeros((self.mirror.t.capacity,), bool)
        row = self.mirror.row_of.get(pod.spec.node_name)
        if row is not None:
            vec[row] = True
        return vec


# --------------------------------------------------------------- pod batch

class PodBatchTensors:
    """Pod-axis arrays for one batch, padded to a pod bucket.

    The static feasibility mask is deduplicated: `unique_masks [U, N]` holds
    one row per distinct constraint-term set, `mask_idx [P]` points each pod
    at its row. Pods from one controller share every term, so U is the
    number of distinct term sets in the queue, not of pods: 1 where every
    pod is alike, and as many as a deployment has node selectors where
    workloads are confined to node pools (120 zone pairs in one pop:
    U = 120 in a bucket of 128, a [128, N] bool table built and
    uploaded every batch, 1 MiB at 8,192 rows, and as many classes in
    the class scan). The building is timed as stage `static_masks`. Static
    priority scores use the same scheme (`unique_scores [S, N]`, `score_idx
    [P]`, filled by core.BatchScheduler from ScoreCompiler output; default is
    a single all-zeros row meaning "only on-device resource priorities").
    """

    def __init__(self, pods: List[Pod], mirror: TensorMirror,
                 terms: TermCompiler, extra_mask: Optional[np.ndarray] = None,
                 min_bucket: int = 8, seq_base: int = 0,
                 extra_group: Optional[np.ndarray] = None,
                 stage: Optional[Callable] = None):
        """`stage(name, **attrs)` is the caller's stage timer
        (core.BatchScheduler._stage): the term vectors and their stack
        are timed as `static_masks`, once a batch."""
        self.pods = pods
        P = _bucket(len(pods), min_bucket)
        vocab = mirror.vocab
        # intern every requested resource FIRST so the mirror's column axis
        # covers the batch (a dropped column would silently zero a request).
        # The per-pod signature (requests, QoS, constraint key, warmed
        # memos) is normally precomputed on the informer thread
        # (precompute_pod_features); computing it here is the fallback.
        sigs = []
        for pod in pods:
            sig = pod.__dict__.get("_tsig")
            if sig is None:
                sig = precompute_pod_features(pod)
            sigs.append(sig)
            for rname in sig[0]:
                if rname not in (wellknown.RESOURCE_CPU, wellknown.RESOURCE_MEMORY,
                                 wellknown.RESOURCE_EPHEMERAL_STORAGE,
                                 wellknown.RESOURCE_PODS):
                    vocab.col(rname)
        mirror.ensure_cols()
        R = mirror.t.n_cols
        N = mirror.t.capacity
        self.req = np.zeros((P, R), np.float32)
        self.nonzero_req = np.zeros((P, 2), np.float32)
        self.mem_pressure_blocked = np.zeros((P,), bool)
        self.active = np.zeros((P,), bool)
        # tie-break rotation, persistent across batches like the reference's
        # lastNodeIndex (generic_scheduler.go:286-296)
        self.seq = (seq_base + np.arange(P, dtype=np.int64)) \
            .astype(np.int32) & 0x7FFFFFFF
        self.mask_idx = np.zeros((P,), np.int32)
        # the pod's own nominated node's row (-1 if none): the kernel
        # subtracts the pod's own reservation there so a preemptor is not
        # blocked by the space reserved for itself. Filled by the caller
        # (core.schedule_launch) from the live NominatedPodMap — the SAME
        # source the reservation tensor is built from; pod.status can lag
        # the map (cleared nominations) and would desync the subtraction.
        self.nom_row = np.full((P,), -1, np.int32)
        self._mirror = mirror

        # Pods stamped from one controller template share requests, QoS,
        # tolerations, and constraint terms; dedupe the per-pod numeric work
        # by template signature and fill rows with one gather per array.
        uniq: Dict[Tuple, int] = {}
        #: the first pod of each distinct constraint key, and its index
        #: where the key carries an extra row (-1: none)
        firsts: List[Tuple[Pod, int]] = []
        tmpl: Dict[Tuple, int] = {}
        tmpl_req: List[np.ndarray] = []
        tmpl_nz: List[Tuple[float, float]] = []
        tmpl_blocked: List[bool] = []
        tmpl_mask: List[int] = []
        tmpl_idx = np.zeros((P,), np.int32)
        for i, pod in enumerate(pods):
            reqs, reqs_key, qos_be, blocked_sig, ckey0 = sigs[i]
            if extra_group is not None and extra_mask is not None:
                # the caller's residual group id names the extra row's
                # template: dedupe by id instead of hashing 8K of mask
                # bytes per pod (-1 = no extra row)
                g = int(extra_group[i])
                has_extra = g != -1  # >= 0: template row; -2: all-False
                ckey = ckey0 + (("eg", g) if has_extra else None,)
            else:
                has_extra = extra_mask is not None \
                    and not extra_mask[i].all()
                ckey = ckey0 + (extra_mask[i].tobytes()
                                if has_extra else None,)
            # the QoS class itself is a template key component (aggregate
            # request maps can't distinguish init-container-only
            # BestEffort pods)
            tkey = (reqs_key, qos_be, ckey)
            t_i = tmpl.get(tkey)
            if t_i is None:
                req_row = np.zeros((R,), np.float32)
                for rname, v in reqs.items():
                    if rname == wellknown.RESOURCE_CPU:
                        req_row[COL_CPU] = _f32_ceil(v)
                    elif rname == wellknown.RESOURCE_MEMORY:
                        req_row[COL_MEM] = _f32_ceil(v)
                    elif rname == wellknown.RESOURCE_EPHEMERAL_STORAGE:
                        req_row[COL_EPH] = _f32_ceil(v)
                    elif rname == wellknown.RESOURCE_PODS:
                        pass
                    else:
                        req_row[vocab.col(rname)] = _f32_ceil(v)
                nz = helpers.pod_requests_nonzero(pod)
                blocked = blocked_sig
                u = uniq.get(ckey)
                if u is None:
                    u = uniq[ckey] = len(firsts)
                    firsts.append((pod, i if has_extra else -1))
                t_i = len(tmpl_req)
                tmpl[tkey] = t_i
                tmpl_req.append(req_row)
                tmpl_nz.append((nz.get(wellknown.RESOURCE_CPU, 0),
                                nz.get(wellknown.RESOURCE_MEMORY, 0)))
                tmpl_blocked.append(blocked)
                tmpl_mask.append(u)
            tmpl_idx[i] = t_i
        n = len(pods)
        if tmpl_req:
            idx = tmpl_idx[:n]
            self.req[:n] = np.stack(tmpl_req)[idx]
            self.nonzero_req[:n] = np.asarray(tmpl_nz, np.float32)[idx]
            self.mem_pressure_blocked[:n] = \
                np.asarray(tmpl_blocked, bool)[idx]
            self.mask_idx[:n] = np.asarray(tmpl_mask, np.int32)[idx]
        self.active[:n] = True
        # template tables retained for the class-indexed incremental scan
        # (enable_class_scan): pods sharing a template share every
        # batch-varying row the scan would otherwise recompute per pod
        self.tmpl_idx = tmpl_idx                       # [P] (pads -> 0)
        self._tmpl_req = tmpl_req
        self._tmpl_nz = tmpl_nz
        self._tmpl_blocked = tmpl_blocked
        self._tmpl_mask = tmpl_mask
        self._class_tables: Optional[Dict[str, np.ndarray]] = None
        U = _bucket(len(firsts), minimum=1)
        self.unique_masks = np.zeros((U, N), bool)
        with stage("static_masks", rows=len(firsts)) if stage is not None \
                else nullcontext():
            for u, (pod, i) in enumerate(firsts):
                mask = terms.tolerations_vector(pod) & \
                    terms.node_selector_vector(pod)
                pv = terms.host_ports_vector(pod)
                if pv is not None:
                    mask = mask & pv
                hv = terms.hostname_vector(pod)
                if hv is not None:
                    mask = mask & hv
                if i >= 0:
                    mask = mask & extra_mask[i]
                self.unique_masks[u] = mask
        self.n_unique_masks = len(firsts)
        #: distinct (template, score row) pairs of the class scan, before
        #: bucketing (enable_class_scan); 0 while no class table is built
        self.n_classes = 0
        # score dedupe table; default single zero row (resource-only scoring)
        self.score_idx = np.zeros((P,), np.int32)
        self.unique_scores = np.zeros((1, N), np.float32)
        # [LeastRequested, BalancedAllocation] weights for the device scan
        # (Policy-configurable; defaults.go:126-137 defaults both to 1)
        self.resource_weights = np.ones((2,), np.float32)
        # in-scan SelectorSpread groups (core._assign_spread_groups): pods
        # sharing (namespace, selector set) share a group whose per-node
        # match counts update inside the kernel scan
        self.spread_gidx = np.full((P,), -1, np.int32)
        self.spread_slots: Optional[np.ndarray] = None  # [G] f32 zeros
        self.spread_nz: Optional[np.ndarray] = None     # [3, S] int32
        self.spread_mg: Optional[np.ndarray] = None     # [P, K] int32
        self.spread_zone = None         # [N] int32 (0=no zone), on the device
        self.spread_zinit: Optional[np.ndarray] = None  # [Z] f32 zeros
        self.spread_tab = None          # [M+1, M+1] int32, on the device
        self.spread_weight = 0.0

        # in-scan required (anti-)affinity term tables
        # (core._assign_topology_terms)
        self.anti_dom: Optional[np.ndarray] = None      # [T, N] int32
        #: epoch-cached DEVICE copy of the padded anti_dom table (sharded
        #: by the name rules) — set under a mesh so repeat batches skip
        #: the [T, N] upload entirely
        self.anti_dom_dev = None
        self.anti_cnt0: Optional[np.ndarray] = None     # [T, D] f32 zeros
        self.anti_tids: Optional[np.ndarray] = None     # [P, K] int32 (-1 pad)
        self.aff_tids: Optional[np.ndarray] = None      # [P, K] int32
        self.match_tids: Optional[np.ndarray] = None    # [P, K] int32
        self.cmatch_tids: Optional[np.ndarray] = None   # [P, K] int32
        self.canti_tids: Optional[np.ndarray] = None    # [P, K] int32

        # in-scan preferred (anti-)affinity credit tables
        # (core._assign_soft_terms)
        self.soft_dom: Optional[np.ndarray] = None       # [Ts, N] int32
        self.soft_cnt0: Optional[np.ndarray] = None      # [Ts, Ds] f32 zeros
        self.soft_base: Optional[np.ndarray] = None      # [Sb, N] f32
        self.soft_base_idx: Optional[np.ndarray] = None  # [P] int32 (-1 off)
        self.soft_read_tids: Optional[np.ndarray] = None   # [P, Ks] int32
        self.soft_read_w: Optional[np.ndarray] = None      # [P, Ks] f32
        self.soft_write_tids: Optional[np.ndarray] = None  # [P, Ks] int32
        self.soft_write_w: Optional[np.ndarray] = None     # [P, Ks] f32
        self.soft_weight = 0.0

    def set_topology_terms(self, dom: np.ndarray, n_domains: int,
                           anti_tids: np.ndarray, aff_tids: np.ndarray,
                           match_tids: np.ndarray,
                           cmatch_tids: Optional[np.ndarray] = None,
                           canti_tids: Optional[np.ndarray] = None,
                           dom_dev=None) -> None:
        """Install in-scan term tables; T, D, and the per-pod K axis all
        bucketed to powers of two (padded term rows carry dom=-1
        everywhere: never conflict, never bump) so consecutive batches
        with drifting term fan-outs share one compiled kernel instead of
        recompiling per batch. The per-pod [K]-term lists keep the scan
        O(K*N) per step. `dom_dev` is an already-padded, already-sharded
        DEVICE copy of the same table (TopologyIndex.term_table_device's
        epoch cache); its T bucketing matches this method's."""
        T = _bucket(dom.shape[0], minimum=8)
        P = self.req.shape[0]
        dom_p = np.full((T, dom.shape[1]), -1, np.int32)
        dom_p[:dom.shape[0]] = dom
        self.anti_dom = dom_p
        assert dom_dev is None or tuple(dom_dev.shape) == dom_p.shape, \
            "device dom table bucketing diverged from the host table"
        self.anti_dom_dev = dom_dev
        self.anti_cnt0 = np.zeros((T, _bucket(max(n_domains, 1),
                                              minimum=64)), np.float32)
        K = _bucket(max(anti_tids.shape[1], aff_tids.shape[1],
                        match_tids.shape[1], 1), minimum=1)

        def pad(m):
            out = np.full((P, K), -1, np.int32)
            out[:m.shape[0], :m.shape[1]] = m
            return out
        self.anti_tids = pad(anti_tids)
        self.aff_tids = pad(aff_tids)
        self.match_tids = pad(match_tids)
        # direction-2 lists (winner carries / pod matches), present only
        # when some pure matcher in the batch needs them — their absence
        # drops the whole carry-counter table from the kernel trace
        self.cmatch_tids = pad(cmatch_tids) if cmatch_tids is not None \
            else None
        self.canti_tids = pad(canti_tids) if canti_tids is not None \
            else None

    def set_soft_terms(self, dom: np.ndarray, n_domains: int,
                       base: np.ndarray, base_idx: np.ndarray,
                       read_tids: np.ndarray, read_w: np.ndarray,
                       write_tids: np.ndarray, write_w: np.ndarray,
                       weight: float) -> None:
        """Install in-scan preferred inter-pod (anti-)affinity credit
        tables (core._assign_soft_terms): per-(term slot, domain) weight
        accumulators start at zero (pre-batch credits live in the per-class
        `base` raw rows); each pod reads its slot list at its nodes'
        domains (signed weights) and a winner writes its slot list at the
        chosen node's domain. Ts/Ds/Ks/Sb bucketed like the required-term
        tables."""
        Ts = _bucket(dom.shape[0], minimum=8)
        P = self.req.shape[0]
        dom_p = np.full((Ts, dom.shape[1]), -1, np.int32)
        dom_p[:dom.shape[0]] = dom
        self.soft_dom = dom_p
        self.soft_cnt0 = np.zeros((Ts, _bucket(max(n_domains, 1),
                                               minimum=64)), np.float32)
        Sb = _bucket(base.shape[0], minimum=1)
        base_p = np.zeros((Sb, base.shape[1]), np.float32)
        base_p[:base.shape[0]] = base
        self.soft_base = base_p
        self.soft_base_idx = np.full((P,), -1, np.int32)
        self.soft_base_idx[:len(base_idx)] = base_idx
        Ks = _bucket(max(read_tids.shape[1], write_tids.shape[1], 1),
                     minimum=1)

        def pad_i(m):
            out = np.full((P, Ks), -1, np.int32)
            out[:m.shape[0], :m.shape[1]] = m
            return out

        def pad_f(m):
            out = np.zeros((P, Ks), np.float32)
            out[:m.shape[0], :m.shape[1]] = m
            return out
        self.soft_read_tids = pad_i(read_tids)
        self.soft_read_w = pad_f(read_w)
        self.soft_write_tids = pad_i(write_tids)
        self.soft_write_w = pad_f(write_w)
        self.soft_weight = float(weight)

    def enable_class_scan(self) -> None:
        """Build the (template, score-row) class tables for the kernel's
        incremental class-indexed scan (kernels/batch.py
        _schedule_batch_classes). Called AFTER static scores are set —
        score_idx is part of the class key. Spread groups, soft credits,
        and nominated reservations ride the class scan as per-pod
        carried/overlaid state, so every non-gang batch builds these."""
        if not self._tmpl_req:
            return
        P = self.req.shape[0]
        S = max(1, self.unique_scores.shape[0])
        pair = self.tmpl_idx.astype(np.int64) * S \
            + self.score_idx.astype(np.int64)
        uniq, class_idx = np.unique(pair, return_inverse=True)
        self.n_classes = len(uniq)
        C = _bucket(len(uniq), minimum=1)
        t_of = (uniq // S).astype(np.int64)
        s_of = (uniq % S).astype(np.int64)
        req = np.zeros((C, self.req.shape[1]), np.float32)
        nz = np.zeros((C, 2), np.float32)
        blocked = np.zeros((C,), bool)
        mask_idx = np.zeros((C,), np.int32)
        score_idx = np.zeros((C,), np.int32)
        req[:len(uniq)] = np.stack(self._tmpl_req)[t_of]
        nz[:len(uniq)] = np.asarray(self._tmpl_nz, np.float32)[t_of]
        blocked[:len(uniq)] = np.asarray(self._tmpl_blocked, bool)[t_of]
        mask_idx[:len(uniq)] = np.asarray(self._tmpl_mask, np.int32)[t_of]
        score_idx[:len(uniq)] = s_of
        self._class_tables = {
            "class_req": req, "class_nz": nz, "class_blocked": blocked,
            "class_mask_idx": mask_idx, "class_score_idx": score_idx,
            "class_idx": class_idx.astype(np.int32)[:P]}

    def set_spread(self, n_groups: int, nz: np.ndarray,
                   matched: List[Tuple[int, ...]], zone_of: np.ndarray,
                   n_zones: int, weight: float, round_table) -> None:
        """Install the spread group tables. What crosses to the device is
        what is non-zero, and the kernel scatters it into zeros
        (kernels/batch.py _spread_tables): a group's base row holds at
        most as many entries as the group has pods, so G rows of N f32
        are nearly all zeros (4-8 MB a launch at G = 128-256, N = 8,192,
        on a transfer of their own; the triplets of a pop's groups are
        some KB inside the packed buffer).

          nz [3, S0] int32      (group, node row, count) of every non-zero
                                base count; padded with group G, which
                                the scatter drops
          matched [P0] tuples   the groups whose selectors match each pod
                                (its own and those that overlap it): a
                                winner bumps EVERY one of them, as the
                                serial re-count would; [P, K] int32, -1
                                padded
          zone_of, round_table  the zone ids [N] int32 and
                                kernels.batch.spread_round_table, both on
                                the device already (ScoreCompiler ships
                                them once a rescan / a size)

        G, S, K and Z are bucketed to bound XLA recompiles across
        batches, G and S from SPREAD_MIN_GROUPS and SPREAD_MIN_ENTRIES
        up, so that the pops of one deployment share one program a pod
        bucket however many groups each holds; spread_slots [G] is
        shipped for its shape alone."""
        G = _bucket(n_groups, minimum=SPREAD_MIN_GROUPS)
        P = self.req.shape[0]
        self.spread_slots = np.zeros((G,), np.float32)
        S = _bucket(nz.shape[1], minimum=SPREAD_MIN_ENTRIES)
        self.spread_nz = np.zeros((3, S), np.int32)
        self.spread_nz[0] = G
        self.spread_nz[:, :nz.shape[1]] = nz
        K = _bucket(max((len(m) for m in matched), default=1), minimum=1)
        self.spread_mg = np.full((P, K), -1, np.int32)
        for i, m in enumerate(matched):
            self.spread_mg[i, :len(m)] = m
        self.spread_zone = zone_of
        self.spread_zinit = np.zeros((_bucket(n_zones, minimum=8),),
                                     np.float32)
        self.spread_tab = round_table
        self.spread_weight = float(weight)

    def set_static_scores(self, score_idx: np.ndarray,
                          unique_scores: np.ndarray) -> None:
        """Install ScoreCompiler output (S-bucketed unique score rows)."""
        S = _bucket(unique_scores.shape[0], minimum=1)
        padded = np.zeros((S, self.unique_scores.shape[1]), np.float32)
        padded[:unique_scores.shape[0]] = unique_scores
        self.unique_scores = padded
        self.score_idx[:len(score_idx)] = score_idx

    def _base_ok(self) -> np.ndarray:
        t = self._mirror.t
        return t.node_ok & t.valid & (t.pod_count + 1.0 <= t.max_pods)

    def fits_row(self, i: int) -> np.ndarray:
        """One pod's batch-start feasibility [N] on host numpy."""
        t = self._mirror.t
        fits = self.unique_masks[self.mask_idx[i]] & self._base_ok()
        if self.mem_pressure_blocked[i]:
            fits = fits & ~t.mem_pressure
        free = t.alloc - t.used
        fits = fits & (self.req[i][None, :] <= free).all(axis=1)
        return fits

    def device(self):
        """The batch on the device, as kernels.batch.PackedInputs: every
        small replicated array (the pod-axis vectors, the class tables,
        resource_weights, the spread / topology / soft term lists and
        the two weights) in ONE buffer so a launch costs a single
        host->device transfer for them, cut back into the same names
        inside the jitted kernel (unpack_inputs). What a partition rule
        places on the node axis (unique_masks, unique_scores,
        spread_zone, anti_dom, soft_dom, soft_base) still
        crosses on its own: it is large and shards under a mesh; the
        epoch-cached anti_dom_dev is on the device already.
        pack_inputs reads which is which off each array."""
        from .kernels.batch import pack_inputs
        out = {"req": self.req,
               "nonzero_req": self.nonzero_req,
               "mem_pressure_blocked": self.mem_pressure_blocked,
               "active": self.active,
               "seq": self.seq,
               "mask_idx": self.mask_idx,
               "score_idx": self.score_idx,
               "nom_row": self.nom_row,
               "unique_masks": self.unique_masks,
               "unique_scores": self.unique_scores,
               "resource_weights": self.resource_weights}
        if self.spread_slots is not None:
            out["spread_gidx"] = self.spread_gidx
            out["spread_mg"] = self.spread_mg
            out["spread_slots"] = self.spread_slots
            out["spread_nz"] = self.spread_nz
            out["spread_tab"] = self.spread_tab
            # the zone-id vector is node-axis data: it shards with the
            # mirror rows so the shard_map kernel's local slice aligns
            # (on the device already, like the round table)
            out["spread_zone"] = self.spread_zone
            out["spread_zinit"] = self.spread_zinit
            out["spread_weight"] = np.float32(self.spread_weight)
        if self.anti_dom is not None:
            # the dom table may already sit on device, epoch-cached and
            # sharded by the topology index (set_topology_terms dom_dev)
            out["anti_dom"] = self.anti_dom_dev \
                if self.anti_dom_dev is not None else self.anti_dom
            out["anti_cnt0"] = self.anti_cnt0
            out["anti_tids"] = self.anti_tids
            out["aff_tids"] = self.aff_tids
            out["match_tids"] = self.match_tids
            if self.cmatch_tids is not None:
                out["cmatch_tids"] = self.cmatch_tids
                out["canti_tids"] = self.canti_tids
        if self.soft_dom is not None:
            out["soft_dom"] = self.soft_dom
            out["soft_cnt0"] = self.soft_cnt0
            out["soft_base"] = self.soft_base
            out["soft_base_idx"] = self.soft_base_idx
            out["soft_read_tids"] = self.soft_read_tids
            out["soft_read_w"] = self.soft_read_w
            out["soft_write_tids"] = self.soft_write_tids
            out["soft_write_w"] = self.soft_write_w
            out["soft_weight"] = np.float32(self.soft_weight)
        if self._class_tables is not None:
            out.update(self._class_tables)
        return pack_inputs(self._mirror.put_named, out)
