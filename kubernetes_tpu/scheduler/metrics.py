"""Scheduler metrics — the reference's metric families over the batch path.

Ref: pkg/scheduler/metrics/metrics.go:30-180. Same families and labels
where the concept survives batching; the batch-specific additions are
labeled phases of the device pipeline (tensorize/kernel/fetch) that the
reference's per-pod timers have no analog for.
"""

from __future__ import annotations

from ..observability.tracer import thread_cpu_by_role
from ..utils.metrics import Registry

SCHEDULING_LATENCY_BUCKETS = (0.0005, 0.001, 0.002, 0.004, 0.008, 0.016,
                              0.032, 0.064, 0.128, 0.256, 0.512, 1.024,
                              2.048, 4.096, 8.192)


#: every `operation` of scheduler_scheduling_duration_seconds, one per
#: stage site of scheduler.py and core.py. Parents (their leaves nest
#: inside them): algorithm and commit in the served cycle; launch, fetch
#: and commit in the pipelined drain. Leaves, in the order a cycle meets
#: them. The bind transaction has its own family,
#: scheduler_binding_duration_seconds (the reference's BindingLatency):
#: inside commit before assume where the bind is synchronous, on a binder
#: thread beside the next cycle where it is not (an HTTP client), and
#: then bind_backlog is the scheduling thread waiting for the oldest of
#: max_inflight_binds transactions.
STAGE_PARENTS = ("algorithm", "commit", "launch", "fetch")
STAGE_LEAVES = ("pop_wait", "refresh", "tensorize", "dispatch",
                "scan_wait", "repair", "assume", "bind_backlog",
                "soft_terms")
# soft_terms is the channel plan and the credit tables of in-scan
# preferred inter-pod (anti-)affinity (core._soft_plan,
# core._assign_soft_terms), a leaf of its own at two sites: in the
# launch between tensorize and dispatch, entered by every batch (the
# table build, and the plan of a pod list not planned before), and in
# core.soft_batch_limit, which the shell calls before the algorithm
# parent opens, around the plan of a pop of more than SOFT_SCORE_CHUNK
# pods that carries preferred terms (outside the cycle, as pop_wait
# is). It is no part of tensorize, so the cycle's leaves still sum to
# the cycle.
#: what the inter-pod (anti-)affinity machinery takes of a leaf, each
#: nested in the leaf named: topology_apply in refresh (TopologyIndex.
#: apply, the (term, domain) counts kept as binds land, and beside it
#: scorer.SpreadIndex.apply, SelectorSpread's counts), affinity_masks
#: in tensorize (the second pass of core._residual_mask: template
#: profiles, TopologyIndex.required_masks, the rows laid on the pods),
#: affinity_scores in dispatch (scorer.static_scores while the cluster
#: holds inter-pod score carriers). A batch that needs none of them
#: enters none, so a cluster without such pods reads 0 in all three.
#: static_masks, also in tensorize, is entered by every batch, once:
#: PodBatchTensors' term vectors of the batch's distinct constraint keys
#: (tolerations, node selector and required node affinity, host ports,
#: hostname; cached vectors catching up by row) and their stack into
#: unique_masks. spread_groups, in tensorize too, is
#: core._assign_spread_groups: entered by every singleton batch while
#: SelectorSpreadPriority is weighted and the scheduler has listers
#: (the served scheduler always), it finds each pod's selectors, gives
#: every selected label set of the batch a slot and reads its base
#: counts off the index. static_scores, in dispatch, is
#: scorer.static_scores wherever affinity_scores is not (no inter-pod
#: score carrier): entered by every batch, it computes a row only where a
#: priority can tell the feasible nodes apart (TaintToleration once some
#: node carries a PreferNoSchedule taint), so affinity_scores keeps its
#: meaning and the two never time the same batch.
STAGE_PARTS = ("topology_apply", "affinity_masks", "affinity_scores",
               "static_masks", "spread_groups", "static_scores")
#: every reason of scheduler_topo_inscan_fallbacks_total
INSCAN_FALLBACK_REASONS = ("term_cap", "kmax", "soft_terms", "soft_kmax",
                           "soft_gang", "aff_growth", "spread_groups",
                           "spread_range")


#: every `role` of scheduler_thread_cpu_seconds: the prefix of the names
#: of the threads that carry it (the loop thread, the two binders, the
#: informers' delivery threads, the HTTP watch streams' readers)
THREAD_ROLES = ("scheduling", "binder", "informer", "watch_pump")


#: every `cache` of scheduler_node_vector_rebuilds_total and
#: scheduler_node_vector_evictions_total
NODE_VECTOR_CACHES = ("terms", "scores", "zones")
#: every `side` of scheduler_mirror_row_writes_total
MIRROR_ROW_WRITE_SIDES = ("node", "usage")


class SchedulerMetrics:
    def __init__(self, registry: Registry = None):
        self.registry = registry if registry is not None else Registry()
        r = self.registry
        # ref: SchedulingLatency histogram labeled by operation
        # {predicate_evaluation, priority_evaluation, binding, ...}; the
        # batch analog is per-stage wall time per cycle. A cycle's self
        # time is its e2e sum minus its leaves
        self.scheduling_duration = r.histogram(
            "scheduler_scheduling_duration_seconds",
            "Scheduling stage latency per batch cycle, by operation",
            buckets=SCHEDULING_LATENCY_BUCKETS)
        # the same stages on the CPU clock of the thread that entered
        # them (SpanTracer.stage's cpu): a stage's duration minus its CPU
        # is its time off the core, the waits for the interpreter lock
        # and the calls that block (for pop_wait, scan_wait and
        # bind_backlog, the wait they are)
        self.scheduling_cpu = r.counter(
            "scheduler_scheduling_cpu_seconds_total",
            "CPU seconds of the entering thread inside each scheduling "
            "stage, by operation")
        for op in STAGE_PARENTS + STAGE_LEAVES + STAGE_PARTS:
            self.scheduling_duration.declare(operation=op)
            self.scheduling_cpu.declare(operation=op)
        # CPU of the process's threads by role, read off each live
        # thread's CPU clock at the scrape (nothing on the hot path); a
        # thread that has exited, the collector's own and the /metrics
        # server's are in none
        self.thread_cpu = r.gauge(
            "scheduler_thread_cpu_seconds",
            "CPU seconds of the scheduler's live threads, by role",
            fn=lambda: {(("role", role),): v for role, v in
                        thread_cpu_by_role(THREAD_ROLES).items()})
        # ref: E2eSchedulingLatency — queue pop to bind committed
        self.e2e_scheduling_duration = r.histogram(
            "scheduler_e2e_scheduling_duration_seconds",
            "End-to-end batch latency from pop to binds committed",
            buckets=SCHEDULING_LATENCY_BUCKETS)
        self.e2e_scheduling_duration.declare()
        self.binding_duration = r.histogram(
            "scheduler_binding_duration_seconds",
            "Bind transaction latency per batch",
            buckets=SCHEDULING_LATENCY_BUCKETS)
        self.binding_duration.declare()
        # what the popped pods waited in the queue, summed once per pop
        # (queue clock at the pop - the pod's enqueue timestamp), and the
        # pods it is divided by
        self.queue_wait_seconds = r.counter(
            "scheduler_queue_wait_seconds_total",
            "Seconds popped pods had waited in the scheduling queue")
        self.queue_popped_pods = r.counter(
            "scheduler_queue_popped_pods_total",
            "Pods popped from the scheduling queue")
        # pipelined drain: wall time the commit stage spent on the commit
        # thread — time the drain thread did NOT serialize on (it was
        # tensorizing/dispatching the next batch); the occupancy lens the
        # device_profile's pipelined section reports per-batch
        self.commit_overlap_duration = r.histogram(
            "scheduler_commit_overlap_duration_seconds",
            "Commit-stage wall time overlapped with the next batch's "
            "launch and device compute (pipelined drain)",
            buckets=SCHEDULING_LATENCY_BUCKETS)
        # ref: scheduleAttempts counter labeled result
        # {scheduled, unschedulable, error}
        self.schedule_attempts = r.counter(
            "scheduler_schedule_attempts_total",
            "Scheduling attempts by result")
        for result in ("scheduled", "unschedulable", "error"):
            self.schedule_attempts.declare(result=result)
        # ref: PreemptionAttempts / PreemptionVictims; family names use
        # the reference's POST-rename spelling (the originals predate
        # its metrics-naming linter — exactly the KTPU004 contract)
        self.preemption_attempts = r.counter(
            "scheduler_preemption_attempts_total",
            "Preemption attempts")
        self.preemption_victims = r.counter(
            "scheduler_preemption_victims_total",
            "Pods evicted by preemption")
        # gang members no longer skip preemption silently: each failed
        # attempt by a gang member routes to WHOLE-GANG preemption
        # (price minMember placements against one ICI domain) and is
        # counted here — the old skip path's disappearance is observable
        self.preemption_gang_routed = r.counter(
            "scheduler_preemption_gang_routed_total",
            "Unschedulable gang members routed to whole-gang preemption "
            "(previously skipped outright)")
        self.pod_scheduling_errors = r.counter(
            "scheduler_pod_scheduling_errors_total",
            "Pods that failed a scheduling cycle with an error")
        # ref: PendingPods gauges {active, backoff, unschedulable}
        self.pending_pods = r.gauge(
            "scheduler_pending_pods",
            "Pending pods by queue")
        self.batch_size = r.histogram(
            "scheduler_batch_size",
            "Pods decided per batch cycle",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                     4096))
        # in-scan (anti-)affinity fallbacks, by reason {term_cap, kmax,
        # soft_terms, soft_kmax, soft_gang, aff_growth, spread_groups,
        # spread_range}: batches the kernel tables could not cover take
        # the repair-overlay / sub-chunked path instead (aff_growth: cut
        # short before a pod whose required affinity an earlier pod of
        # the batch can widen; spread_groups: cut short before the
        # spread group past core.SPREAD_GROUP_CAP, or that group scored
        # from a batch-start row where the caller did not cut;
        # spread_range: a spread group whose counts could pass what the
        # kernel's int32 score holds exactly) — a capped code path must
        # be visible, never silent
        self.topo_inscan_fallbacks = r.counter(
            "scheduler_topo_inscan_fallbacks_total",
            "Batches that fell back from the in-scan topology/soft-credit "
            "tables, by reason")
        for reason in INSCAN_FALLBACK_REASONS:
            self.topo_inscan_fallbacks.declare(reason=reason)
        # the same without the reason, once a batch however many of its
        # caps overflowed: over the cycles, the share of batches that
        # left the scan
        self.topo_inscan_fallback_batches = r.counter(
            "scheduler_topo_inscan_fallback_batches_total",
            "Batches in which at least one in-scan fallback was counted")
        self.topo_inscan_fallback_batches.declare()
        # what a batch's constraint machinery was sized by: the distinct
        # residual templates it resolved (the U of required_masks'
        # [U, N] rows) and the distinct terms those rows read (its T)
        self.constraint_templates = r.counter(
            "scheduler_constraint_templates_total",
            "Constraint templates (distinct residual signatures) resolved, "
            "summed over batches")
        self.constraint_templates.declare()
        self.constraint_terms = r.counter(
            "scheduler_constraint_terms_total",
            "Distinct (anti-)affinity terms read by the batches' template "
            "mask rows, summed over batches")
        self.constraint_terms.declare()
        # what a batch's static-mask and class machinery was sized by:
        # the rows of PodBatchTensors.unique_masks (one per distinct
        # constraint-term set of the batch; a deployment of a hundred
        # node selectors has a hundred) and the classes of the class
        # scan (distinct (template, score row) pairs, before bucketing;
        # 0 for a batch that builds no class tables)
        self.static_mask_rows = r.counter(
            "scheduler_static_mask_rows_total",
            "Rows of the batches' static feasibility masks (distinct "
            "constraint-term sets), summed over batches")
        self.static_mask_rows.declare()
        self.scan_classes = r.counter(
            "scheduler_scan_classes_total",
            "Classes (distinct template and score-row pairs) of the "
            "batches' class scans, summed over batches")
        self.scan_classes.declare()
        # the weighted static score rows scorer.static_scores computed
        # (one a distinct score key and mask row of the batch; the zero
        # row every batch has is not counted), before bucketing: the S of
        # unique_scores [S, N] less one, 0 where no priority but the two
        # resource ones can tell a pod's feasible nodes apart
        self.static_score_rows = r.counter(
            "scheduler_static_score_rows_total",
            "Static score rows computed (distinct score keys and mask "
            "rows), summed over batches")
        self.static_score_rows.declare()
        # the credit channels of in-scan preferred inter-pod
        # (anti-)affinity a launch installed (core._assign_soft_terms: a
        # (kind, term) accumulator each, len of the plan's chan_list),
        # summed over the launches that install soft tables: 0 where no
        # pod carries a preferred term and no required-affinity credit
        # can move in a batch; 2 a launch where every pod carries one
        # preferred anti-affinity term on its own label
        self.soft_channels = r.counter(
            "scheduler_soft_channels_total",
            "Credit channels of in-scan preferred inter-pod affinity "
            "tables installed, summed over launches")
        self.soft_channels.declare()
        # SelectorSpread in the scan: the (namespace, label set) groups
        # given a slot of the carried [G, N] counts, before bucketing,
        # summed over batches (a pop of a rollout holds about one a
        # Service it touches); and the node rows visited to make base
        # counts: the pass that switches scorer.SpreadIndex on, and 0
        # from then on, because the index follows the binds
        self.spread_groups = r.counter(
            "scheduler_spread_groups_total",
            "Spread groups given a slot of the scan's carried counts, "
            "summed over batches")
        self.spread_groups.declare()
        self.spread_rows_walked = r.counter(
            "scheduler_spread_rows_walked_total",
            "Node rows visited to make spread groups' base counts")
        self.spread_rows_walked.declare()
        # which way a batch's affinity rows were computed: host numpy or
        # the device matmuls of kernels/affinity.py (the score rows have
        # the host route alone)
        self.affinity_evaluations = r.counter(
            "scheduler_affinity_evaluations_total",
            "Batches whose affinity mask or score rows were computed, by "
            "stage and route")
        for stage, route in (("masks", "host"), ("masks", "device"),
                             ("scores", "host")):
            self.affinity_evaluations.declare(stage=stage, route=route)
        # serving-mode adaptive drain: the batch cap the sizing policy
        # chose per cycle (grows with queue depth, shrinks under commit/
        # bind backpressure or a priority-lane express batch)
        self.adaptive_batch_cap = r.histogram(
            "scheduler_adaptive_batch_cap",
            "Adaptive drain batch cap chosen per cycle (serving mode)",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                     4096, 8192, 16384))
        # express batches popped for the high-priority lane, and cycles
        # shrunk because the hub-side commit/bind stages were backed up
        self.lane_batches = r.counter(
            "scheduler_priority_lane_batches_total",
            "Drain cycles sized to the high-priority lane cohort's "
            "bucket (floored at min_batch, so a tiny lane pops with "
            "bulk pods behind it)")
        self.backpressure_shrinks = r.counter(
            "scheduler_backpressure_shrinks_total",
            "Drain cycles whose batch cap was shrunk by bind/commit "
            "backpressure")
        # unschedulable attribution: one inc per (failed attempt, distinct
        # reason) from the explain() diagnosis, plus the queue's park
        # causes (gang below minMember) — the "why is my pod pending"
        # family /debug/pending reads per-pod detail for
        self.unschedulable_reasons = r.counter(
            "scheduler_unschedulable_reasons_total",
            "Unschedulable scheduling attempts by failure reason "
            "(predicate message or queue park cause)")
        # no silent caps (the PR 5 contract, enforced by KTPU005): every
        # bounded search that truncated its candidate set is visible
        self.capped_scans = r.counter(
            "scheduler_capped_scans_total",
            "Scans truncated at a documented cap, by cap name")
        # exceptions that escaped a scheduling cycle in the served run loop
        # (Scheduler._run_loop): a scan the device compiler refuses must
        # be visible on /metrics, not only as a traceback on stderr
        self.loop_errors = r.counter(
            "scheduler_loop_errors_total",
            "Exceptions raised out of a scheduling cycle in the run loop")
        # host->device transfers of the launch path, counted where each
        # is issued (TensorMirror.put_named: the shell installs this
        # counter on the algorithm's mirror): the packed batch, the mask
        # and score tables, the packed dirty-row scatter, and whatever
        # else a launch ships. Over the cycles, what a launch costs in
        # the runtime's fixed price a transfer
        self.host_to_device_transfers = r.counter(
            "scheduler_host_to_device_transfers_total",
            "Host-to-device transfers issued by the launch path")
        self.host_to_device_transfers.declare()
        # cached node vectors (tensorize.NodeVectorCache behind
        # TermCompiler and ScoreCompiler, and ScoreCompiler's zone ids)
        # catch up with the mirror by the rows written since they were
        # last true, on the side they read; the shell installs both
        # counters on the algorithm's mirror. Rows: every row of every
        # such vector recomputed, by patch or by full walk (a bind-only
        # cycle: 0 for the vectors that read the node alone, the rows
        # that took a pod for a host-port or spread vector; a node event:
        # its rows x the vectors in use). Rebuilds: the full walks, by
        # cache; none in a window without a node event
        self.node_vector_rows_recomputed = r.counter(
            "scheduler_node_vector_rows_recomputed_total",
            "Rows of cached node vectors recomputed")
        self.node_vector_rows_recomputed.declare()
        self.node_vector_rebuilds = r.counter(
            "scheduler_node_vector_rebuilds_total",
            "Full walks over the nodes to rebuild a cached node vector, "
            "by cache")
        for cache in NODE_VECTOR_CACHES:
            self.node_vector_rebuilds.declare(cache=cache)
        # cached node vectors dropped, by cache: a key unused for
        # tensorize.NODE_VECTOR_IDLE_BATCHES batches, or the least
        # recently used past NODE_VECTOR_CACHE_BYTES; never a key of the
        # batch in hand. A dropped key is rebuilt by the full walk on its
        # next use, so 0 while the queue's keys stay in use
        self.node_vector_evictions = r.counter(
            "scheduler_node_vector_evictions_total",
            "Cached node vectors dropped by disuse or by bytes, by cache")
        for cache in NODE_VECTOR_CACHES:
            self.node_vector_evictions.declare(cache=cache)
        # what the mirror's row writes changed (TensorMirror._write_row /
        # _remove_row, installed like the two above): "node" where the
        # node side moved (a row taken or removed, a later set_node),
        # "usage" where the pods and their requests alone did (a bind).
        # The usage share is the share of writes no node-side vector pays
        self.mirror_row_writes = r.counter(
            "scheduler_mirror_row_writes_total",
            "Rows written to the tensor mirror, by the side that changed")
        for side in MIRROR_ROW_WRITE_SIDES:
            self.mirror_row_writes.declare(side=side)
        # ---- sharded drain (mesh execution substrate) ----
        # batches routed through the shard_map kernel (per-shard
        # filter+score, cross-shard argmax) vs the GSPMD/single paths
        self.sharded_batches = r.counter(
            "scheduler_sharded_batches_total",
            "Batches scheduled by the shard-mapped class scan")
        # wall time the fetch spent draining the cross-shard argmax
        # pipeline for a sharded batch (mesh synchronization cost)
        self.shard_sync_seconds = r.histogram(
            "scheduler_shard_sync_seconds",
            "Mesh-synchronization wait fetching a sharded batch's packed "
            "results",
            buckets=SCHEDULING_LATENCY_BUCKETS)
        # mirror rows added purely for shard divisibility (TensorMirror
        # pads the node capacity to a multiple of the mesh's shard count;
        # pad rows are valid=False and excluded from every decision) —
        # padding is visible, never a silent cap
        self.mirror_shard_pad_rows = r.gauge(
            "scheduler_mirror_shard_pad_rows",
            "Node-mirror rows added to make the capacity shard-divisible")

    def stage(self, tracer, name: str, ring: bool = True, **attrs):
        """observability.SpanTracer.stage for one `operation` of the
        cycle: its wall into scheduler_scheduling_duration_seconds and
        the entering thread's CPU clock into
        scheduler_scheduling_cpu_seconds_total, both always (the CPU
        into the counter alone, never into a span); a leaf, and a part
        of one, also gets the trace annotation sched.<name>, a parent
        none (it would cover the host time that its leaves leave
        unexplained)."""
        return tracer.stage(
            name, self.scheduling_duration, cpu=self.scheduling_cpu,
            labels={"operation": name},
            trace="sched." + name
            if name in STAGE_LEAVES + STAGE_PARTS else None,
            ring=ring, **attrs)

    def observe_queue(self, queue) -> None:
        """Sample the three sub-queue depths (PendingPods gauges)."""
        with queue._lock:
            active = len(queue._in_active)
            backoff = len(queue._in_backoff)
            unschedulable = len(queue._unschedulable)
        self.pending_pods.set(active, queue="active")
        self.pending_pods.set(backoff, queue="backoff")
        self.pending_pods.set(unschedulable, queue="unschedulable")
