"""What decides `correct`: one function, compare(), over what a run
left behind, and correct() over its answer. A measured run (run.py) and
the control (control.py: the plain reference one precision down, put in
the program's place) hand it the same facts and get the same verdict.

The facts, as plain data:

  ref          the configuration's reference (cluster.load_reference)
  nodes        the node manifests
  taken        every pod manifest handed out, in order
  created_rv   name -> resourceVersion the hub gave the create (> 0
               acknowledged, -1 refused, 0 never answered)
  watch_node   name -> node the client's watch saw the pod bound to
  rebinds      [(name, first node, second node)] seen on the watch
  listed       the hub's LIST of pods after the run (pod objects)
  scheduler    the scheduler's /metrics at the end
  exit_codes   of the children
  sched_err    the scheduler's stderr
  objects      the manifests of the configuration's set-up objects

Every limit is exact: 0."""

import time

SCHEDULED = 'scheduler_schedule_attempts_total{result="scheduled"}'
ERRORS = 'scheduler_schedule_attempts_total{result="error"}'


def compare(ref, nodes, taken, created_rv, watch_node, rebinds, listed,
            scheduler, exit_codes, sched_err, say=None, objects=()):
    """{name: {"value": v, "limit": 0}} of every number compared."""
    by_name = {p["metadata"]["name"]: p for p in listed}
    bound_node, no_condition, not_listed = {}, 0, 0
    acked = []
    for m in taken:
        name = m["metadata"]["name"]
        if created_rv.get(name, 0) <= 0:
            continue                # never acknowledged by the hub
        acked.append(m)
        p = by_name.get(name)
        if p is None:
            not_listed += 1
            continue
        node = p["spec"].get("nodeName") or ""
        bound_node[name] = node
        if node and not any(
                c.get("type") == "PodScheduled" and c.get("status") == "True"
                for c in p.get("status", {}).get("conditions", [])):
            no_condition += 1
    # the order of the decisions: creation order (see reference.py)
    acked.sort(key=lambda m: created_rv[m["metadata"]["name"]])
    t = time.monotonic()
    rep = ref.replay(nodes, acked, bound_node, objects=objects)
    if say is not None:
        say("reference", seconds=time.monotonic() - t, **rep)
    watch_differs = sum(
        1 for name, node in bound_node.items()
        if watch_node.get(name) is not None and watch_node[name] != node)
    n_bound = sum(1 for n in bound_node.values() if n)
    values = {
        "score_gap_max": rep["score_gap_max"],
        "binds_that_do_not_fit": rep["binds_that_do_not_fit"],
        "nodes_over_allocatable": rep["nodes_over_allocatable"],
        "acked_pods_not_listed": not_listed,
        "pods_without_node": rep["pods_without_node"],
        "bound_without_PodScheduled": no_condition,
        "pods_bound_twice": len(rebinds) + watch_differs
        + max(0, int(scheduler.get(SCHEDULED, 0)) - n_bound),
        "scheduler_loop_errors": int(
            scheduler.get("scheduler_loop_errors_total", 0)),
        "scheduler_bind_errors": int(scheduler.get(ERRORS, 0)),
        "scheduler_tracebacks": sched_err.count("Traceback"),
        "child_exit_codes": sum(abs(rc or 0) for rc in exit_codes),
    }
    return {k: {"value": v, "limit": 0} for k, v in values.items()}


def correct(compared):
    return all(c["value"] <= c["limit"] for c in compared.values())
