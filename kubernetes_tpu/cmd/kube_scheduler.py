"""kube-scheduler entry point.

Ref: cmd/kube-scheduler/app/server.go (NewSchedulerCommand :62, runCommand
:109, Run :159): load component config, optional Policy, optional leader
election, healthz+metrics serving, then Scheduler.Run against the hub.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading

from ..apiserver.httpclient import HTTPClient
from ..scheduler.config import (KubeSchedulerConfiguration, Policy,
                                build_scheduler)
from ..state.leaderelection import LeaderElector
from ..utils import gcpolicy
from ..utils.healthz import HealthzServer


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="kube-scheduler")
    p.add_argument("--master", required=True,
                   help="API server URL, e.g. http://127.0.0.1:8080")
    p.add_argument("--config", help="KubeSchedulerConfiguration JSON file")
    p.add_argument("--policy-config-file", help="Policy JSON file")
    p.add_argument("--scheduler-name", default=None)
    p.add_argument("--leader-elect", action="store_true", default=None)
    p.add_argument("--healthz-port", type=int, default=None,
                   help="healthz+metrics port (0 disables)")
    p.add_argument("--disable-preemption", action="store_true", default=None)
    args = p.parse_args(argv)

    cfg = KubeSchedulerConfiguration.from_file(args.config) if args.config \
        else KubeSchedulerConfiguration()
    # flags override the config file (component-base precedence)
    if args.policy_config_file:
        cfg.policy = Policy.from_file(args.policy_config_file)
    if args.scheduler_name is not None:
        cfg.scheduler_name = args.scheduler_name
    if args.leader_elect is not None:
        cfg.leader_election.leader_elect = args.leader_elect
    if args.healthz_port is not None:
        cfg.healthz_bind_port = args.healthz_port
    if args.disable_preemption is not None:
        cfg.disable_preemption = args.disable_preemption

    # name the device before anything is scheduled on it: a backend that
    # cannot initialise raises HERE, and whoever started this process
    # reads which chip (or CPU) its decisions come from
    from ..scheduler import (compile_log, device_report,
                             enable_compile_cache)
    cache_dir = enable_compile_cache()
    compiles = compile_log()
    print("kube-scheduler device " + json.dumps(
        {**device_report(), "compile_cache": cache_dir}), flush=True)

    client = HTTPClient(args.master)
    sched = build_scheduler(client, cfg)
    # the collector stops re-walking the informer store and the cache:
    # each full collection freezes what survived it (utils/gcpolicy.py)
    gcpolicy.install(prefix="scheduler", process="scheduler",
                     registry=sched.metrics.registry)

    healthz = None
    if cfg.healthz_bind_port > 0:
        healthz = HealthzServer(registry=sched.metrics.registry,
                                port=cfg.healthz_bind_port)
        healthz.add_check("scheduler",
                          lambda: sched._thread is None
                          or sched._thread.is_alive())
        healthz.start()
        print(f"healthz+metrics on {healthz.url}", flush=True)

    stop = threading.Event()

    def shutdown(*_):
        stop.set()
    signal.signal(signal.SIGTERM, shutdown)
    signal.signal(signal.SIGINT, shutdown)
    # a run loop that keeps raising the same exception ends the process
    # with a non-zero exit (Scheduler._run_loop) for the supervisor to see
    sched.on_fatal = shutdown

    if cfg.leader_election.leader_elect:
        le = cfg.leader_election

        def lost_lease():
            # ref: server.go OnStoppedLeading -> klog.Fatalf("leaderelection
            # lost") — the process EXITS and the supervisor restarts it; a
            # stopped Scheduler is not restartable in-process (closed queue)
            sched.stop()
            stop.set()
        elector = LeaderElector(
            client, name=le.resource_name,
            identity=f"{os.uname().nodename}_{os.getpid()}",
            namespace=le.resource_namespace,
            lease_duration=le.lease_duration_seconds,
            renew_deadline=le.renew_deadline_seconds,
            retry_period=le.retry_period_seconds,
            on_started_leading=sched.start,
            on_stopped_leading=lost_lease)
        elector.start()
        stop.wait()
        elector.stop()
    else:
        sched.start()
        stop.wait()
        sched.stop()
    if healthz is not None:
        healthz.stop()
    # what this process compiled and what the persistent cache gave it
    print("kube-scheduler compiles " + json.dumps(compiles.summary()),
          flush=True)
    if sched.fatal_error is not None:
        print(f"kube-scheduler: scheduling loop failed: "
              f"{sched.fatal_error!r}", file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
