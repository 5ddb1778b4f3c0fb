"""Overload drill (ISSUE 19): the client-storm chaos leg.

Unit coverage for APF itself lives in tests/test_flowcontrol.py (the
BENCH_r13 record is a pre-chip CPU run of a harness that is gone).
Here we pin the drill's CONTRACTS:

- flag-off schedules are byte-identical to pre-overload PRs' schedules
  (no storm actions, no storm_ticks draws);
- enable_storms gates storm EXECUTION, never the schedule — a control
  run replays the identical script;
- a small APF-on drill comes out green (no starved renews, no spurious
  failovers, no double-binds) and same-seed deterministic on both the
  event log and the semantic store state.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubernetes_tpu.chaos.harness import ChaosHarness  # noqa: E402


class TestOverloadSchedule:
    def test_flag_off_schedule_has_no_storm_markers(self):
        h = ChaosHarness(seed=11, nodes=4)
        try:
            sched = h.make_schedule(40)
        finally:
            h.close()
        assert all(ev["action"] != "client_storm" for ev in sched)
        assert all("storm_ticks" not in ev for ev in sched)

    def test_enable_storms_does_not_change_schedule(self):
        # the control leg (enable_storms=False) must replay the very
        # same script; the flag gates execution, not scheduling
        scheds = []
        for storms in (True, False):
            h = ChaosHarness(seed=5, nodes=4, http=True, ha=True,
                             overload=4, enable_storms=storms,
                             error_rate=0.0, enable_restarts=False)
            try:
                scheds.append(h.make_schedule(40))
            finally:
                h.close()
        assert scheds[0] == scheds[1]

    def test_overload_schedule_draws_storm_params_every_event(self):
        # every event draws storm_ticks (used or not) so the schedule
        # stays a pure function of (seed, n_events, flags)
        h = ChaosHarness(seed=7, nodes=4, http=True, ha=True,
                         overload=4, error_rate=0.0,
                         enable_restarts=False)
        try:
            sched = h.make_schedule(25)
        finally:
            h.close()
        assert all(2 <= ev["storm_ticks"] <= 4 for ev in sched)
        assert any(ev["action"] == "client_storm" for ev in sched)


class TestOverloadDrill:
    def _run(self, tmp_path, tag):
        h = ChaosHarness(seed=7, nodes=6, nodes_per_slice=3,
                         http=True, ha=True, enable_restarts=False,
                         error_rate=0.0, overload=4, apf=True,
                         wal_path=str(tmp_path / f"{tag}.wal"))
        try:
            return h.run(n_events=25, quiesce_steps=12)
        finally:
            h.close()

    def test_small_apf_drill_green_and_deterministic(self, tmp_path):
        a = self._run(tmp_path, "a")
        b = self._run(tmp_path, "b")
        # green: the strict overload invariants (no starved lease renew,
        # no spurious failover, no double-bind) all hold with APF on
        assert a.violations == []
        assert b.violations == []
        # the schedule actually exercised the storm, and the storm's
        # traffic reached the hub (counters are real-time totals; their
        # exact values are racy by design and NOT part of determinism)
        assert any(e[1] == "client_storm" for e in a.events)
        assert a.storm_ok + a.storm_rejected + a.storm_errors > 0
        # deterministic: same seed => identical event log AND identical
        # semantic end state, real storm threads notwithstanding
        assert a.events == b.events
        assert a.store_state == b.store_state
