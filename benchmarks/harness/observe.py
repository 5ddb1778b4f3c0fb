"""What the client saw: for every pod the time its create was sent, the
resourceVersion the hub gave the create, and the time and node of the
bind as this process's own watch of pods delivered it."""

import threading
import time


class Observer:
    def __init__(self):
        self._cond = threading.Condition()
        #: name -> [t_sent, create_rv, t_bound, node]
        self.pods = {}
        self.pending = 0          # created (or being created), not seen bound
        self.rebinds = []         # (name, first node, second node)
        self.unknown_binds = 0
        #: (t, pending) at every change, for the time-average of fill
        self.fill_log = []

    def _log(self, now):
        self.fill_log.append((now, self.pending))

    def reserve(self, n, limit, stop):
        """Count n pods as in flight once that keeps pending <= limit
        (None: at once). False when stopped first."""
        with self._cond:
            while limit is not None and self.pending + n > limit:
                if stop.is_set():
                    return False
                self._cond.wait(0.05)
            if stop.is_set():
                return False
            self.pending += n
            self._log(time.monotonic())
            return True

    def sent(self, names, t):
        with self._cond:
            for name in names:
                self.pods[name] = [t, 0, None, None]

    def created(self, name, rv):
        self.pods[name][1] = rv

    def refused(self, name):
        with self._cond:
            self.pods[name][1] = -1
            self.pending -= 1
            self._cond.notify_all()

    def on_bind(self, name, node, t):
        with self._cond:
            rec = self.pods.get(name)
            if rec is None:
                self.unknown_binds += 1
                return
            if rec[3] is not None:
                if rec[3] != node:
                    self.rebinds.append((name, rec[3], node))
                return
            rec[2], rec[3] = t, node
            self.pending -= 1
            self._log(t)
            self._cond.notify_all()

    def wake(self):
        with self._cond:
            self._cond.notify_all()

    def count_pending(self):
        with self._cond:
            return self.pending

    def wait_all_bound(self, timeout, alive=None):
        """True once nothing created is still unbound."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self.pending > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                if alive is not None and not alive():
                    return False
                self._cond.wait(min(left, 0.2))
            return True

    def mean_fill(self, t0, t1, limit):
        """Time-average of pending / limit over [t0, t1], in percent."""
        area, last_t, last_p = 0.0, t0, None
        for t, p in self.fill_log:
            if t <= t0:
                last_p = p
                continue
            if t >= t1:
                break
            if last_p is not None:
                area += (t - last_t) * last_p
            last_t, last_p = t, p
        if last_p is None:
            return None
        area += (t1 - last_t) * last_p
        return 100.0 * area / ((t1 - t0) * limit)
