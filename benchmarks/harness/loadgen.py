"""The one load generator. A traffic mix is a data file
(benchmarks/traffic/<mix>.json); this reads its parameters and drives
pods from a PodStream through the hub's bulk create on `creators`
connections, while an Observer (observe.py) counts what the watch sees
bound. The loop is closed: it keeps `in_flight` pods created and not yet
seen bound, and a creator sends `chunk` more whenever that many slots
are free (a controller with a parallelism).

Every pod's create-sent time is taken on this process's monotonic clock
just before its POST is written."""

import threading
import time


class Traffic:
    def __init__(self, hub, stream, observer, mix, namespace="default"):
        self.hub, self.stream, self.obs, self.mix = hub, stream, observer, mix
        self.path = f"/api/v1/namespaces/{namespace}/pods"
        self._stop = threading.Event()
        self._threads = []
        self._take_lock = threading.Lock()
        self.refused = []       # creates the hub refused
        self.error = None

    # ----------------------------------------------------------- sends

    def _send(self, conn, pods):
        names = [p["metadata"]["name"] for p in pods]
        self.obs.sent(names, time.monotonic())
        results = self.hub.create_bulk(conn, self.path, pods)
        for name, r in zip(names, results):
            if isinstance(r, Exception):
                self.refused.append((name, repr(r)))
                self.obs.refused(name)
            else:
                self.obs.created(name, r[1])

    def burst(self, n, blocker=0):
        """n pods in one POST (one store transaction); the caller waits
        for them. With a blocker, that many pods go first in a POST of
        their own: the scheduler pops them at once and is busy with them
        for a cycle while the n arrive, so that its next pop holds all n
        (and what was left of the blocker) instead of a short head and
        the rest."""
        conn = self.hub.connect()
        try:
            if blocker:
                self._burst_one(conn, blocker)
                time.sleep(0.03)
            self._burst_one(conn, n)
        finally:
            conn.close()

    def _burst_one(self, conn, count):
        with self._take_lock:
            pods = self.stream.take(count)
        self.obs.reserve(count, None, self._stop)
        self._send(conn, pods)

    # ------------------------------------------------------------ loops

    def start(self):
        for _ in range(int(self.mix["creators"])):
            t = threading.Thread(target=self._guard, daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self):
        """No further creates; returns once every POST under way has been
        answered."""
        self._stop.set()
        self.obs.wake()
        for t in self._threads:
            t.join(timeout=120)

    def _guard(self):
        conn = self.hub.connect()
        try:
            self._closed(conn)
        except Exception as e:
            self.error = e
            self._stop.set()
        finally:
            conn.close()

    def _closed(self, conn):
        in_flight, chunk = int(self.mix["in_flight"]), int(self.mix["chunk"])
        while not self._stop.is_set():
            if not self.obs.reserve(chunk, in_flight, self._stop):
                break
            with self._take_lock:
                pods = self.stream.take(chunk)
            self._send(conn, pods)
