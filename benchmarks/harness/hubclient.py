"""The benchmark's own client of the hub: plain HTTP and JSON, nothing of
the program imported. Bulk create (one POST of a List = one store
transaction), LIST, and a watch of pods with the slim bind frames the
program's own informers negotiate (`slimBind=true`), read line by line."""

import http.client
import json
import threading
import time
from urllib.parse import urlsplit


class Hub:
    def __init__(self, base):
        u = urlsplit(base)
        self.host, self.port = u.hostname, u.port

    def connect(self, timeout=120):
        return http.client.HTTPConnection(self.host, self.port,
                                          timeout=timeout)

    def request(self, conn, method, path, body=None):
        data = None if body is None else json.dumps(body).encode()
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        if resp.status >= 300:
            raise RuntimeError(f"{method} {path}: {resp.status} "
                               f"{raw[:500]!r}")
        return json.loads(raw)

    def create_bulk(self, conn, path, manifests):
        """[(name, creation resourceVersion) or Exception] per manifest."""
        resp = self.request(conn, "POST", path, {
            "apiVersion": "v1", "kind": "List", "items": manifests})
        out = []
        for item in resp.get("items", []):
            if item.get("kind") == "Status" and \
                    item.get("status") != "Success":
                out.append(RuntimeError(
                    f"{item.get('reason')}: {item.get('message')}"))
            else:
                meta = item.get("metadata", {})
                out.append((meta.get("name"),
                            int(meta.get("resourceVersion") or 0)))
        while len(out) < len(manifests):
            out.append(RuntimeError("bulk create: missing result slot"))
        return out

    def create_all(self, path, manifests, chunk=1000, threads=4):
        """Mass load for set-up (bench.bulk_create's shape: chunked POSTs,
        four in flight). Raises on the first refused object."""
        from concurrent.futures import ThreadPoolExecutor
        local = threading.local()

        def one(lo):
            if not hasattr(local, "conn"):
                local.conn = self.connect()
            res = self.create_bulk(local.conn, path, manifests[lo:lo + chunk])
            bad = next((r for r in res if isinstance(r, Exception)), None)
            if bad is not None:
                raise bad
            return res
        with ThreadPoolExecutor(max_workers=threads) as ex:
            parts = list(ex.map(one, range(0, len(manifests), chunk)))
        return [r for part in parts for r in part]

    def list(self, path):
        conn = self.connect(timeout=300)
        try:
            return self.request(conn, "GET", path).get("items", [])
        finally:
            conn.close()


class PodWatch:
    """A watch of one namespace's pods on a thread of its own. Every bind
    it sees goes to on_bind(name, node, t_seen); a bind of a pod to a
    second node is kept in `rebinds`."""

    def __init__(self, hub, namespace, on_bind):
        self._conn = hub.connect(timeout=None)
        self._conn.request(
            "GET", f"/api/v1/namespaces/{namespace}/pods"
                   f"?watch=true&slimBind=true")
        self._resp = self._conn.getresponse()
        if self._resp.status != 200:
            raise RuntimeError(f"watch refused: {self._resp.status}")
        self._on_bind = on_bind
        self.error = None
        self._stopped = False
        self._thread = threading.Thread(target=self._pump, daemon=True,
                                        name="pod-watch")
        self._thread.start()

    def _pump(self):
        try:
            for line in self._resp:
                if self._stopped:
                    return
                line = line.strip()
                if not line:
                    continue  # the hub's heartbeat
                now = time.monotonic()
                frame = json.loads(line)
                slim = frame.get("slim")
                if slim == "bind":
                    o = frame["o"]
                    self._on_bind(o["name"], o["node"], now)
                elif slim == "binds":
                    for o in frame["o"]["items"]:
                        self._on_bind(o["name"], o["node"], now)
                elif frame.get("type") in ("ADDED", "MODIFIED"):
                    obj = frame.get("object") or {}
                    node = (obj.get("spec") or {}).get("nodeName")
                    if node:
                        self._on_bind(obj["metadata"]["name"], node, now)
        except Exception as e:  # a torn stream is a failed run
            if not self._stopped:
                self.error = e

    def stop(self):
        self._stopped = True
        try:
            self._conn.sock.shutdown(2)
        except Exception:
            pass
        self._thread.join(timeout=5)
