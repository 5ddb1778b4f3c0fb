"""HTTP client speaking the API server's REST+watch protocol.

Ref: staging/src/k8s.io/client-go/rest (RESTClient) + the generated typed
clientsets. Implements the same surface as state.client.Client /
ResourceClient / PodClient, so every component — scheduler, controllers,
informers — runs unmodified against either the in-process store or a
remote hub: swap `Client()` for `HTTPClient(url)` and nothing else
changes. That substitutability is the tested process boundary.
"""

from __future__ import annotations

import json
import os
import threading
import time
from queue import Queue
from time import perf_counter
from typing import Any, Callable, List, Optional, Type
from urllib import error as urlerror
from urllib import request as urlrequest
from urllib.parse import urlsplit

from ..api import binenc
from ..api import core as corev1
from ..api import labels as labelsmod
from ..api import serde
from ..api.meta import LabelSelector
from ..runtime.scheme import SCHEME, Scheme
from ..state.store import (BOOKMARK, MODIFIED, AlreadyExistsError,
                           ConflictError, ExpiredError, NotFoundError,
                           SlimBindRef, WatchEvent)
from ..utils.backoff import BackoffPolicy
from ..utils.clock import REAL_CLOCK
from ..utils.metrics import WIRE_CODEC_BUCKETS, Counter, Histogram

#: terminal watch-stream errors by (resource, reason) — the TRANSPORT
#: layer's family, counted in the pump for every consumer including raw
#: .watch() users that have no informer. Informer consumers get a
#: second, per-factory family (InformerMetrics.watch_stream_errors) with
#: reconnect/relist context; the two deliberately overlap for informer
#: streams because they serve different audiences. Standalone Counter:
#: register into a Registry only if exposition is wanted.
WATCH_STREAM_ERRORS = Counter(
    "httpwatch_stream_errors_total",
    "HTTP watch streams terminated by an error, by resource and reason")

#: client half of the wire-volume split (the hub's apiserver_wire_*
#: families are the server half): request/watch bytes and payload decode
#: time by negotiated encoding, so the r04 "watch decode is
#: scheduler-side" attribution can be re-measured per encoding.
#: Standalone like WATCH_STREAM_ERRORS — process-wide across every
#: HTTPClient, which is what a per-process bench wants to sample.
WIRE_BYTES_SENT = Counter(
    "httpclient_wire_bytes_sent_total",
    "Request body bytes written, by encoding")
WIRE_BYTES_RECEIVED = Counter(
    "httpclient_wire_bytes_received_total",
    "Response + watch-frame bytes read, by encoding")
WIRE_DECODE_SECONDS = Histogram(
    "httpclient_wire_decode_seconds",
    "Payload decode latency, by encoding", WIRE_CODEC_BUCKETS)


class WatchStaleError(ConnectionError):
    """A watch stream went silent past the heartbeat-staleness window and
    was killed by the consumer's watchdog (the server heartbeats every
    second, so silence means dead TCP, not an idle cluster)."""


class TooManyRequestsError(RuntimeError):
    """HTTP 429 from the server's overload protection (an APF fair-queue
    rejection or the legacy max-inflight shed). Carries the parsed
    Retry-After seconds so retry layers honor the server's hint instead
    of hammering back on their own schedule."""

    def __init__(self, msg: str, retry_after: Optional[float] = None):
        super().__init__(msg)
        self.retry_after = retry_after


#: wire-hook kinds — an injectable transport interceptor
#: (`HTTPClient(wire_hook=...)`): called as hook(kind, op, resource, path)
#: ahead of every request ("request" — may sleep to model latency or
#: raise to model a connection reset) and at watch-stream creation
#: ("watch" — returns None, or an int K to sever the stream after K
#: events, the mid-stream-drop fault). chaos/injector.py provides the
#: deterministic implementation.
WIRE_REQUEST = "request"
WIRE_WATCH = "watch"


def _raise_for(status: int, body: str, headers=None) -> None:
    try:
        msg = json.loads(body).get("message", body)
    except Exception:
        msg = body
    if status == 401:
        raise PermissionError(f"Unauthorized: {msg}")
    if status == 403:
        raise PermissionError(f"Forbidden: {msg}")
    if status == 429:
        # two distinct 429s: a PDB-refused eviction vs the server's
        # overload protection — callers handle them differently
        # (drain waits on budgets; overload is a generic retry)
        if "disruption budget" in msg:
            from ..state.client import TooManyDisruptions
            raise TooManyDisruptions(msg)
        # the header used to be dropped here, leaving callers to guess a
        # retry delay the server had already computed for them
        ra = None
        if headers is not None:
            try:
                ra = float(headers.get("Retry-After"))
            except (TypeError, ValueError):
                ra = None
        raise TooManyRequestsError(msg, retry_after=ra)
    if status == 404:
        raise NotFoundError(msg)
    if status == 410:
        raise ExpiredError(msg)  # reflector relists on this
    if status == 409:
        if "AlreadyExists" in body:
            raise AlreadyExistsError(msg)
        raise ConflictError(msg)
    raise RuntimeError(f"HTTP {status}: {msg}")


class _HTTPWatch:
    """Client half of the chunked watch stream; mirrors store.Watch's
    iterator contract (iterate WatchEvents, stop() to cancel), plus the
    reflector-resume surface:

      - `last_rv`: resourceVersion of the last event delivered — the
        consumer reconnects here instead of relisting.
      - `error`: the terminal stream error, or None for a clean close
        (stop() or the server ending the stream). The old blanket
        `except Exception: pass` made those indistinguishable.
      - `last_activity`: time.monotonic() of the last byte read —
        heartbeat lines included — so a consumer can tell a silently-dead
        TCP stream (no FIN ever arrives) from an idle-but-alive one and
        `kill()` it instead of hanging forever.
    """

    def __init__(self, resp, cls: Type, resource: str = "",
                 drop_after: Optional[int] = None, binary: bool = False):
        self._resp = resp
        self._cls = cls
        self._resource = resource
        self._stopped = False
        #: injected wire fault: sever the stream after this many events
        self._drop_after = drop_after
        #: the server ECHOED the binary opt-in (Content-Type sniff): the
        #: pump reads length-prefixed binenc frames instead of JSON lines
        self._binary = binary
        self._delivered = 0
        self.killed = False
        self.error: Optional[BaseException] = None
        self.last_rv: Optional[int] = None
        self.last_activity = time.monotonic()
        self.events: "Queue[Optional[WatchEvent]]" = Queue()
        # the name is the role scheduler_thread_cpu_seconds sums it by
        self._thread = threading.Thread(target=self._pump, daemon=True,
                                        name="watch_pump")
        self._thread.start()

    def _pump(self) -> None:
        try:
            if self._binary:
                self._pump_binary()
            else:
                self._pump_json()
        except Exception as e:
            # a stop() tears the socket down under the read — that is a
            # clean close, not a stream failure; everything else is
            # terminal and the consumer decides resume-vs-relist from it
            if not self._stopped and self.error is None:
                self.error = e
            if self.error is not None:
                WATCH_STREAM_ERRORS.inc(
                    resource=self._resource,
                    reason=type(self.error).__name__)
        finally:
            try:
                self._resp.close()
            except Exception:
                pass
            self.events.put(None)

    def _pump_json(self) -> None:
        # the server heartbeats an empty line every second, so this
        # blocking read always turns over and a stop() is noticed
        # promptly; the response is closed by _pump's finally (closing
        # from another thread deadlocks http.client's buffered reader)
        for line in self._resp:
            self.last_activity = time.monotonic()
            if self._stopped:
                break
            WIRE_BYTES_RECEIVED.inc(len(line), encoding="json")
            line = line.strip()
            if not line:
                continue
            t0 = perf_counter()
            frame = json.loads(line)
            WIRE_DECODE_SECONDS.observe(perf_counter() - t0,
                                        encoding="json")
            if frame.get("type") == "BOOKMARK":
                # negotiated heartbeat carrying the server's current
                # rv: advances the consumer's resume point through
                # quiet periods. NOT an object event — it bypasses
                # the injected drop budget (wire-chaos watch plans
                # are keyed to real event counts, and a wall-clock-
                # timed heartbeat must not perturb them).
                rv = int(frame.get("rv") or 0)
                if rv:
                    self.last_rv = rv
                    self.events.put(WatchEvent(BOOKMARK, None, rv))
                continue
            if self._drop_after is not None \
                    and self._delivered >= self._drop_after:
                raise ConnectionResetError(
                    "injected watch drop "
                    f"(after {self._delivered} events)")
            slim = frame.get("slim")
            if slim == "bind" or slim == "binds":
                # negotiated compact bind frame(s): the informer
                # materializes each pod from its cached prior
                # revision. "binds" is the server's coalesced form —
                # one frame (one dumps/loads) for a whole bind batch,
                # split back into per-pod events here
                items = [frame["o"]] if slim == "bind" \
                    else frame["o"]["items"]
                for o in items:
                    rv = int(o["rv"])
                    self.last_rv = rv
                    self.events.put(WatchEvent(
                        frame["type"],
                        SlimBindRef(o.get("namespace", ""), o["name"],
                                    o["node"], o.get("ts"), rv), rv))
                    self._delivered += 1
                continue
            obj = serde.decode(self._cls, frame["object"])
            rv = int(obj.metadata.resource_version or 0)
            self.last_rv = rv
            self.events.put(WatchEvent(frame["type"], obj, rv))
            self._delivered += 1

    def _read_exact(self, n: int) -> bytes:
        """Read exactly n bytes off the (transparently de-chunked)
        response, or b"" on a clean EOF at a frame boundary. A short
        read mid-frame is a torn stream and raises."""
        buf = self._resp.read(n)
        if not buf or len(buf) == n:
            return buf
        chunks = [buf]
        got = len(buf)
        while got < n:
            chunk = self._resp.read(n - got)
            if not chunk:
                raise ConnectionError(
                    f"binary watch: stream ended {n - got} bytes into "
                    f"a frame")
            chunks.append(chunk)
            got += len(chunk)
        return b"".join(chunks)

    def _pump_binary(self) -> None:
        """Binary frame pump: 6-byte header, exact-length body. Same
        consumer contract as the JSON pump — BOOKMARK bypasses the
        injected drop budget, FT_BINDS splits into per-pod SlimBindRef
        events, FT_EVENT decodes the full object."""
        while True:
            hdr = self._read_exact(binenc.HEADER_SIZE)
            if not hdr:
                break  # server ended the stream cleanly
            self.last_activity = time.monotonic()
            if self._stopped:
                break
            ftype, blen = binenc.parse_header(hdr)
            body = self._read_exact(blen) if blen else b""
            WIRE_BYTES_RECEIVED.inc(binenc.HEADER_SIZE + blen,
                                    encoding="binary")
            if ftype == binenc.FT_HEARTBEAT:
                continue
            if ftype == binenc.FT_BOOKMARK:
                rv = int.from_bytes(body, "big")
                if rv:
                    self.last_rv = rv
                    self.events.put(WatchEvent(BOOKMARK, None, rv))
                continue
            if self._drop_after is not None \
                    and self._delivered >= self._drop_after:
                raise ConnectionResetError(
                    "injected watch drop "
                    f"(after {self._delivered} events)")
            if ftype == binenc.FT_BINDS:
                t0 = perf_counter()
                items = binenc.unpack(body)
                WIRE_DECODE_SECONDS.observe(perf_counter() - t0,
                                            encoding="binary")
                for o in items:
                    rv = int(o["rv"])
                    self.last_rv = rv
                    self.events.put(WatchEvent(
                        MODIFIED,
                        SlimBindRef(o.get("namespace", ""), o["name"],
                                    o["node"], o.get("ts"), rv), rv))
                    self._delivered += 1
                continue
            if ftype != binenc.FT_EVENT:
                raise binenc.BinencError(
                    f"binary watch: unknown frame type {ftype}")
            t0 = perf_counter()
            ev_type = binenc.EVENT_NAMES[body[0]]
            data, off = binenc.unpack_from(body, 1)
            if off != len(body):
                raise binenc.BinencError(
                    "binary watch: trailing bytes in event frame")
            obj = serde.decode(self._cls, data)
            WIRE_DECODE_SECONDS.observe(perf_counter() - t0,
                                        encoding="binary")
            rv = int(obj.metadata.resource_version or 0)
            self.last_rv = rv
            self.events.put(WatchEvent(ev_type, obj, rv))
            self._delivered += 1

    def stop(self) -> None:
        self._stopped = True

    def kill(self, reason: str = "watch stream stale") -> None:
        """Force-abort a silently-dead stream: mark it errored and shut
        the socket down so the blocked read returns NOW (a plain close()
        from this thread would deadlock http.client's buffered reader;
        socket shutdown doesn't take the reader's lock). Idempotent —
        the watchdog polls every second and the dead stream's
        last_activity never advances, so repeat calls must be no-ops."""
        if self.killed:
            return
        self.killed = True
        if self.error is None:
            self.error = WatchStaleError(reason)
        try:
            import socket as _socket
            self._resp.fp.raw._sock.shutdown(_socket.SHUT_RDWR)
        except Exception:
            # the socket is unreachable (nonstandard transport, fp
            # already detached): end the CONSUMER's round so it can
            # reconnect; the pump thread stays parked on its blocked
            # read (daemon — leaks until process exit). Never close()
            # from this thread: that deadlocks the buffered reader.
            self.events.put(None)

    def __iter__(self):
        while True:
            ev = self.events.get()
            if ev is None:
                return
            yield ev


class HTTPResourceClient:
    def __init__(self, base_url: str, scheme: Scheme, cls: Type,
                 namespace: Optional[str] = None,
                 token: Optional[str] = None, ssl_context=None,
                 wire_hook: Optional[Callable] = None,
                 wire: str = "json",
                 wire_state: Optional[dict] = None,
                 limiter=None, retry_budget=None, retry_429: int = 0,
                 clock=REAL_CLOCK, seed: int = 0):
        self._ssl = ssl_context
        #: client-side flow control, SHARED across the per-resource
        #: clients one HTTPClient hands out (like _wire_state): one
        #: token bucket and one retry budget per client process —
        #: per-resource instances would multiply the limit
        self._limiter = limiter
        self._retry_budget = retry_budget
        self._retry_429 = int(retry_429)
        self._retry_policy = BackoffPolicy(attempts=self._retry_429 + 1) \
            if self._retry_429 else None
        self._clock = clock
        self._seed = seed
        #: transport interceptor (see WIRE_REQUEST/WIRE_WATCH above):
        #: chaos runs inject latency, connection resets, and watch drops
        #: into the REAL http path here, not into a client wrapper
        self._wire_hook = wire_hook
        #: negotiated payload encoding preference ("json" | "binary"):
        #: binary ASKS via query opt-in and falls back silently when the
        #: peer answers JSON — old hubs keep working
        self._wire_binary = wire == "binary"
        #: capability state SHARED across this HTTPClient's per-resource
        #: clients (they are constructed per accessor call): flips to
        #: confirmed on the first binary-typed response, after which
        #: request BODIES (BindList) may be packed too — a binary body
        #: to an unconfirmed peer could land on an old hub that only
        #: reads JSON
        self._wire_state = wire_state if wire_state is not None \
            else {"confirmed": False}
        self._base = base_url.rstrip("/")
        self._scheme = scheme
        self._cls = cls
        self._token = token
        self._resource = scheme.resource_for(cls)
        self._namespaced = scheme.is_namespaced(cls)
        self._ns = namespace if self._namespaced else ""
        api_version, _ = scheme.gvk_for(cls)
        self._prefix = f"/api/{api_version}" if "/" not in api_version \
            else f"/apis/{api_version}"

    # ------------------------------------------------------------ plumbing

    def _url(self, name: str = "", namespace: Optional[str] = None,
             subresource: str = "", query: str = "") -> str:
        ns = namespace if namespace is not None else self._ns
        path = self._prefix
        if self._namespaced and ns:
            path += f"/namespaces/{ns}"
        path += f"/{self._resource}"
        if name:
            path += f"/{name}"
        if subresource:
            path += f"/{subresource}"
        if query:
            path += f"?{query}"
        return self._base + path

    def _headers(self) -> dict:
        headers = {"Content-Type": "application/json"}
        if self._token:
            headers["Authorization"] = f"Bearer {self._token}"
        return headers

    def _request(self, method: str, url: str, body: Any = None,
                 content_type: Optional[str] = None):
        if self._limiter is not None:
            # the client-go flowcontrol analog: smooth this client's
            # offered load BEFORE the server has to queue or shed it
            self._limiter.wait()
        if not self._retry_429:
            return self._request_once(method, url, body, content_type)
        # 429 retry loop: safe for every verb because the server sheds
        # BEFORE handling (the rejected request never executed). Delays
        # come from the shared backoff policy, floored by the server's
        # Retry-After, and gated by the per-client retry budget so a
        # synchronized fleet can't amplify an overload into a herd.
        op = f"{method}:{urlsplit(url).path}"
        delays = self._retry_policy.delays(seed=self._seed, op=op)
        while True:
            try:
                return self._request_once(method, url, body, content_type)
            except TooManyRequestsError as e:
                delay = next(delays, None)
                if delay is None:
                    raise  # policy exhausted: surface the 429
                if self._retry_budget is not None and \
                        not self._retry_budget.try_spend():
                    raise  # budget dry: stop amplifying
                if e.retry_after:
                    delay = max(delay, float(e.retry_after))
                self._clock.sleep(delay)
                if self._limiter is not None:
                    self._limiter.wait()

    def _request_once(self, method: str, url: str, body: Any = None,
                      content_type: Optional[str] = None):
        if content_type is not None:
            if content_type.startswith(binenc.CONTENT_TYPE):
                data = binenc.pack(body) if body is not None else None
            else:
                data = json.dumps(body).encode() \
                    if body is not None else None
        else:
            data = serde.to_json_str(body).encode() \
                if body is not None else None
        headers = self._headers()
        if content_type is not None:
            headers["Content-Type"] = content_type
        if data is not None:
            WIRE_BYTES_SENT.inc(
                len(data),
                encoding="binary" if content_type is not None
                and content_type.startswith(binenc.CONTENT_TYPE)
                else "json")
        req = urlrequest.Request(url, data=data, method=method,
                                 headers=headers)
        if self._wire_hook is not None:
            # may sleep (latency) or raise (connection reset) BEFORE the
            # bytes leave this process — the path component only, so the
            # fault signature is stable across runs with ephemeral ports
            self._wire_hook(WIRE_REQUEST, method, self._resource,
                            urlsplit(url).path)
        try:
            with urlrequest.urlopen(req, context=self._ssl) as resp:
                raw = resp.read()
                if resp.headers.get("Content-Type", "").startswith(
                        binenc.CONTENT_TYPE):
                    # the peer echoed the binary opt-in: decode packed,
                    # and unlock packed request bodies on this client
                    self._wire_state["confirmed"] = True
                    WIRE_BYTES_RECEIVED.inc(len(raw), encoding="binary")
                    t0 = perf_counter()
                    out = binenc.unpack(raw)
                    WIRE_DECODE_SECONDS.observe(perf_counter() - t0,
                                                encoding="binary")
                    return out
                WIRE_BYTES_RECEIVED.inc(len(raw), encoding="json")
                t0 = perf_counter()
                out = json.loads(raw)
                WIRE_DECODE_SECONDS.observe(perf_counter() - t0,
                                            encoding="json")
                return out
        except urlerror.HTTPError as e:
            _raise_for(e.code, e.read().decode(errors="replace"),
                       headers=e.headers)

    def _decode(self, data) -> Any:
        return serde.decode(self._cls, data)

    def _effective_ns(self, obj=None) -> str:
        if not self._namespaced:
            return ""
        if obj is not None and obj.metadata.namespace:
            return obj.metadata.namespace
        return self._ns or "default"

    # ------------------------------------------------------------ verbs

    def create(self, obj):
        ns = self._effective_ns(obj)
        return self._decode(self._request("POST", self._url(namespace=ns),
                                          obj))

    def create_bulk(self, objs: List[Any],
                    namespace: Optional[str] = None) -> List[Any]:
        """One POST of a List to the collection -> one store transaction
        server-side (mirrors state.ResourceClient.create_bulk). Result
        slots are truthy success markers ({"name", "resourceVersion"}
        dicts from the server's slim Status echo) or per-slot Exceptions.
        Mass loaders (benchmarks, kubeadm addons, controllers stamping N
        pods) stop paying one HTTP round trip per object."""
        if not objs:
            return []
        ns = namespace if namespace is not None else self._effective_ns()
        body = {"apiVersion": "v1", "kind": "List",
                "items": [serde.encode(o) for o in objs]}
        resp = self._request("POST", self._url(namespace=ns), body,
                             content_type="application/json")
        out: List[Any] = []
        for item in resp.get("items", []):
            if item.get("kind") == "Status" and \
                    item.get("status") != "Success":
                exc = {"NotFoundError": NotFoundError,
                       "AlreadyExistsError": AlreadyExistsError,
                       "ConflictError": ConflictError} \
                    .get(item.get("reason", ""), RuntimeError)(
                        item.get("message", ""))
                out.append(exc)
            else:
                out.append(item.get("metadata", True))
        while len(out) < len(objs):
            out.append(RuntimeError("bulk create: missing result slot"))
        return out

    def get(self, name: str, namespace: Optional[str] = None):
        return self._decode(self._request(
            "GET", self._url(name, namespace=namespace)))

    def list(self, namespace: Optional[str] = None,
             label_selector: Optional[LabelSelector] = None) -> List[Any]:
        items, _ = self.list_rv(namespace)
        if label_selector is not None:
            items = [o for o in items
                     if labelsmod.matches(label_selector, o.metadata.labels)]
        return items

    def list_rv(self, namespace: Optional[str] = None):
        ns = namespace if namespace is not None else (self._ns or None)
        # binary opt-in rides the query like slimBind; the response
        # shape is IDENTICAL either way (_request decodes by the
        # response Content-Type), so an old hub silently answers JSON
        url = self._url(namespace=ns or "",
                        query="binary=true" if self._wire_binary else "")
        data = self._request("GET", url)
        items = [self._decode(d) for d in data.get("items", [])]
        rv = int(data.get("metadata", {}).get("resourceVersion", 0))
        return items, rv

    def update(self, obj):
        ns = self._effective_ns(obj)
        return self._decode(self._request(
            "PUT", self._url(obj.metadata.name, namespace=ns), obj))

    def update_status(self, obj):
        ns = self._effective_ns(obj)
        return self._decode(self._request(
            "PUT", self._url(obj.metadata.name, namespace=ns,
                             subresource="status"), obj))

    def _raw_patch(self, name: str, body: Any, content_type: str,
                   namespace: Optional[str] = None, subresource: str = ""):
        ns = namespace if namespace is not None else self._effective_ns()
        url = self._url(name, namespace=ns, subresource=subresource)
        return self._decode(self._request("PATCH", url, body,
                                          content_type=content_type))

    def merge_patch(self, name: str, patch: dict,
                    namespace: Optional[str] = None, subresource: str = "",
                    strategic: bool = True):
        """Send a server-side merge patch (strategic by default — named
        lists like containers merge by name; RFC 7386 otherwise)."""
        ctype = "application/strategic-merge-patch+json" if strategic \
            else "application/merge-patch+json"
        return self._raw_patch(name, patch, ctype, namespace, subresource)

    def json_patch(self, name: str, ops: list,
                   namespace: Optional[str] = None, subresource: str = ""):
        """Send an RFC 6902 op-list patch."""
        return self._raw_patch(name, ops, "application/json-patch+json",
                               namespace, subresource)

    def get_scale(self, name: str, namespace: Optional[str] = None):
        """GET the /scale subresource (ref: scale client in client-go)."""
        from ..api.autoscaling import Scale
        ns = namespace if namespace is not None else self._effective_ns()
        return serde.decode(Scale, self._request(
            "GET", self._url(name, namespace=ns, subresource="scale")))

    def update_scale(self, name: str, scale,
                     namespace: Optional[str] = None):
        from ..api.autoscaling import Scale
        ns = namespace if namespace is not None else self._effective_ns()
        return serde.decode(Scale, self._request(
            "PUT", self._url(name, namespace=ns, subresource="scale"),
            scale))

    def patch(self, name: str, mutate: Callable[[Any], Any],
              namespace: Optional[str] = None, retries: int = 16):
        """Read-modify-write that ships only the DIFF as a server-side
        merge patch, preconditioned on the read's resourceVersion (the
        reference's optimistic-concurrency PATCH). Retries re-read and
        re-run mutate, so concurrent writers to OTHER fields never lose
        updates to ours."""
        from ..api.patch import diff_merge_patch
        for _ in range(retries):
            cur = self.get(name, namespace=namespace)
            before = json.loads(serde.to_json_str(cur))
            updated = mutate(serde.deepcopy_obj(cur))
            after = json.loads(serde.to_json_str(updated))
            delta = diff_merge_patch(before, after)
            if not delta:
                return cur
            delta.setdefault("metadata", {})["resourceVersion"] = \
                cur.metadata.resource_version
            try:
                return self.merge_patch(name, delta, namespace=namespace,
                                        strategic=False)
            except ConflictError:
                continue
        raise ConflictError(f"{self._resource} {name}: too many conflicts")

    def delete(self, name: str, namespace: Optional[str] = None,
               resource_version: Optional[str] = None):
        query = f"resourceVersion={resource_version}" \
            if resource_version is not None else ""
        return self._decode(self._request(
            "DELETE", self._url(name, namespace=namespace, query=query)))

    #: slim-frame negotiation is an INFORMER opt-in (it materializes
    #: deltas from its indexer); raw watch consumers iterate full
    #: objects and must never receive SlimBindRef placeholders
    _SLIM_WATCH = False

    def watch(self, namespace: Optional[str] = None,
              resource_version: Optional[int] = None,
              bookmarks: bool = False) -> _HTTPWatch:
        ns = namespace if namespace is not None else (self._ns or None)
        query = "watch=true"
        if resource_version is not None:
            query += f"&resourceVersion={resource_version}"
        if self._SLIM_WATCH:
            query += "&slimBind=true"
        if bookmarks:
            # opt-in BOOKMARK heartbeats (the reference's
            # allowWatchBookmarks): raw consumers that iterate events
            # must be ready for object-less frames, so informers — which
            # track last_sync_rv — are the ones that ask
            query += "&allowWatchBookmarks=true"
        if self._wire_binary:
            query += "&binary=true"
        url = self._url(namespace=ns or "", query=query)
        drop_after = None
        if self._wire_hook is not None:
            # the hook may raise (connect-time reset) or hand back an
            # event budget after which the stream is severed mid-flight
            drop_after = self._wire_hook(WIRE_WATCH, "WATCH",
                                         self._resource,
                                         urlsplit(url).path)
        req = urlrequest.Request(url, headers=self._headers())
        try:
            resp = urlrequest.urlopen(req, context=self._ssl)
        except urlerror.HTTPError as e:
            _raise_for(e.code, e.read().decode(errors="replace"),
                       headers=e.headers)
        # the server's Content-Type echo decides the pump: an old hub
        # ignores &binary=true and answers json;stream=watch, and the
        # line pump keeps working — negotiation is response-driven,
        # never assumed
        binary = resp.headers.get("Content-Type", "").startswith(
            binenc.CONTENT_TYPE)
        if binary:
            self._wire_state["confirmed"] = True
        return _HTTPWatch(resp, self._cls, resource=self._resource,
                          drop_after=drop_after, binary=binary)


class HTTPPodClient(HTTPResourceClient):

    def evict(self, name: str, namespace: Optional[str] = None):
        """POST the pods/eviction subresource (PDB-guarded delete). Raises
        TooManyDisruptions on a 429 budget refusal."""
        ns = namespace if namespace is not None else self._effective_ns()
        body = {"apiVersion": "policy/v1beta1", "kind": "Eviction",
                "metadata": {"name": name, "namespace": ns}}
        return self._request(
            "POST", self._url(name, namespace=ns, subresource="eviction"),
            body, content_type="application/json")

    def bind(self, binding: corev1.Binding):
        ns = binding.metadata.namespace or self._effective_ns()
        return self._decode(self._request(
            "POST", self._url(binding.metadata.name, namespace=ns,
                              subresource="binding"), binding))

    def bind_bulk_pairs(self, namespace: str, pairs) -> List[Any]:
        """One POST of slim BindList pairs to one namespace -> one store
        transaction server-side. The cheapest wire bind: no Binding/
        ObjectMeta construction caller-side, no per-item serde decode
        server-side. Result slots are truthy success markers or per-slot
        Exceptions, in pair order."""
        if not pairs:
            return []
        body = {"apiVersion": "v1", "kind": "BindList",
                "items": [[name, node] for name, node in pairs]}
        url = f"{self._base}/api/v1/namespaces/{namespace}/bindings"
        if self._wire_binary:
            # ask for a binary Status echo; pack the request body only
            # once a prior binary response CONFIRMED the peer speaks it
            # (the first batch goes JSON — an old hub must never be
            # handed bytes it cannot parse). The echo itself confirms,
            # so a write-only client upgrades on its second batch.
            url += "?binary=true"
            ctype = binenc.CONTENT_TYPE \
                if self._wire_state.get("confirmed") \
                else "application/json"
        else:
            ctype = "application/json"
        resp = self._request("POST", url, body, content_type=ctype)
        out = [self._decode_bind_slot(item)
               for item in resp.get("items", [])]
        # a truncated/malformed response must not leave missing slots —
        # the scheduler treats non-Exception slots as bound pods
        while len(out) < len(pairs):
            out.append(RuntimeError("bulk bind: missing result slot"))
        return out[:len(pairs)]

    @staticmethod
    def _decode_bind_slot(item):
        from ..state.store import ConflictError, NotFoundError
        if item.get("kind") == "Status" and \
                item.get("status") != "Success":
            reason = item.get("reason", "")
            msg = item.get("message", "")
            return {"NotFoundError": NotFoundError,
                    "ConflictError": ConflictError} \
                .get(reason, RuntimeError)(msg)
        if item.get("kind") == "Status":
            return True
        # an older/full server echoing the bound pod
        return serde.decode(corev1.Pod, item)

    def bind_bulk(self, bindings: List[corev1.Binding]) -> List[Any]:
        """One POST of a Binding List per namespace -> one store
        transaction server-side (the wire analog of the in-process batch
        bind; the reference has no bulk verb — N sequential bind POSTs
        there cost N round trips, the hot cost this path removes).
        Result slots are truthy success markers (the server answers with
        slim Status slots, like the reference's bind) or per-slot
        Exceptions — callers needing the bound object use their own copy
        (the scheduler clones locally; the informer echo confirms)."""
        if not bindings:
            return []
        by_ns: dict = {}
        for i, b in enumerate(bindings):
            ns = b.metadata.namespace or self._effective_ns()
            by_ns.setdefault(ns, []).append((i, b))
        out: List[Any] = [None] * len(bindings)
        for ns, slots in by_ns.items():
            try:
                rs = self.bind_bulk_pairs(
                    ns, [(b.metadata.name, b.target.name)
                         for _, b in slots])
            except Exception as e:
                rs = [e] * len(slots)
            for (i, _), r in zip(slots, rs):
                out[i] = r
        return out


class HTTPClient:
    """Drop-in for state.client.Client over REST. `token` sends bearer
    credentials (the kubeconfig token shape)."""

    def __init__(self, base_url: str, scheme: Scheme = SCHEME,
                 token: Optional[str] = None,
                 cert_file: Optional[str] = None,
                 key_file: Optional[str] = None,
                 ca_file: Optional[str] = None,
                 insecure_skip_tls_verify: bool = False,
                 wire_hook: Optional[Callable] = None,
                 wire: Optional[str] = None,
                 qps: Optional[float] = None, burst: int = 10,
                 retry_429: int = 0, retry_budget=None,
                 clock=None, seed: int = 0):
        self.base_url = base_url
        self.scheme = scheme
        self.token = token
        self.wire_hook = wire_hook
        # ---- client-side flow control (ISSUE 19, the client-go
        # flowcontrol analog): `qps`/`burst` smooth offered load through
        # a token bucket; `retry_429` > 0 turns on honoring the server's
        # Retry-After for that many retries, spent from a shared
        # RetryBudget (default cap 10, +0.5/s) so a herd can't form.
        # Both default OFF — existing callers see identical behavior.
        from .flowcontrol import RetryBudget, TokenBucket
        self._clock = clock if clock is not None else REAL_CLOCK
        self.seed = seed
        self.retry_429 = int(retry_429)
        self.limiter = TokenBucket(qps, burst=burst, clock=self._clock) \
            if qps else None
        self.retry_budget = retry_budget if retry_budget is not None \
            else (RetryBudget(clock=self._clock) if self.retry_429
                  else None)
        #: payload encoding preference ("json" | "binary"); defaults
        #: from KTPU_WIRE so a whole deployment flips with one env var.
        #: Read ONCE at construction — no per-request env draws.
        self.wire = wire if wire is not None \
            else os.environ.get("KTPU_WIRE", "json")
        #: binary-capability state shared by every per-resource client
        #: this instance hands out (see HTTPResourceClient.__init__)
        self._wire_state = {"confirmed": False}
        self.ssl_context = None
        if base_url.startswith("https") or cert_file or ca_file:
            # kubeconfig TLS shape: server CA pinning + optional client
            # cert/key pair for x509 authentication. An https server with
            # neither a CA nor the explicit insecure flag FAILS here —
            # silently skipping verification would hand bearer tokens to
            # any MITM
            import ssl
            if ca_file:
                ctx = ssl.create_default_context(cafile=ca_file)
                ctx.check_hostname = False  # pinned by CA; hosts are IPs
            elif insecure_skip_tls_verify:
                ctx = ssl.create_default_context()
                ctx.check_hostname = False
                ctx.verify_mode = ssl.CERT_NONE
            else:
                raise ValueError(
                    "https server requires ca_file (to pin the server "
                    "cert) or insecure_skip_tls_verify=True")
            if cert_file:
                ctx.load_cert_chain(cert_file, key_file)
            self.ssl_context = ctx

    def resource(self, cls: Type, namespace: Optional[str] = None):
        kind = HTTPPodClient if cls is corev1.Pod else HTTPResourceClient
        return kind(self.base_url, self.scheme, cls, namespace,
                    token=self.token,
                    ssl_context=self.ssl_context,
                    wire_hook=self.wire_hook,
                    wire=self.wire,
                    wire_state=self._wire_state,
                    limiter=self.limiter,
                    retry_budget=self.retry_budget,
                    retry_429=self.retry_429,
                    clock=self._clock, seed=self.seed)

    def __getattr__(self, name):
        """Convenience accessors (pods(), nodes(), ...) mirror Client's by
        delegating through the same resource table."""
        from ..state.client import Client
        template = getattr(Client, name, None)
        if template is None or not callable(template):
            raise AttributeError(name)

        def accessor(*args, **kwargs):
            shim = _AccessorShim(self)
            return template(shim, *args, **kwargs)
        return accessor


class _AccessorShim:
    """Duck-typed `self` for Client's accessor methods: only .resource is
    consulted by them."""

    def __init__(self, http: HTTPClient):
        self._http = http

    def resource(self, cls: Type, namespace: Optional[str] = None):
        return self._http.resource(cls, namespace)
