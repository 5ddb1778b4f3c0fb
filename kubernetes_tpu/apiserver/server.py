"""HTTP API server over the Store.

Ref: staging/src/k8s.io/apiserver. Routes follow the reference's URL
scheme (endpoints/installer.go registerResourceHandlers):

    /api/v1/{resource}                              cluster-scoped core
    /api/v1/namespaces/{ns}/{resource}[/{name}]     namespaced core
    /apis/{group}/{version}/...                     named groups
    .../pods/{name}/binding                         bind subresource (POST)
    .../{resource}/{name}/status                    status subresource (PUT)
    GET ...?watch=true&resourceVersion=N            chunked watch stream
    /healthz, /readyz                               health endpoints

The handler chain is the reference's DefaultBuildHandlerChain
(config.go:543-557) reduced to what a single-tenant hub needs: panic
recovery (http.server gives per-request isolation), request-info parsing,
then ADMISSION on writes — the mutating-then-validating plugin chain
(apiserver/pkg/admission) as a first-class hook point.

Wire format: the serde camelCase JSON; watch frames are one JSON object
per line `{"type": "ADDED", "object": {...}}` exactly like the reference's
watch framing (application/json;stream=watch).
"""

from __future__ import annotations

import json
import os
import threading
import traceback
from collections import deque
from contextlib import contextmanager
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from time import perf_counter
from typing import Any, Callable, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from ..api import binenc, serde
from ..api.core import Binding
from . import flowcontrol
from .admission import QuotaExceeded
from ..api.validation import ValidationError
from ..runtime.scheme import SCHEME, Scheme
from ..state.client import Client, TooManyDisruptions
from ..state.store import (BOOKMARK, MODIFIED, AlreadyExistsError,
                           ConflictError, ExpiredError, NotFoundError, Store)
from ..observability.tracer import NULL_TRACER
from ..utils.errlog import SwallowedErrors


class AdmissionDenied(Exception):
    pass


class AdmissionChain:
    """Mutating-then-validating plugin chain (ref: apiserver/pkg/admission
    — Interface.Admit then Validate). A mutator returns the (possibly
    replaced) object; a validator raises AdmissionDenied to reject."""

    def __init__(self):
        self.mutators: List[Callable[[str, str, Any], Any]] = []
        self.validators: List[Callable[[str, str, Any], None]] = []

    def admit(self, operation: str, resource: str, obj: Any) -> Any:
        for m in self.mutators:
            obj = m(operation, resource, obj) or obj
        for v in self.validators:
            v(operation, resource, obj)
        return obj


class _CreateGate:
    """The single-writer section of the create path: one request at a
    time prepares (decode, admission, defaults, validation) and commits.
    Under one interpreter lock parallel preparation buys no parallelism,
    and the forced hand-offs between compute-bound handler threads cost
    a quarter to a half again of the hub's CPU. Waiters enter in arrival
    order (a plain Lock promises none), so no creator starves; a lone
    create finds the gate free and pays two uncontended lock operations.
    Binds, updates, deletes and reads never come here."""

    def __init__(self, metrics):
        self._mutex = threading.Lock()
        self._owner: Optional[int] = None  # thread ident, None when free
        #: (held Lock, thread ident) per waiting thread, in arrival order
        self._waiters: deque = deque()
        self._wait = metrics.create_gate_wait
        self._contended = metrics.create_gate_contended

    def _acquire(self) -> bool:
        """Enter in arrival order; True when the gate was taken."""
        with self._mutex:
            if self._owner is None:
                self._owner = threading.get_ident()
                return False
            turn = threading.Lock()
            turn.acquire()
            self._waiters.append((turn, threading.get_ident()))
        turn.acquire()  # _release has made this thread the owner
        return True

    def _release(self) -> None:
        with self._mutex:
            if self._waiters:
                # handed over, never free in between: a newcomer cannot
                # overtake the queue
                turn, self._owner = self._waiters.popleft()
                turn.release()
            else:
                self._owner = None

    def __enter__(self) -> "_CreateGate":
        # asked -> entered, one observation a request
        with NULL_TRACER.stage("create_gate_wait", self._wait):
            if self._acquire():
                self._contended.inc()
        return self

    def __exit__(self, *exc) -> None:
        self._release()

    @contextmanager
    def released(self):
        """Leave the gate for a call that waits on a socket (an admission
        webhook) and queue for it again afterwards; a no-op on a thread
        that is not inside (admission of an UPDATE, the in-process
        Client)."""
        if self._owner != threading.get_ident():
            yield
            return
        self._release()
        try:
            yield
        finally:
            self._acquire()


class _Request:
    """Parsed request-info (ref: apiserver/pkg/endpoints/request
    RequestInfoFactory)."""

    __slots__ = ("resource", "namespace", "name", "subresource", "query",
                 "tail")

    def __init__(self, resource: str, namespace: str, name: str,
                 subresource: str, query: dict, tail=()):
        self.resource = resource
        self.namespace = namespace
        self.name = name
        self.subresource = subresource
        self.query = query
        #: path segments past the subresource (the proxy verb's target)
        self.tail = tuple(tail)


class APIServer:
    """The hub: one handler thread per connection over one Store.

    The write path of a create: read and parse the body -> the
    single-writer section (`_CreateGate`: decode the items, admit,
    default and validate, commit the store transaction; one create
    request at a time, in arrival order) -> encode and write the
    response. Body read, response, audit and an admission webhook's
    remote call are outside the section. Binds, updates, patches,
    deletes, LISTs and watch streams never enter it."""

    def __init__(self, store: Optional[Store] = None, scheme: Scheme = SCHEME,
                 host: str = "127.0.0.1", port: int = 0,
                 audit_log_path: Optional[str] = None,
                 tls_cert_file: Optional[str] = None,
                 tls_key_file: Optional[str] = None,
                 client_ca_file: Optional[str] = None,
                 max_mutating_inflight: int = 200,
                 max_nonmutating_inflight: int = 400,
                 request_timeout: float = 60.0,
                 cors_allowed_origins: Optional[List[str]] = None,
                 metrics=None, flight_recorder=None,
                 apf: Optional[bool] = None,
                 flow_queues: int = 8,
                 flow_queue_length: int = 16,
                 flow_queue_timeout: float = 5.0,
                 flow_seed: int = 0,
                 flow_shares: Optional[dict] = None,
                 flow_clock=None,
                 flow_record: bool = False):
        self.client = Client(store)
        self.store = self.client.store
        self.scheme = scheme
        self.admission = AdmissionChain()
        #: binary-frame kill-switch (KTPU_BINARY_WIRE=0): a hub that
        #: never echoes the binary opt-in — every client silently keeps
        #: JSON, exactly the old-peer downgrade contract. Read ONCE at
        #: construction, like the client's KTPU_WIRE draw.
        self.binary_wire = os.environ.get("KTPU_BINARY_WIRE", "1") != "0"
        # ---- observability surface (ISSUE 11): the hub is the cluster's
        # scrape point. `metrics` is an observability.MetricsRegistry
        # aggregating every attached component's families (collision-
        # checked) plus the hub's own request/watch counters, served at
        # GET /metrics; `flight_recorder` backs /debug/traces; pending
        # providers (scheduler.debugger.pending_report) back
        # /debug/pending; `health` checks gate /readyz.
        from ..observability import FlightRecorder, MetricsRegistry
        from ..utils.healthz import HealthChecks
        from ..utils.metrics import APIServerMetrics
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.request_metrics = APIServerMetrics()
        self.metrics.add_registry("apiserver", self.request_metrics.registry)
        self.flight = flight_recorder if flight_recorder is not None \
            else FlightRecorder()
        self.health = HealthChecks()
        self.pending_providers: List[Callable[[], dict]] = []
        #: structured audit trail (ref: apiserver/pkg/audit — the
        #: ResponseComplete stage as one JSON line per request)
        self._audit_file = open(audit_log_path, "a") \
            if audit_log_path else None
        self._audit_lock = threading.Lock()
        #: optional authn/authz (ref: DefaultBuildHandlerChain slots at
        #: config.go:543-557); None = open hub (the insecure port shape)
        self.authenticator = None
        self.authorizer = None
        self._bootstrap_namespaces()
        self._register_existing_crds()
        self.admission.validators.append(self._namespace_lifecycle)
        # default-enabled plugins (ref: kube-apiserver's default enabled
        # admission set includes LimitRanger and ResourceQuota; both no-op
        # in namespaces carrying no LimitRange/ResourceQuota objects)
        from .admission import (LimitRanger, PriorityAdmission,
                                ResourceQuotaAdmission,
                                ServiceAccountAdmission)
        self.admission.mutators.append(PriorityAdmission(self.client).admit)
        limitranger = LimitRanger(self.client)
        self.admission.mutators.append(limitranger.admit)
        self.admission.validators.append(limitranger.validate)
        sa = ServiceAccountAdmission(self.client)
        self.admission.mutators.append(sa.admit)
        self.admission.validators.append(sa.validate)
        from ..tenancy import QuotaMetrics
        self.quota_metrics = QuotaMetrics()
        self.metrics.add_registry("quota", self.quota_metrics.registry)
        self._quota = ResourceQuotaAdmission(
            self.client, metrics=self.quota_metrics)
        from .admission import NodeRestriction
        self.admission.validators.append(NodeRestriction(self).validate)
        # out-of-process webhooks: mutating AFTER the in-process mutators
        # (they see defaulted objects), validating LAST (ref: the
        # reference's plugin ordering — ValidatingAdmissionWebhook at the
        # end of the chain)
        from .admission import WebhookDispatcher
        #: the create path's single-writer section (_CreateGate); a
        #: webhook's remote call leaves it for the round trip
        self._create_gate = _CreateGate(self.request_metrics)
        webhooks = WebhookDispatcher(
            self.client, around_call=self._create_gate.released)
        self.admission.mutators.append(webhooks.admit)
        self.admission.validators.append(webhooks.validate)
        # ResourceQuota runs LAST so a later validator's denial can never
        # strand a committed charge (the reference orders ResourceQuota at
        # the end of the default plugin set for exactly this reason)
        self.admission.validators.append(self._quota.validate)
        #: request-scoped authenticated user (ThreadingHTTPServer gives one
        #: thread per request) — admission plugins that need the requester
        #: (NodeRestriction) read it via current_user()
        self._req_local = threading.local()
        #: overload protection (ref: DefaultBuildHandlerChain's
        #: max-in-flight slot, config.go:545 — split read/write pools so N
        #: slow readers can't starve writes); watches are long-running and
        #: exempt, like the reference's longRunningRequestCheck
        self._read_sem = threading.BoundedSemaphore(
            max_nonmutating_inflight) if max_nonmutating_inflight else None
        self._write_sem = threading.BoundedSemaphore(
            max_mutating_inflight) if max_mutating_inflight else None
        self._read_pool = max_nonmutating_inflight
        self._write_pool = max_mutating_inflight
        # ---- API Priority & Fairness (ISSUE 19): flow-schema
        # classification + per-priority-level fair queues carved from the
        # SAME pool sizes the legacy try-acquire used, so APF negotiates
        # the existing capacity rather than adding any. KTPU_APF=0 (or
        # apf=False) keeps the legacy instant-shed path — whose
        # Retry-After is now computed from the observed completion rate
        # instead of hardcoded. Env read ONCE at construction, like
        # KTPU_BINARY_WIRE above.
        from ..utils.clock import REAL_CLOCK
        from ..utils.metrics import FlowControlMetrics
        if apf is None:
            apf = os.environ.get("KTPU_APF", "1") != "0"
        self.apf = bool(apf) and bool(max_mutating_inflight
                                      or max_nonmutating_inflight)
        self._flow_clock = flow_clock if flow_clock is not None \
            else REAL_CLOCK
        self.flow_metrics = FlowControlMetrics()
        self.metrics.add_registry("flowcontrol",
                                  self.flow_metrics.registry)
        self._flow = flowcontrol.FlowController(
            read_pool=max_nonmutating_inflight,
            write_pool=max_mutating_inflight,
            shares=flow_shares,
            n_queues=flow_queues, queue_length=flow_queue_length,
            queue_timeout=flow_queue_timeout, seed=flow_seed,
            clock=self._flow_clock, metrics=self.flow_metrics,
            record=flow_record) if self.apf else None
        #: completion-rate estimator backing the legacy shed path's
        #: computed Retry-After (APF computes its own from queue state)
        self._legacy_drain = flowcontrol.DrainEstimator(self._flow_clock)
        #: namespace -> serving.ktpu/tenant label, cached for flow-key
        #: resolution (invalidated on namespace writes)
        self._tenant_cache: dict = {}
        self._flow_swallowed = SwallowedErrors("apiserver-flow")
        #: per-request socket deadline for non-watch requests (the
        #: timeout filter analog: a stalled client can't pin a worker
        #: thread forever)
        self._request_timeout = request_timeout
        self._cors_origins = list(cors_allowed_origins or [])
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):  # quiet
                pass

            def do_GET(self):
                outer._dispatch(self, "GET")

            def do_POST(self):
                outer._dispatch(self, "POST")

            def do_PUT(self):
                outer._dispatch(self, "PUT")

            def do_DELETE(self):
                outer._dispatch(self, "DELETE")

            def do_PATCH(self):
                outer._dispatch(self, "PATCH")

            def do_OPTIONS(self):
                outer._preflight(self)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self._tls = bool(tls_cert_file)
        if tls_cert_file:
            # the reference's secure serving port: TLS with OPTIONAL
            # client certs verified against --client-ca-file; an x509
            # peer identity then wins over bearer headers
            import ssl
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(tls_cert_file, tls_key_file)
            if client_ca_file:
                ctx.load_verify_locations(client_ca_file)
                ctx.verify_mode = ssl.CERT_OPTIONAL
            # handshake on first read in the per-connection WORKER thread:
            # with do_handshake_on_connect the handshake runs inside
            # accept() on the single serve_forever thread, so one stalled
            # client would freeze every new connection
            self._httpd.socket = ctx.wrap_socket(
                self._httpd.socket, server_side=True,
                do_handshake_on_connect=False)
        self._thread: Optional[threading.Thread] = None

    def _bootstrap_namespaces(self) -> None:
        """The system namespaces every cluster has (ref: the apiserver's
        bootstrap controller creating default/kube-system/kube-public)."""
        from ..api.core import Namespace
        from ..api.meta import ObjectMeta
        from ..state.replication import ReplicaNotPromoted
        for name in ("default", "kube-system", "kube-node-lease",
                     "kube-public"):
            try:
                self.client.namespaces().create(
                    Namespace(metadata=ObjectMeta(name=name)))
            except AlreadyExistsError:
                pass  # WAL replay already restored it
            except ReplicaNotPromoted:
                return  # standby over a follower store: the primary's
                # replicated namespaces arrive through replication
            self._ensure_default_sa(name)

    def _ensure_default_sa(self, namespace: str) -> None:
        """Every namespace carries a "default" ServiceAccount (the
        serviceaccounts controller's invariant; stamped server-side too so
        pod admission never races namespace creation)."""
        from ..api.core import ServiceAccount
        from ..api.meta import ObjectMeta
        try:
            self.client.service_accounts(namespace).create(ServiceAccount(
                metadata=ObjectMeta(name="default", namespace=namespace)))
        except (AlreadyExistsError, NotFoundError):
            pass

    def _register_existing_crds(self) -> None:
        """CRDs already in the store (handed-in store without WAL replay)
        must serve immediately."""
        from ..runtime.crd import register_crd
        try:
            items, _ = self.store.list("customresourcedefinitions", None)
        except Exception:
            return
        for crd in items:
            try:
                register_crd(crd, self.scheme)
            except ValueError:
                pass

    def _update_crd(self, rc, obj):
        """CRD updates must re-validate and re-register live — otherwise
        the scheme serves the OLD names until restart while WAL replay
        would register the NEW shape (live/replay divergence), and a
        rename onto a builtin's plural would only explode at replay."""
        from ..runtime.crd import register_crd, unregister_crd, validate_crd
        old = rc.get(obj.metadata.name)
        validate_crd(obj, self.scheme if obj.spec.names.plural !=
                     old.spec.names.plural else None)
        out = rc.update(obj)
        if (old.spec.group, old.spec.names.kind,
                old.spec.names.plural) != (out.spec.group,
                                           out.spec.names.kind,
                                           out.spec.names.plural):
            unregister_crd(old, self.scheme)
        register_crd(out, self.scheme)
        return out

    def _delete_cr_instances(self, crd) -> None:
        """Deleting a CRD deletes its custom resources (the reference's
        apiextensions finalizer does this cleanup); without it the orphaned
        records resurrect on WAL replay once the type re-registers."""
        plural = crd.spec.names.plural
        try:
            items, _ = self.store.list(plural, None)
        except Exception:
            return
        for obj in items:
            try:
                self.store.delete(plural, obj.metadata.namespace,
                                  obj.metadata.name)
            except NotFoundError:
                pass

    def _namespace_lifecycle(self, operation: str, resource: str,
                             obj) -> None:
        """The NamespaceLifecycle admission plugin (ref: plugin/pkg/
        admission/namespace/lifecycle): creates into a terminating or
        missing namespace are rejected."""
        if operation != "CREATE" or resource == "namespaces":
            return
        ns = getattr(obj.metadata, "namespace", "")
        if not ns:
            return  # cluster-scoped
        try:
            cur = self.client.namespaces().get(ns)
        except NotFoundError:
            raise AdmissionDenied(
                f'namespace "{ns}" not found')
        if cur.metadata.deletion_timestamp is not None or \
                cur.status.phase == "Terminating":
            raise AdmissionDenied(
                f'unable to create new content in namespace "{ns}" because '
                f"it is being terminated")

    # ------------------------------------------------------------ lifecycle

    @property
    def address(self) -> str:
        host, port = self._httpd.server_address[:2]
        scheme = "https" if self._tls else "http"
        return f"{scheme}://{host}:{port}"

    def attach_replica(self, replica,
                       max_lag_records: int = 1024) -> None:
        """Wire a StoreReplica into this server's observability surface:
        its lag/promote attribution joins /debug/pending and a
        replication-lag readiness check gates /readyz (a standby too far
        behind would lose acknowledged writes if promoted, so it must
        stop answering ready)."""
        from ..utils.healthz import replication_contributor
        self.pending_providers.append(replica.pending_report)
        self.health.add_all(replication_contributor(
            replica, max_lag_records=max_lag_records))

    def start(self) -> "APIServer":
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True, name="apiserver")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._audit_file is not None:
            with self._audit_lock:
                self._audit_file.close()
                self._audit_file = None

    # ------------------------------------------------------------- routing

    def _parse(self, path: str, query: dict) -> Optional[_Request]:
        """URL -> request-info. Accepts /api/v1/... and /apis/{g}/{v}/..."""
        parts = [p for p in path.split("/") if p]
        if not parts:
            return None
        if parts[0] == "api" and len(parts) >= 2:
            rest = parts[2:]
        elif parts[0] == "apis" and len(parts) >= 3:
            rest = parts[3:]
        else:
            return None
        ns = ""
        # /namespaces/{ns}/{resource}/... scopes the request; a bare
        # /namespaces or /namespaces/{name}[/{sub}] addresses Namespace
        # objects — disambiguated by whether the third segment is a known
        # resource (the reference's RequestInfoFactory does the same)
        if rest and rest[0] == "namespaces" and len(rest) >= 3 and \
                self.scheme.type_for_resource(rest[2]) is not None:
            ns, rest = rest[1], rest[2:]
        if not rest:
            return None
        resource = rest[0]
        name = rest[1] if len(rest) > 1 else ""
        sub = rest[2] if len(rest) > 2 else ""
        return _Request(resource, ns, name, sub, query, tail=rest[3:])

    def _preflight(self, h) -> None:
        """CORS preflight (ref: the chain's CORS filter, config.go:552)."""
        origin = h.headers.get("Origin", "")
        h.send_response(204)
        if self._cors_allowed(origin):
            h.send_header("Access-Control-Allow-Origin", origin)
            h.send_header("Access-Control-Allow-Methods",
                          "GET, POST, PUT, PATCH, DELETE, OPTIONS")
            h.send_header("Access-Control-Allow-Headers",
                          "Content-Type, Authorization")
        h.send_header("Content-Length", "0")
        h.end_headers()

    def _cors_allowed(self, origin: str) -> bool:
        return bool(origin) and ("*" in self._cors_origins
                                 or origin in self._cors_origins)

    def _dispatch(self, h: BaseHTTPRequestHandler, method: str) -> None:
        # CORS response header on every request from an allowed origin —
        # reset unconditionally: keep-alive reuses the handler instance,
        # so a stale grant must not leak onto the NEXT request
        origin = h.headers.get("Origin", "")
        h._cors_origin = origin if self._cors_allowed(origin) else None
        # keep-alive reuses the handler instance: a request that dies
        # before writing any response must not be counted (or audited)
        # under the PREVIOUS request's status code
        h._audit_code = 0
        # parse ONCE: request-info drives both flow-control classification
        # and routing. The watch exemption reads the PARSED query — the
        # old substring check also matched a "watch=true" anywhere in the
        # path, e.g. inside an object name
        url = urlparse(h.path or "")
        query = {k: v[0] for k, v in parse_qs(url.query).items()}
        is_watch = query.get("watch") in ("true", "1")
        exempt = url.path in ("/healthz", "/livez", "/readyz")
        req = self._parse(url.path, query) if not exempt else None
        # overload protection: APF classifies into a priority level and
        # fair-queues per flow (queue overflow/timeout answers 429 with a
        # drain-rate Retry-After); the legacy path keeps the instant
        # try-acquire shed. Watches are long-running and exempt, like the
        # reference's longRunningRequestCheck; so are health probes —
        # a liveness check that 429s under load would turn an overload
        # into a restart storm
        ticket = None
        sem = None
        if not is_watch and not exempt:
            c = self._classify(h, method, req)
            if self._flow is not None:
                try:
                    ticket = self._flow.admit(
                        c, "read" if method == "GET" else "write")
                except flowcontrol.Rejected as rej:
                    self._error(
                        h, 429, "TooManyRequests",
                        f"too many requests ({rej.reason}), "
                        "please try again later",
                        headers={"Retry-After": str(rej.retry_after)})
                    # shed requests are exactly the ones the request
                    # counter exists to make visible during an overload
                    self.request_metrics.requests.inc(
                        verb=method,
                        resource=req.resource if req is not None else "",
                        code="429", priority_level=c.level)
                    return
            else:
                sem = self._read_sem if method == "GET" \
                    else self._write_sem
                if sem is not None and not sem.acquire(blocking=False):
                    pool = self._read_pool if method == "GET" \
                        else self._write_pool
                    ra = self._legacy_drain.retry_after(1, pool)
                    self._error(
                        h, 429, "TooManyRequests",
                        "too many requests, please try again later",
                        headers={"Retry-After": str(ra)})
                    self.request_metrics.requests.inc(
                        verb=method,
                        resource=req.resource if req is not None else "",
                        code="429", priority_level=c.level)
                    return
            if self._request_timeout:
                try:
                    h.connection.settimeout(self._request_timeout)
                except Exception:
                    pass
        t0 = perf_counter()
        try:
            self._dispatch_inner(h, method, url, query, req)
        finally:
            if ticket is not None:
                self._flow.release(ticket)
            if sem is not None:
                sem.release()
            if not is_watch and self._flow is None:
                # completion stamp feeding the legacy Retry-After math
                self._legacy_drain.note_dispatch()
            if req is not None and req.resource == "namespaces" and \
                    method != "GET":
                # the flow-key cache must re-read a re-labeled namespace
                self._tenant_cache.pop(req.name, None)
            # request accounting (ref: apiserver_request_total): resource
            # from the parsed request-info when routing got that far, the
            # code the response actually carried; watch streams skip the
            # duration histogram (their wall time is stream lifetime)
            am = self.request_metrics
            ctx = getattr(h, "_audit_ctx", None)
            am.requests.inc(
                verb=method,
                resource=ctx[1].resource if ctx is not None else "",
                code=str(getattr(h, "_audit_code", 0)))
            if not is_watch:
                # resource label so overload benches can separate
                # scheduler binds from tenant storm traffic
                am.request_duration.observe(
                    perf_counter() - t0, verb=method,
                    resource=ctx[1].resource if ctx is not None else "")
            self._finish_audit(h)

    def _classify(self, h, method: str,
                  req: Optional[_Request]) -> flowcontrol.FlowClassification:
        """Flow-schema classification for one request (pure given the
        peeked identity + parsed request-info; also labels legacy-path
        429s, so APF-off keeps the same priority-level attribution)."""
        user = self._peek_user(h)
        if req is None:
            return flowcontrol.classify(
                flowcontrol.request_verb(method, False), "", "", "",
                user=user, headers=h.headers)
        return flowcontrol.classify(
            flowcontrol.request_verb(method, bool(req.name)),
            req.resource, req.subresource, req.namespace, user=user,
            headers=h.headers, tenant_of=self._tenant_of)

    def _peek_user(self, h):
        """Best-effort identity peek for classification — same cert-then-
        bearer order as _authorized, but never writes an error (the real
        authn/authz gate still runs downstream)."""
        if self.authenticator is None:
            return None
        user = None
        peer_auth = getattr(self.authenticator, "authenticate_cert", None)
        if peer_auth is not None and self._tls:
            try:
                der = h.connection.getpeercert(binary_form=True)
            except Exception:
                der = None
            if der:
                user = peer_auth(der)
        if user is None:
            try:
                user = self.authenticator.authenticate(
                    h.headers.get("Authorization", ""))
            except Exception:
                user = None
        return user

    def _tenant_of(self, namespace: str) -> str:
        """Namespace -> serving.ktpu/tenant label (the flow key: one
        tenant's burst must not ride another tenant's queues). Cached;
        misses on a missing namespace are NOT cached so a namespace
        created later resolves correctly."""
        try:
            return self._tenant_cache[namespace]
        except KeyError:
            pass
        from ..tenancy import TENANT_LABEL
        try:
            ns = self.client.namespaces().get(namespace)
            tenant = (ns.metadata.labels or {}).get(TENANT_LABEL, "")
            self._flow_swallowed.ok("tenant_lookup")
        except NotFoundError:
            return ""  # namespace not created yet: expected, not cached
        except Exception as e:
            # flow key degrades to the namespace itself; counted so a
            # systematically failing lookup is visible, not silent
            self._flow_swallowed.swallow("tenant_lookup", e)
            return ""
        self._tenant_cache[namespace] = tenant
        return tenant

    def _finish_audit(self, h) -> None:
        # the ResponseComplete audit line fires after EVERY outcome,
        # including the error mappings (which set _audit_code)
        ctx = getattr(h, "_audit_ctx", None)
        if ctx is not None:
            # consume the ctx: keep-alive reuses this handler for the
            # next request, which must not replay this line
            h._audit_ctx = None
            self._audit(h, *ctx)

    def _dispatch_inner(self, h: BaseHTTPRequestHandler, method: str,
                        url, query: dict,
                        req: Optional[_Request]) -> None:
        try:
            if url.path in ("/healthz", "/livez"):
                # liveness: the process is up and serving
                self._respond_raw(h, 200, b"ok", "text/plain")
                return
            if url.path == "/readyz":
                # readiness reflects registered component contributors
                # (utils/healthz: scheduler informer sync/staleness,
                # queue progress, controller loops) — not just server-up
                failed = self.health.failed()
                if failed:
                    self._respond_raw(
                        h, 500,
                        ("unhealthy: " + ",".join(failed)).encode(),
                        "text/plain")
                else:
                    self._respond_raw(h, 200, b"ok", "text/plain")
                return
            if url.path == "/metrics":
                if self._observability_authorized(h):
                    self._handle_metrics(h, method)
                return
            if url.path == "/debug/traces":
                if self._observability_authorized(h):
                    self._handle_debug_traces(h, query)
                return
            if url.path == "/debug/pending":
                if self._observability_authorized(h):
                    self._handle_debug_pending(h)
                return
            if url.path == "/debug/flows":
                if self._observability_authorized(h):
                    self._handle_debug_flows(h)
                return
            if req is None:
                if self._try_aggregate(h, method, url.path, url.query):
                    return
                self._error(h, 404, "NotFound", f"unknown path {url.path}")
                return
            cls = self.scheme.type_for_resource(req.resource)
            if cls is None:
                # aggregation (ref: kube-aggregator proxyHandler): a
                # group/version the main server does not serve locally
                # may be claimed by a stored APIService — Local types
                # always win (checked above), exactly the reference's
                # precedence
                if self._try_aggregate(h, method, url.path, url.query):
                    return
                self._error(h, 404, "NotFound",
                            f"unknown resource {req.resource}")
                return
            ok, user = self._authorized(h, method, req)
            h._audit_ctx = (method, req, user)
            if not ok:
                return  # 401/403 already written
            self._handle(h, method, req, cls, user)
        except ExpiredError as e:
            # 410 Gone: the reflector must relist (reflector.go:159)
            self._error(h, 410, "Expired", str(e))
        except (NotFoundError, KeyError) as e:
            self._error(h, 404, "NotFound", str(e))
        except AlreadyExistsError as e:
            self._error(h, 409, "AlreadyExists", str(e))
        except ConflictError as e:
            self._error(h, 409, "Conflict", str(e))
        except QuotaExceeded as e:
            # the reference's quota denial is 403 Forbidden, not 422
            self._error(h, 403, "Forbidden", str(e))
        except TooManyDisruptions as e:
            # a PDB-refused eviction: 429 + Retry-After (eviction.go's
            # TooManyRequests with a 10s suggestion)
            self._error(h, 429, "TooManyRequests", str(e),
                        headers={"Retry-After": "10"})
        except (ValidationError, AdmissionDenied, ValueError) as e:
            self._error(h, 422, "Invalid", str(e))
        except (BrokenPipeError, ConnectionResetError):
            pass
        except Exception as e:
            from ..state.replication import ReplicaNotPromoted
            if isinstance(e, ReplicaNotPromoted):
                # a standby serving a follower store: writes 503 until
                # promote() (the learner's not-the-leader answer)
                self._error(h, 503, "ServiceUnavailable", str(e),
                            headers={"Retry-After": "1"})
                return
            traceback.print_exc()
            try:
                self._error(h, 500, "InternalError", str(e))
            except Exception:
                pass

    # ------------------------------------------------- observability routes

    def _observability_authorized(self, h) -> bool:
        """On a SECURED hub (authenticator configured), /metrics and the
        /debug endpoints require an authenticated caller — the reference
        serves them behind the full handler chain, and DELETE /metrics
        is a mutation no anonymous client may reach; pod names and span
        attributes are cluster-internal detail. Only /healthz-class
        liveness stays open. An open hub (no authenticator) keeps the
        insecure-port shape. Writes the 401 on failure."""
        if self.authenticator is None:
            return True
        user = None
        peer_auth = getattr(self.authenticator, "authenticate_cert", None)
        if peer_auth is not None and self._tls:
            try:
                der = h.connection.getpeercert(binary_form=True)
            except Exception:
                der = None
            if der:
                user = peer_auth(der)
        if user is None:
            user = self.authenticator.authenticate(
                h.headers.get("Authorization", ""))
        if user is None or "system:unauthenticated" in \
                tuple(getattr(user, "groups", ()) or ()):
            # bad credentials AND the no-credentials ANONYMOUS identity:
            # the main API path lets the authorizer judge anonymous, but
            # these endpoints have no resource to authorize against —
            # authenticated-only is the gate
            self._error(h, 401, "Unauthorized", "invalid credentials")
            return False
        return True

    def _handle_metrics(self, h, method: str) -> None:
        """GET /metrics — the aggregated text exposition; DELETE resets
        values across every attached registry (ref: the scheduler's
        DELETE /metrics -> metrics.Reset, server.go:287-291)."""
        if method == "GET":
            self._respond_raw(h, 200, self.metrics.expose().encode(),
                              "text/plain; version=0.0.4")
        elif method == "DELETE":
            self.metrics.reset()
            self._respond_raw(h, 200, b"metrics reset", "text/plain")
        else:
            self._error(h, 405, "MethodNotAllowed", method)

    def _handle_debug_traces(self, h, query: dict) -> None:
        """GET /debug/traces[?component=&trace=] — the flight recorder's
        JSONL export (oldest-evicted ring; per-component drop counts ride
        as X-Trace-Dropped so truncation is never silent)."""
        body = self.flight.export_jsonl(
            component=query.get("component") or None,
            trace_id=query.get("trace") or None).encode()
        dropped = sum(self.flight.dropped.values())
        self._respond_raw(h, 200, body, "application/jsonl",
                          headers={"X-Trace-Dropped": str(dropped)})

    def _handle_debug_pending(self, h) -> None:
        """GET /debug/pending — every registered component's pending-pod
        report (scheduler.debugger.pending_report): pod, last failure
        reason, attempts. The wire answer to 'why is my pod pending'."""
        reports = []
        for provider in list(self.pending_providers):
            try:
                reports.append(provider())
            except Exception as e:
                reports.append({"error": str(e)})
        body = json.dumps({"pending": reports}).encode()
        self._respond_raw(h, 200, body, "application/json")

    def _handle_debug_flows(self, h) -> None:
        """GET /debug/flows — APF's live state: per-(priority level,
        verb class) seats, inflight, queue depths, and lifetime
        dispatch/queue/reject counters. APF off answers {"apf": false}
        so operators can tell 'disabled' from 'idle'."""
        if self._flow is None:
            state = {"apf": False}
        else:
            state = {"apf": True}
            state.update(self._flow.debug_state())
        body = json.dumps(state).encode()
        self._respond_raw(h, 200, body, "application/json")

    # ------------------------------------------------------------- handlers

    def _authorized(self, h, method: str, req: _Request):
        """authn then authz (ref: the chain's ordering — a bad token is 401
        before any authorization opinion; default deny once enabled).
        Returns (ok, user); user is None in open-hub mode."""
        h._impersonator = ""  # reset: keep-alive reuses the handler
        if self.authenticator is None:
            return True, None
        from .auth import request_verb
        user = None
        peer_auth = getattr(self.authenticator, "authenticate_cert", None)
        if peer_auth is not None and self._tls:
            try:
                der = h.connection.getpeercert(binary_form=True)
            except Exception:
                der = None
            if der:
                user = peer_auth(der)
        if user is None:
            user = self.authenticator.authenticate(
                h.headers.get("Authorization", ""))
        if user is None:
            self._error(h, 401, "Unauthorized", "invalid credentials")
            return False, None
        impersonate = h.headers.get("Impersonate-User", "")
        if not impersonate and h.headers.get("Impersonate-Group"):
            # group-without-user impersonation is an error, not a no-op:
            # silently proceeding as the REAL user would hand a caller
            # that believes it dropped privileges its full power (ref:
            # filters/impersonation.go rejects this shape)
            self._error(h, 400, "BadRequest",
                        "Impersonate-Group requires Impersonate-User")
            return False, user
        if impersonate:
            # ref: apiserver/pkg/endpoints/filters/impersonation.go — the
            # REAL user needs the "impersonate" verb on users (and on
            # groups for each requested group); the request then proceeds
            # AS the impersonated identity, with the original actor in
            # the audit line
            groups = [v.strip() for k, vs in h.headers.items()
                      for v in [vs] if k.lower() == "impersonate-group"]
            if not self._check_authz(h, user, "impersonate", "users",
                                     "", name=impersonate):
                return False, user
            for g in groups:
                if not self._check_authz(h, user, "impersonate", "groups",
                                         "", name=g):
                    return False, user
            h._impersonator = user.name  # audit: who really acted
            from .auth import UserInfo
            user = UserInfo(impersonate,
                            tuple(groups) + ("system:authenticated",))
        if self.authorizer is not None:
            verb = request_verb(method, req.query.get("watch") in
                                ("true", "1"), bool(req.name))
            # subresources authorize as resource/subresource (the RBAC
            # model: pods/binding and pods/status are distinct privileges)
            resource = req.resource
            if req.subresource:
                resource = f"{req.resource}/{req.subresource}"
            elif req.resource == "bindings":
                # the bindings collection IS the bind privilege (single or
                # bulk) — authorizing it as a plain "bindings" create would
                # let a role without pods/binding bind pods
                resource = "pods/binding"
            if not self._check_authz(h, user, verb, resource, req.namespace,
                                     name=req.name):
                return False, user
        return True, user

    def _check_authz(self, h, user, verb: str, resource: str,
                     namespace: str, name: str = "") -> bool:
        if self.authorizer is None or user is None:
            return True
        if not self.authorizer.authorize(user, verb, resource, namespace,
                                         name):
            self._error(
                h, 403, "Forbidden",
                f'user "{user.name}" cannot {verb} {resource}'
                + (f' in namespace "{namespace}"' if namespace else ""))
            return False
        return True

    def _stamp_namespace(self, req: _Request, obj) -> None:
        """The URL's namespace is authoritative on every write verb (ref:
        the apiserver rejects URL/body disagreement): a body naming another
        namespace than the one the request was authorized and
        lifecycle-checked under must not win. Raises ValueError, which
        _dispatch_inner answers with the 422."""
        if req.namespace and hasattr(obj, "metadata"):
            if obj.metadata.namespace and \
                    obj.metadata.namespace != req.namespace:
                raise ValueError(
                    f"the namespace of the object "
                    f"({obj.metadata.namespace}) does not match the "
                    f"namespace on the request ({req.namespace})")
            obj.metadata.namespace = req.namespace

    def _rc(self, cls, namespace: str):
        return self.client.resource(cls, namespace or None)

    def _read_body(self, h) -> Any:
        length = int(h.headers.get("Content-Length", 0))
        if not length:
            return None
        raw = h.rfile.read(length)
        # negotiated binary bodies carry the SAME wire dicts as JSON
        # (binenc packs what serde emits), so every downstream branch —
        # BindList, bulk create, Binding decode — is encoding-blind
        if h.headers.get("Content-Type", "").startswith(
                binenc.CONTENT_TYPE):
            self.request_metrics.wire_bytes_received.inc(
                length, encoding="binary")
            return binenc.unpack(raw)
        self.request_metrics.wire_bytes_received.inc(
            length, encoding="json")
        return json.loads(raw)

    #: resources serving the /scale subresource (ref: the ScaleREST
    #: registrations in pkg/registry/{apps,core}/.../storage.go)
    SCALABLE = ("deployments", "replicasets", "replicationcontrollers",
                "statefulsets")

    def _handle_scale(self, h, method: str, req: _Request, rc) -> None:
        if req.resource not in self.SCALABLE:
            self._error(h, 404, "NotFound",
                        f"resource {req.resource} has no scale subresource")
            return
        from ..api.autoscaling import project_scale
        if method == "GET":
            obj = rc.get(req.name, namespace=req.namespace or None)
            self._respond(h, 200, project_scale(obj))
        elif method == "PUT":
            from ..api.autoscaling import Scale
            data = self._read_body(h)
            if data is None:
                self._error(h, 422, "Invalid", "empty request body")
                return
            scale = serde.decode(Scale, data)
            if scale.spec.replicas < 0:
                raise ValueError("scale.spec.replicas must be >= 0")
            expect_rv = scale.metadata.resource_version

            def mutate(cur):
                if expect_rv and \
                        cur.metadata.resource_version != expect_rv:
                    raise ConflictError(
                        f"{req.resource} {req.name}: the object has been "
                        f"modified")
                cur.spec.replicas = scale.spec.replicas
                return cur
            out = rc.patch(req.name, mutate,
                           namespace=req.namespace or None)
            self._respond(h, 200, project_scale(out))
        else:
            self._error(h, 405, "MethodNotAllowed", method)

    def current_user(self):
        """The request's authenticated user (None on an open hub)."""
        return getattr(self._req_local, "user", None)

    def _handle(self, h, method: str, req: _Request, cls, user=None) -> None:
        self._req_local.user = user
        if method != "GET" and self.store.read_only:
            # a standby over a follower store refuses writes BEFORE
            # admission — the guard, not an admission side effect, must
            # be the answer (503 like a learner's not-the-leader)
            self._error(h, 503, "ServiceUnavailable",
                        "replica is read-only until promote()",
                        headers={"Retry-After": "1"})
            return
        if req.resource == "nodes" and req.subresource == "proxy" and \
                method != "GET":
            # the proxy subresource is GET-only here; falling through
            # would let a nodes/proxy-scoped credential write the Node
            self._error(h, 405, "MethodNotAllowed",
                        "the node proxy supports only GET")
            return
        rc = self._rc(cls, req.namespace)
        if req.subresource == "scale":
            self._handle_scale(h, method, req, rc)
            return
        if method == "GET":
            if req.resource == "nodes" and req.subresource == "proxy":
                self._proxy_to_kubelet(h, req)
                return
            if req.resource == "pods" and req.subresource == "attach":
                # kubectl attach transport (ref: AttachREST + getAttach)
                self._handle_pod_attach(h, req)
                return
            if req.name:
                obj = rc.get(req.name, namespace=req.namespace or None)
                self._respond(h, 200, obj)
            elif req.query.get("watch") in ("true", "1"):
                self._serve_watch(h, req)
            else:
                items, rv = self.store.list(
                    req.resource, req.namespace or None)
                if self.binary_wire and \
                        req.query.get("binary") in ("true", "1"):
                    # negotiated binary collection: per-item packed
                    # bytes come from the rv-keyed object cache, shared
                    # with every binary watch frame of the same revision
                    t0 = perf_counter()
                    body = binenc.encode_list_body(items, rv)
                    self.request_metrics.wire_encode_seconds.observe(
                        perf_counter() - t0, encoding="binary")
                    self._respond_raw(h, 200, body, binenc.CONTENT_TYPE)
                    return
                # assemble from per-object cached JSON: the store's frozen
                # objects encode once per revision (serde.to_json_cached),
                # so a 20k-item list is a join, not 20k re-encodes
                t0 = perf_counter()
                body = (
                    b'{"apiVersion": "v1", "kind": "List", "metadata": '
                    b'{"resourceVersion": "%d"}, "items": [' % rv
                    + ", ".join(serde.to_json_cached(o)
                                for o in items).encode()
                    + b"]}")
                self.request_metrics.wire_encode_seconds.observe(
                    perf_counter() - t0, encoding="json")
                self._respond_raw(h, 200, body, "application/json")
        elif method == "POST":
            data = self._read_body(h)
            if data is None:
                self._error(h, 422, "Invalid", "empty request body")
                return
            if req.resource == "pods" and req.subresource == "exec":
                # kubectl exec transport (ref: registry/core/pod/rest
                # ExecREST + kubelet server.go getExec): resolve the
                # pod's node, forward one exec round trip to its kubelet
                self._handle_pod_exec(h, req, data)
                return
            if req.resource == "pods" and req.subresource == "eviction":
                # the Eviction API: PDB-guarded delete (ref:
                # pkg/registry/core/pod/storage/eviction.go); a refused
                # eviction is 429 TooManyRequests, mapped in dispatch
                self.client.pods(req.namespace or None).evict(
                    req.name, namespace=req.namespace or "default")
                self._respond_raw(h, 200, json.dumps(
                    {"apiVersion": "v1", "kind": "Status",
                     "status": "Success"}).encode(), "application/json")
                return
            if req.resource == "bindings":
                # the scheduler's bulk bind: a List of Bindings lands as
                # ONE store transaction (PodClient.bind_bulk), the wire
                # analog of the in-process batch-bind path. A single
                # Binding body binds one pod. Authorization already ran as
                # create pods/binding (_authorized maps this resource).
                # "BindList" is the slim form: items are [name, nodeName]
                # pairs under the request namespace — same semantics, no
                # per-item object decode on the hot path.
                if data.get("kind") == "BindList":
                    ns = req.namespace or "default"
                    pairs = []
                    for it in data.get("items", []):
                        if not (isinstance(it, list) and len(it) == 2 and
                                isinstance(it[0], str) and
                                isinstance(it[1], str)):
                            self._error(h, 422, "Invalid",
                                        "BindList items must be "
                                        "[podName, nodeName] pairs")
                            return
                        pairs.append((it[0], it[1]))
                    # pair fast path: no Binding/ObjectMeta/ObjectReference
                    # construction per pod; shares the Status-list response
                    # below with the classic Binding-decode form
                    outs = self.client.pods(None).bind_bulk_pairs(ns, pairs)
                else:
                    items = data.get("items", [data]) \
                        if data.get("kind") == "List" else [data]
                    bindings = []
                    for d in items:
                        b = serde.decode(Binding, d)
                        if req.namespace:
                            if b.metadata.namespace and \
                                    b.metadata.namespace != req.namespace:
                                self._error(
                                    h, 422, "Invalid",
                                    f"binding namespace "
                                    f"({b.metadata.namespace}) does not "
                                    f"match the request ({req.namespace})")
                                return
                            b.metadata.namespace = req.namespace
                        bindings.append(b)
                    outs = self.client.pods(req.namespace or None) \
                        .bind_bulk(bindings)
                self.request_metrics.pods_bound.inc(sum(
                    1 for o in outs if not isinstance(o, Exception)))
                # slim per-slot results — the reference's bind returns
                # metav1.Status, never the pod; echoing N full pods would
                # cost an encode+decode per bind on the hot path
                body = {"apiVersion": "v1", "kind": "List", "items": [
                    {"kind": "Status", "status": "Success"}
                    if not isinstance(o, Exception) else
                    {"kind": "Status", "status": "Failure",
                     "reason": type(o).__name__, "message": str(o)}
                    for o in outs]}
                if self.binary_wire and \
                        req.query.get("binary") in ("true", "1"):
                    # the binary echo doubles as capability discovery: a
                    # client that asked and got a binary Content-Type
                    # back knows it may pack its NEXT BindList body
                    # (old hubs ignore the query and answer JSON)
                    self._respond_raw(h, 200, binenc.pack(body),
                                      binenc.CONTENT_TYPE)
                    return
                self._respond_raw(h, 200, json.dumps(body).encode(),
                                  "application/json")
                return
            if (req.resource == "pods" and req.subresource == "binding") or (
                    req.resource == "pods" and not req.name and
                    data and data.get("kind") == "Binding"):
                binding = serde.decode(Binding, data)
                if req.name and binding.metadata.name and \
                        binding.metadata.name != req.name:
                    # the URL's name is as authoritative as its namespace:
                    # a stale body must not silently bind a different pod
                    self._error(h, 422, "Invalid",
                                f"the name of the object "
                                f"({binding.metadata.name}) does not match "
                                f"the name on the request ({req.name})")
                    return
                if not req.subresource:
                    # a Binding posted to the bare pods collection is still
                    # the bind privilege: authorize as pods/binding, not
                    # pods create (RBAC treats them as distinct)
                    if not self._check_authz(h, user, "create",
                                             "pods/binding", req.namespace):
                        return
                self._stamp_namespace(req, binding)
                out = self.client.pods(req.namespace or None).bind(binding)
                self.request_metrics.pods_bound.inc()
                self._respond(h, 201, out)
                return
            if data.get("kind") == "List" and \
                    req.resource != "customresourcedefinitions":
                # bulk create: a List posted to the collection creates all
                # items in ONE store transaction (create_bulk) — the
                # write-side analog of the bulk bindings path; per-request
                # HTTP/serde overhead stops dominating mass loads
                self._handle_bulk_create(h, req, cls, data, user)
                return
            with self._create_gate:
                out = self._create_one(req, rc, cls, data, user)
            self._respond(h, 201, out)
        elif method == "PUT":
            data = self._read_body(h)
            if data is None:
                self._error(h, 422, "Invalid", "empty request body")
                return
            obj = serde.decode(cls, data)
            if req.name and getattr(obj.metadata, "name", "") and \
                    obj.metadata.name != req.name:
                self._error(h, 422, "Invalid",
                            f"the name of the object ({obj.metadata.name}) "
                            f"does not match the name on the request "
                            f"({req.name})")
                return
            self._stamp_namespace(req, obj)
            if req.subresource == "status":
                out = rc.update_status(obj)
            else:
                obj = self.admission.admit("UPDATE", req.resource, obj)
                if req.resource == "customresourcedefinitions":
                    out = self._update_crd(rc, obj)
                else:
                    out = rc.update(obj)
            self._respond(h, 200, out)
        elif method == "PATCH":
            data = self._read_body(h)
            if data is None:
                self._error(h, 422, "Invalid", "empty request body")
                return
            if not req.name:
                self._error(h, 405, "MethodNotAllowed",
                            "PATCH requires a resource name")
                return
            ctype = h.headers.get("Content-Type",
                                  "application/strategic-merge-patch+json")
            out = self._apply_patch(req, rc, cls, ctype, data)
            self._respond(h, 200, out)
        elif method == "DELETE":
            if req.resource == "namespaces" and req.name in (
                    "default", "kube-system", "kube-node-lease",
                    "kube-public"):
                # the immortal namespaces (ref: the lifecycle plugin's
                # immortalNamespaces set): deleting one would terminate it
                # forever — bootstrap can't resurrect a Terminating object
                self._error(h, 403, "Forbidden",
                            f'namespace "{req.name}" cannot be deleted')
                return
            out = rc.delete(req.name, namespace=req.namespace or None,
                            resource_version=req.query.get("resourceVersion"))
            if req.resource == "customresourcedefinitions":
                # cascade only AFTER the delete committed — a stale-rv
                # rejection above must not have destroyed the instances
                # (WAL replay handles instance tombstones appearing after
                # the CRD's DELETE record by raw metadata removal)
                from ..runtime.crd import unregister_crd
                self._delete_cr_instances(out)
                unregister_crd(out, self.scheme)
            self._respond(h, 200, out)
        else:
            self._error(h, 405, "MethodNotAllowed", method)

    def _create_one(self, req: _Request, rc, cls, data, user):
        """The single-object create between body and response: decode,
        admit, validate, commit. Runs inside the create gate, so it
        writes nothing to the socket: every refusal is raised and
        _dispatch_inner answers it once the gate is left."""
        obj = self.scheme.decode_any(data) if "kind" in data \
            else serde.decode(cls, data)
        self._stamp_namespace(req, obj)
        if not isinstance(obj, cls):
            # a body of the wrong kind must not land in this resource's
            # bucket (it would poison every watcher of the resource)
            raise ValueError(
                f"body kind {data.get('kind')} does not match "
                f"resource {req.resource}")
        if req.resource == "certificatesigningrequests":
            # the requester identity is SERVER-stamped from the
            # authenticated user; client-supplied values are discarded
            # UNCONDITIONALLY (ref: pkg/registry/certificates
            # PrepareForCreate) — the CSR approver's policy keys off
            # these fields, so an open hub must clear them rather than
            # let a client forge a node identity into auto-approval
            obj.spec.username = user.name if user is not None else ""
            obj.spec.groups = list(user.groups) \
                if user is not None else []
        obj = self.admission.admit("CREATE", req.resource, obj)
        try:
            if req.resource == "customresourcedefinitions":
                # pre-validate WITHOUT registering: a create that fails
                # after registration would leave a phantom served type
                from ..runtime.crd import validate_crd
                validate_crd(obj, self.scheme)
            out = rc.create(obj)
        except Exception:
            # admission already charged quota for this object; a
            # failed create must hand the charge back or the
            # namespace stays falsely throttled until the quota
            # controller's resync
            self._quota.refund_last()
            raise
        if req.resource == "customresourcedefinitions":
            from ..runtime.crd import register_crd
            register_crd(out, self.scheme)
        elif req.resource == "namespaces":
            self._ensure_default_sa(out.metadata.name)
        return out

    def _handle_bulk_create(self, h, req: _Request, cls, data,
                            user=None) -> None:
        """POST of a List to a collection: decode + admit each item, then
        commit every admitted item through ONE store transaction. A bad
        item fails only its slot (mirrors create_bulk / the bulk bindings
        endpoint); a slot whose create fails after admission refunds its
        own quota charge. Responds with a List of slim per-slot Status."""
        with self._create_gate:
            results = self._create_many(req, cls, data, user)
        body = {"apiVersion": "v1", "kind": "List", "items": [
            {"kind": "Status", "status": "Failure",
             "reason": type(r).__name__, "message": str(r)}
            if isinstance(r, Exception) else
            {"kind": "Status", "status": "Success",
             "metadata": {"name": r.metadata.name,
                          "resourceVersion": r.metadata.resource_version}}
            for r in results]}
        self._respond_raw(h, 200, json.dumps(body).encode(),
                          "application/json")

    def _create_many(self, req: _Request, cls, data, user) -> List[Any]:
        """_handle_bulk_create between body and response, inside the
        create gate: per-slot stored objects or the Exception that
        refused the slot."""
        rc = self._rc(cls, req.namespace)
        objs: List[Any] = []
        slots: List[Any] = []  # int index into objs, or Exception
        charges: List[Any] = []
        new_namespaces: List[str] = []
        for d in data.get("items", []):
            try:
                obj = self.scheme.decode_any(d) if "kind" in d \
                    else serde.decode(cls, d)
                if not isinstance(obj, cls):
                    raise ValueError(
                        f"item kind {d.get('kind')} does not match "
                        f"resource {req.resource}")
                if req.namespace and hasattr(obj, "metadata"):
                    if obj.metadata.namespace and \
                            obj.metadata.namespace != req.namespace:
                        raise ValueError(
                            f"item namespace ({obj.metadata.namespace}) "
                            f"does not match the request ({req.namespace})")
                    obj.metadata.namespace = req.namespace
                if req.resource == "certificatesigningrequests":
                    # same server-side stamp as the single-create path
                    obj.spec.username = user.name if user is not None else ""
                    obj.spec.groups = list(user.groups) \
                        if user is not None else []
                obj = self.admission.admit("CREATE", req.resource, obj)
                rec = self._quota.take_last()
            except Exception as e:
                slots.append(e)
                continue
            slots.append(len(objs))
            objs.append(obj)
            charges.append(rec)
        outs = rc.create_bulk(objs)
        results = []
        for s in slots:
            if isinstance(s, Exception):
                results.append(s)
                continue
            out = outs[s]
            if isinstance(out, Exception):
                self._quota.refund_rec(charges[s])
            elif req.resource == "namespaces":
                new_namespaces.append(out.metadata.name)
            results.append(out)
        for name in new_namespaces:
            self._ensure_default_sa(name)
        return results

    def _try_aggregate(self, h, method: str, path: str,
                       rawquery: str) -> bool:
        """Route /apis/{group}/{version}/... claimed by a stored
        APIService to its backing server, relaying method, body, and
        response verbatim (ref: kube-aggregator pkg/apiserver
        proxyHandler.ServeHTTP). Returns False when no APIService claims
        the group/version (the caller 404s)."""
        parts = [p for p in path.split("/") if p]
        if len(parts) < 3 or parts[0] != "apis":
            return False
        group, version = parts[1], parts[2]
        from ..api.apiregistration import APIService
        try:
            svc = self.client.resource(APIService).get(f"{version}.{group}")
        except NotFoundError:
            return False
        base = svc.spec.service_url
        if not base:
            return False  # Local APIService: nothing to proxy to
        # the aggregator authenticates/authorizes BEFORE forwarding (ref:
        # the aggregator sitting behind the full handler chain); the
        # aggregated resource authorizes under its own plural, with the
        # namespaced path shape parsed like RequestInfoFactory
        rest = parts[3:]
        ns = ""
        if len(rest) >= 2 and rest[0] == "namespaces":
            ns, rest = rest[1], rest[2:]
        if "watch=true" in rawquery or "watch=1" in rawquery:
            # the buffering relay below cannot stream; refuse up front
            # instead of hanging the client for the full timeout
            self._error(h, 501, "NotImplemented",
                        "watch is not supported through the "
                        "aggregation proxy")
            return True
        agg_req = _Request(rest[0] if rest else group, ns,
                           rest[1] if len(rest) > 1 else "",
                           "", {}, tail=())
        ok, agg_user = self._authorized(h, method, agg_req)
        # aggregated traffic audits like local traffic — including the
        # denied/probing requests the audit trail exists to catch
        h._audit_ctx = (method, agg_req, agg_user)
        if not ok:
            return True  # 401/403 already written
        from urllib import error as urlerror
        from urllib import request as urlrequest
        target = base.rstrip("/") + path
        if rawquery:
            target += "?" + rawquery
        body = None
        n = int(h.headers.get("Content-Length", 0) or 0)
        if n:
            body = h.rfile.read(n)
        try:
            r = urlrequest.urlopen(urlrequest.Request(
                target, data=body, method=method,
                headers={"Content-Type": h.headers.get(
                    "Content-Type", "application/json")}), timeout=15)
            self._respond_raw(h, r.status, r.read(),
                              r.headers.get("Content-Type",
                                            "application/json"))
        except urlerror.HTTPError as e:
            self._respond_raw(h, e.code, e.read(),
                              e.headers.get("Content-Type", "text/plain"))
        except Exception as e:
            self._error(h, 503, "ServiceUnavailable",
                        f"aggregated API {version}.{group} unavailable: "
                        f"{e}")
        return True

    def _kubelet_target(self, node_name: str):
        """(ip, port) the node publishes for its kubelet server, or
        (None, None) — shared by the proxy and exec/attach routes."""
        node = self.client.nodes().get(node_name)
        port = ((node.status.daemon_endpoints or {})
                .get("kubeletEndpoint") or {}).get("Port")
        ip = next((a.get("address") for a in node.status.addresses
                   if a.get("type") == "InternalIP"), None)
        return ip, port

    def _resolve_pod_kubelet(self, h, req: _Request):
        """(pod, kubelet base url) for a streaming subresource, or None
        after writing the error response."""
        pod = self.client.pods(req.namespace or "default").get(
            req.name, namespace=req.namespace or "default")
        if not pod.spec.node_name:
            self._error(h, 409, "Conflict",
                        f"pod {req.name} is not bound to a node")
            return None
        ip, port = self._kubelet_target(pod.spec.node_name)
        if not port or not ip:
            self._error(h, 503, "ServiceUnavailable",
                        f"node {pod.spec.node_name} publishes no "
                        f"kubelet endpoint")
            return None
        return pod, f"http://{ip}:{port}"

    def _handle_pod_exec(self, h, req: _Request, data) -> None:
        """POST pods/{name}/exec {"container"?, "command": [...],
        "stdin"?: b64} -> the kubelet's {"exitCode", "output"} verbatim."""
        from urllib import error as urlerror
        from urllib import request as urlrequest
        resolved = self._resolve_pod_kubelet(h, req)
        if resolved is None:
            return
        pod, base = resolved
        container = data.get("container") or (
            pod.spec.containers[0].name if pod.spec.containers else "")
        ns = pod.metadata.namespace or "default"
        target = f"{base}/exec/{ns}/{pod.metadata.name}/{container}"
        body = json.dumps({"command": data.get("command", []),
                           "stdin": data.get("stdin", "")}).encode()
        try:
            r = urlrequest.urlopen(urlrequest.Request(
                target, data=body,
                headers={"Content-Type": "application/json"},
                method="POST"), timeout=10)
            self._respond_raw(h, 200, r.read(), "application/json")
        except urlerror.HTTPError as e:
            self._respond_raw(h, e.code, e.read(),
                              e.headers.get("Content-Type", "text/plain"))
        except Exception as e:
            self._error(h, 502, "BadGateway",
                        f"exec to {pod.spec.node_name} failed: {e}")

    def _handle_pod_attach(self, h, req: _Request) -> None:
        """GET pods/{name}/attach?container= -> the kubelet's current
        output stream for the container."""
        from urllib import error as urlerror
        from urllib import request as urlrequest
        resolved = self._resolve_pod_kubelet(h, req)
        if resolved is None:
            return
        pod, base = resolved
        container = req.query.get("container") or (
            pod.spec.containers[0].name if pod.spec.containers else "")
        ns = pod.metadata.namespace or "default"
        target = f"{base}/attach/{ns}/{pod.metadata.name}/{container}"
        try:
            with urlrequest.urlopen(target, timeout=10) as r:
                self._respond_raw(h, 200, r.read(), "text/plain")
        except urlerror.HTTPError as e:
            self._respond_raw(h, e.code, e.read(),
                              e.headers.get("Content-Type", "text/plain"))
        except Exception as e:
            self._error(h, 502, "BadGateway",
                        f"attach to {pod.spec.node_name} failed: {e}")

    def _proxy_to_kubelet(self, h, req: _Request) -> None:
        """GET /api/v1/nodes/{name}/proxy/<path> — the apiserver->kubelet
        proxy (ref: pkg/registry/core/node/rest ProxyREST), the transport
        kubectl logs rides. The kubelet address comes from the node's
        status (InternalIP + daemonEndpoints.kubeletEndpoint.Port)."""
        from urllib import request as urlrequest
        ip, port = self._kubelet_target(req.name)
        if not port or not ip:
            self._error(h, 503, "ServiceUnavailable",
                        f"node {req.name} publishes no kubelet endpoint")
            return
        target = f"http://{ip}:{port}/" + "/".join(req.tail)
        from urllib import error as urlerror
        try:
            # short timeout: this handler occupies a read-inflight slot,
            # so dead kubelets must not pin it for long
            with urlrequest.urlopen(target, timeout=3) as r:
                body = r.read()
                ctype = r.headers.get("Content-Type", "text/plain")
        except urlerror.HTTPError as e:
            # relay the kubelet's own status + body (the reference's
            # ProxyREST forwards upstream errors verbatim)
            self._respond_raw(h, e.code, e.read(),
                              e.headers.get("Content-Type", "text/plain"))
            return
        except Exception as e:
            self._error(h, 502, "BadGateway",
                        f"kubelet proxy to {req.name} failed: {e}")
            return
        self._respond_raw(h, 200, body, ctype)

    def _apply_patch(self, req: _Request, rc, cls, ctype: str, data):
        """The PATCH verb (ref: apiserver/pkg/endpoints/handlers/patch.go:45
        — patcher.patchResource). Dispatches on content type:
        json-patch (RFC 6902 op list), merge-patch (RFC 7386), or
        strategic-merge (merge + named-list merging). Applied inside a CAS
        retry loop against the live object; a metadata.resourceVersion in
        the patch body (or ?resourceVersion=) is an optimistic-concurrency
        precondition like the reference's."""
        from ..api.patch import (JSONPatchError, json_merge_patch,
                                 json_patch, strategic_merge)
        ctype = ctype.split(";")[0].strip()
        expect_rv = req.query.get("resourceVersion")
        if isinstance(data, dict):
            expect_rv = (data.get("metadata") or {}) \
                .get("resourceVersion") or expect_rv

        for _ in range(16):
            cur = rc.get(req.name, namespace=req.namespace or None)
            if expect_rv and cur.metadata.resource_version != str(expect_rv):
                raise ConflictError(
                    f"{req.resource} {req.name}: the object has been "
                    f"modified (rv {cur.metadata.resource_version} != "
                    f"{expect_rv})")
            enc = json.loads(serde.to_json_str(cur))
            if ctype == "application/json-patch+json":
                if not isinstance(data, list):
                    raise ValueError("json-patch body must be an op list")
                merged = json_patch(enc, data)
            elif ctype == "application/merge-patch+json":
                merged = json_merge_patch(enc, data)
            else:  # strategic-merge (the kubectl default)
                merged = strategic_merge(enc, data)
            obj = serde.decode(cls, merged)
            if obj.metadata.name != req.name:
                raise ValueError(
                    "patch may not change the object's name")
            if req.namespace and obj.metadata.namespace != req.namespace:
                raise ValueError(
                    "patch may not change the object's namespace")
            # the patch applies to what we just read, whatever rv the
            # patch body carried
            obj.metadata.resource_version = cur.metadata.resource_version
            try:
                if req.subresource == "status":
                    return rc.update_status(obj)
                obj = self.admission.admit("UPDATE", req.resource, obj)
                return rc.update(obj)
            except ConflictError:
                if expect_rv:
                    raise
                continue  # unconditional patch: re-read and re-apply
        raise ConflictError(f"{req.resource} {req.name}: too many conflicts")

    def _serve_watch(self, h, req: _Request) -> None:
        """Chunked watch stream: one JSON frame per line (ref: the
        apiserver's WatchServer over the cacher; resumable by
        resourceVersion exactly like storage/cacher/cacher.go)."""
        rv = req.query.get("resourceVersion")
        # negotiated compact framing (the protobuf-negotiation analog):
        # a client that opted in receives bind MODIFIED events as slim
        # {"slim":"bind", ...} frames it applies to its cached copy —
        # no full-object encode here, no full decode there
        slim_ok = req.query.get("slimBind") in ("true", "1")
        # negotiated watch bookmarks (ref: allowWatchBookmarks): opted-in
        # clients receive the heartbeat as a BOOKMARK frame carrying the
        # store's CURRENT resourceVersion, so an idle consumer's resume
        # point keeps pace with other resources' churn instead of aging
        # out of the bounded history window (the 410-relist after a quiet
        # period). Non-negotiating clients keep the bare-line heartbeat.
        bookmarks_ok = req.query.get("allowWatchBookmarks") in ("true", "1")
        # negotiated binary framing: length-prefixed packed frames
        # (binenc) instead of JSON lines. The server ECHOES the opt-in
        # via Content-Type, so a client talking to an old hub sees
        # application/json back and keeps its line pump — the same
        # silent-fallback contract slim binds use.
        binary_ok = self.binary_wire and \
            req.query.get("binary") in ("true", "1")
        encoding = "binary" if binary_ok else "json"
        watch = self.store.watch(req.resource, req.namespace or None,
                                 int(rv) if rv else None)
        h._audit_code = 200
        self.request_metrics.watch_streams.inc(resource=req.resource)
        h.send_response(200)
        h.send_header("Content-Type",
                      binenc.CONTENT_TYPE_WATCH if binary_ok
                      else "application/json;stream=watch")
        h.send_header("Transfer-Encoding", "chunked")
        h.end_headers()

        def write_chunk(payload: bytes) -> None:
            h.wfile.write(f"{len(payload):X}\r\n".encode())
            h.wfile.write(payload + b"\r\n")
            h.wfile.flush()

        import queue as queue_mod
        try:
            while True:
                # bookmark rv snapshot BEFORE the blocking get: the store
                # assigns rv and enqueues the event in one locked section,
                # so every event with rv <= this snapshot is already in
                # the queue — an Empty after the wait proves the client
                # has (been sent) all of them and the snapshot is a safe
                # resume point. Reading the rv AFTER the timeout could
                # advertise an rv whose event is still queued here; a
                # resume at that rv would skip the event forever.
                bm_rv = self.store.resource_version if bookmarks_ok else 0
                try:
                    ev = watch.events.get(timeout=1.0)
                except queue_mod.Empty:
                    # heartbeat: keeps the client's blocking read turning
                    # over so a stopped client can notice and close from
                    # its OWN thread — closing an http response
                    # cross-thread deadlocks. Bookmark-negotiated streams
                    # ride the pre-wait rv snapshot on it. Binary streams
                    # need a real (empty-body) frame — an empty chunk is
                    # the chunked-encoding terminator, not a keep-alive.
                    if binary_ok:
                        write_chunk(binenc.bookmark_frame(bm_rv)
                                    if bookmarks_ok
                                    else binenc.HEARTBEAT_FRAME)
                    elif bookmarks_ok:
                        write_chunk(
                            json.dumps({"type": BOOKMARK, "rv": bm_rv})
                            .encode() + b"\n")
                    else:
                        write_chunk(b"\n")
                    continue
                if ev is None:
                    break
                # coalesce everything already queued into ONE chunk: a
                # bulk bind lands thousands of events at once, and one
                # write per event is a syscall + chunk-header per event
                # on both sides of the wire
                batch = [ev]
                closing = False
                while len(batch) < 2048:
                    try:
                        nxt = watch.events.get_nowait()
                    except queue_mod.Empty:
                        break
                    if nxt is None:
                        closing = True
                        break
                    batch.append(nxt)
                # per-object cached JSON: one encode per revision shared
                # across every watcher/list/journal of that revision;
                # negotiated slim frames skip even that. Consecutive slim
                # bind events COALESCE into one {"slim": "binds"} frame —
                # a bulk bind lands thousands of MODIFIED events in this
                # batch, and one json.dumps per event was the hub's
                # largest remaining watch cost (the client splits the
                # frame back into per-pod events)
                parts = []
                slim_run: list = []
                cache_hits = 0
                t0 = perf_counter()

                def flush_slim():
                    if not slim_run:
                        return
                    if binary_ok:
                        # FT_BINDS: the coalesced run as one packed
                        # array (slim × binary compose — binary framing
                        # of the slim payload, not a third protocol)
                        parts.append(binenc.binds_frame(slim_run))
                    elif len(slim_run) == 1:
                        parts.append(
                            f'{{"type": "MODIFIED", "slim": "bind", '
                            f'"o": {json.dumps(slim_run[0])}}}\n'.encode())
                    else:
                        parts.append(
                            ('{"type": "MODIFIED", "slim": "binds", "o": '
                             + json.dumps({"items": slim_run})
                             + "}\n").encode())
                    slim_run.clear()
                for e in batch:
                    if slim_ok and e.slim is not None and \
                            e.type == MODIFIED:
                        d = dict(e.slim)
                        d["rv"] = e.resource_version
                        slim_run.append(d)
                    else:
                        flush_slim()
                        # full-object frames ride the per-(event,
                        # encoding) byte cache: the store publishes ONE
                        # WatchEvent object to every watcher queue, so
                        # the first stream to serialize a revision pays
                        # the encode and the rest ship its bytes
                        if binary_ok:
                            buf, hit = binenc.cached_watch_frame(
                                e, "binary",
                                lambda: binenc.event_frame(
                                    e.type, binenc.encode_obj(e.object)))
                        else:
                            buf, hit = binenc.cached_watch_frame(
                                e, "json",
                                lambda: (
                                    f'{{"type": "{e.type}", "object": '
                                    f"{serde.to_json_cached(e.object)}}}\n"
                                ).encode())
                        cache_hits += hit
                        parts.append(buf)
                flush_slim()
                payload = b"".join(parts)
                wm = self.request_metrics
                wm.wire_encode_seconds.observe(
                    perf_counter() - t0, encoding=encoding)
                if cache_hits:
                    wm.watch_frame_cache_hits.inc(
                        cache_hits, encoding=encoding)
                wm.wire_bytes_sent.inc(len(payload), encoding=encoding)
                wm.watch_events.inc(
                    len(batch), resource=req.resource)
                write_chunk(payload)
                if closing:
                    break
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass
        finally:
            self.request_metrics.watch_streams.dec(resource=req.resource)
            watch.stop()
            try:
                h.wfile.write(b"0\r\n\r\n")
            except Exception:
                pass

    # ------------------------------------------------------------ responses

    def _respond(self, h, code: int, obj: Any) -> None:
        self._respond_raw(h, code, serde.to_json_cached(obj).encode(),
                          "application/json")

    def _audit(self, h, method: str, req: _Request, user) -> None:
        """One ResponseComplete line per request (ref: audit.Event, level
        Metadata — no request/response bodies)."""
        if self._audit_file is None:
            return  # cheap unlocked fast path; re-checked under the lock
        from ..utils.clock import now_iso
        from .auth import request_verb
        line = json.dumps({
            "stage": "ResponseComplete",
            "timestamp": now_iso(),
            "user": getattr(user, "name", "") or "system:unsecured",
            "groups": list(getattr(user, "groups", ()) or ()),
            "verb": request_verb(method, req.query.get("watch")
                                 in ("true", "1"), bool(req.name)),
            "resource": req.resource,
            "subresource": req.subresource,
            "namespace": req.namespace,
            "name": req.name,
            "code": getattr(h, "_audit_code", 200),
            "sourceIP": h.client_address[0],
            # the REAL actor behind an impersonated request (ref: the
            # reference audits impersonated-user in extra)
            "impersonatedBy": getattr(h, "_impersonator", ""),
        })
        with self._audit_lock:
            # the None check lives under the lock: stop() closes the file
            # under the same lock, so an in-flight request cannot race a
            # write onto a closed handle
            if self._audit_file is None:
                return
            self._audit_file.write(line + "\n")
            self._audit_file.flush()

    def _respond_raw(self, h, code: int, body: bytes, ctype: str,
                     headers: Optional[dict] = None) -> None:
        self.request_metrics.wire_bytes_sent.inc(
            len(body),
            encoding="binary" if ctype.startswith(binenc.CONTENT_TYPE)
            else "json")
        h._audit_code = code
        h.send_response(code)
        h.send_header("Content-Type", ctype)
        h.send_header("Content-Length", str(len(body)))
        origin = getattr(h, "_cors_origin", None)
        if origin:
            h.send_header("Access-Control-Allow-Origin", origin)
        for k, v in (headers or {}).items():
            h.send_header(k, v)
        h.end_headers()
        h.wfile.write(body)

    def _error(self, h, code: int, reason: str, message: str,
               headers: Optional[dict] = None) -> None:
        body = json.dumps({
            "apiVersion": "v1", "kind": "Status", "status": "Failure",
            "reason": reason, "message": message, "code": code}).encode()
        self._respond_raw(h, code, body, "application/json",
                          headers=headers)
