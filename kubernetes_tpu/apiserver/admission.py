"""Built-in admission plugins: ResourceQuota and LimitRanger.

Ref: plugin/pkg/admission/resourcequota/admission.go (QuotaAdmission —
Validate computes the incoming object's usage delta, checks it against every
matching quota's hard limits, and commits the new used totals with CAS
retries) and plugin/pkg/admission/limitranger/admission.go (LimitRanger —
Admit defaults container requests/limits from the namespace's LimitRanges,
Validate enforces min/max/ratio constraints).

The usage evaluators mirror pkg/quota/evaluator/core/pods.go (PodUsageFunc:
max(sum containers, init containers) per resource, requests.* and limits.*
plus legacy bare names, count only while not terminal) and the generic
object-count evaluator (count/{resource} for everything else).
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import Any, Dict, List, Optional

from ..api.core import LimitRange, Pod, ResourceQuota
from ..api.quantity import Quantity


class QuotaExceeded(Exception):
    """Maps to HTTP 403 Forbidden, like the reference's quota denial.

    `namespace` and `resource_key` name the exhausted cap (the quota KEY,
    e.g. "requests.cpu", not the REST resource) so callers — the denial
    counter, /debug/pending attribution — can label without parsing the
    message."""

    def __init__(self, message: str, namespace: str = "",
                 resource_key: str = ""):
        super().__init__(message)
        self.namespace = namespace
        self.resource_key = resource_key


# ---------------------------------------------------------------- evaluators

def _pod_compute(pod: Pod) -> Dict[str, Quantity]:
    """Per-resource Quantities: sum over containers, elementwise max with
    init containers (ref: pkg/quota/evaluator/core/pods.go podUsageHelper)."""
    totals: Dict[str, Quantity] = {}
    limits: Dict[str, Quantity] = {}
    for c in pod.spec.containers:
        for name, q in c.resources.requests.items():
            totals[name] = totals.get(name, Quantity(0)) + q
        for name, q in c.resources.limits.items():
            limits[name] = limits.get(name, Quantity(0)) + q
    for c in pod.spec.init_containers:
        for name, q in c.resources.requests.items():
            if q > totals.get(name, Quantity(0)):
                totals[name] = Quantity(q)
        for name, q in c.resources.limits.items():
            if q > limits.get(name, Quantity(0)):
                limits[name] = Quantity(q)
    usage: Dict[str, Quantity] = {}
    for name, q in totals.items():
        usage[f"requests.{name}"] = q
        if name in ("cpu", "memory", "ephemeral-storage"):
            usage[name] = q  # legacy bare names alias requests
    for name, q in limits.items():
        usage[f"limits.{name}"] = q
    return usage


def pod_is_terminal(pod: Pod) -> bool:
    return pod.status.phase in ("Succeeded", "Failed")


def evaluate_usage(resource: str, obj: Any) -> Dict[str, Quantity]:
    """The quota-relevant usage of one object."""
    usage: Dict[str, Quantity] = {f"count/{resource}": Quantity(1)}
    if resource == "pods":
        if pod_is_terminal(obj):
            return {}
        usage["pods"] = Quantity(1)
        usage.update(_pod_compute(obj))
    elif resource in ("services", "persistentvolumeclaims",
                      "replicationcontrollers", "resourcequotas",
                      "configmaps", "secrets"):
        usage[resource] = Quantity(1)
        if resource == "persistentvolumeclaims":
            req = getattr(obj.spec, "resources", None)
            storage = (req.requests.get("storage")
                       if req is not None else None)
            if storage is not None:
                usage["requests.storage"] = storage
    return usage


def pod_qos_best_effort(pod: Pod) -> bool:
    """BestEffort per the ONE shared classifier (helpers.pod_qos) — quota
    scope matching must agree with the scheduler predicates and kubelet
    eviction on what BestEffort means, or the same pod is classed
    differently per subsystem. Like the reference's GetPodQOS
    (pkg/apis/core/v1/helper/qos/qos.go:44) this inspects REGULAR
    containers only; init-container resources do not affect QoS class."""
    from ..api.helpers import pod_qos
    return pod_qos(pod) == "BestEffort"


def scope_matches(scope: str, pod: Pod) -> bool:
    """Ref: pkg/quota/evaluator/core/pods.go podMatchesScopeFunc."""
    if scope == "Terminating":
        return pod.spec.active_deadline_seconds is not None
    if scope == "NotTerminating":
        return pod.spec.active_deadline_seconds is None
    if scope == "BestEffort":
        return pod_qos_best_effort(pod)
    if scope == "NotBestEffort":
        return not pod_qos_best_effort(pod)
    return False


# ----------------------------------------------------------- quota admission

class ResourceQuotaAdmission:
    """Validating plugin: on CREATE, charge the object's usage against every
    matching quota in its namespace atomically (CAS on quota status), or
    deny with QuotaExceeded -> 403.

    Like the reference, replenishment on delete is the quota CONTROLLER's
    job (full recalculation); admission only ever charges forward, so a
    burst can never overshoot but transiently-stale `used` can under-admit
    until the controller resyncs.
    """

    def __init__(self, client, metrics=None):
        self.client = client
        #: tenancy.QuotaMetrics (optional): denials counted by
        #: {namespace, resource} so "who is hitting which cap" is a
        #: /metrics query, not a log grep
        self.metrics = metrics
        # per-thread record of the last request's committed charges so the
        # server can refund them if storage rejects the create AFTER
        # admission (AlreadyExists, CRD validation…) — otherwise the
        # namespace is falsely throttled until the controller's resync
        import threading
        self._last = threading.local()

    def refund_last(self) -> None:
        """Undo the charges committed by the most recent validate() on
        this thread (called by the server when create fails post-admission)."""
        self.refund_rec(self.take_last())

    def take_last(self):
        """Harvest (and clear) this thread's last charge record — bulk
        create stashes one per slot so a failed slot refunds only its own."""
        rec = getattr(self._last, "rec", None)
        self._last.rec = None
        return rec

    def refund_rec(self, rec) -> None:
        if rec:
            charged, delta = rec
            for q, keys in charged:
                self._refund(q, delta, keys)

    def validate(self, operation: str, resource: str, obj: Any) -> None:
        self._last.rec = None
        if operation != "CREATE" or resource == "resourcequotas":
            return
        ns = getattr(getattr(obj, "metadata", None), "namespace", "")
        if not ns:
            return
        quotas: List[ResourceQuota] = \
            self.client.resource_quotas().list(namespace=ns)
        if not quotas:
            return
        delta = evaluate_usage(resource, obj)
        if not delta:
            return
        charged = []  # (quota, keys) already committed, for rollback
        for quota in quotas:
            if quota.spec.scopes:
                if resource != "pods" or not all(
                        scope_matches(s, obj) for s in quota.spec.scopes):
                    continue
            interesting = [k for k in quota.spec.hard
                           if k in delta and not delta[k].is_zero()]
            if not interesting:
                continue
            try:
                self._charge(quota, delta, interesting)
            except QuotaExceeded as e:
                # un-charge quotas already committed this request so a
                # denial leaves no phantom usage behind (the controller
                # would eventually fix it, but until its resync the
                # namespace would be falsely throttled)
                for q, keys in charged:
                    self._refund(q, delta, keys)
                if self.metrics is not None:
                    self.metrics.admission_rejections.inc(
                        namespace=e.namespace or ns,
                        resource=e.resource_key or "unknown")
                raise
            charged.append((quota, interesting))
        if charged:
            self._last.rec = (charged, delta)

    def _charge(self, quota: ResourceQuota, delta: Dict[str, Quantity],
                keys: List[str]) -> None:
        """Atomically move used forward, or raise QuotaExceeded. The check
        runs INSIDE the CAS mutate — a concurrent charge that lands first
        re-runs this one against the fresh totals (no lost update, no
        admit-over-limit window)."""
        name, ns = quota.metadata.name, quota.metadata.namespace

        def mutate(live):
            hard = live.spec.hard
            used = dict(live.status.used)
            for k in keys:
                if k not in hard:
                    continue  # hard shrank since we listed
                new = used.get(k, Quantity(0)) + delta[k]
                if new > hard[k]:
                    raise QuotaExceeded(
                        f"exceeded quota: {name}, requested: "
                        f"{k}={delta[k]}, used: "
                        f"{k}={used.get(k, Quantity(0))}, limited: "
                        f"{k}={hard[k]}",
                        namespace=ns, resource_key=k)
                used[k] = new
            live.status.hard = dict(live.spec.hard)
            live.status.used = used
            return live

        self.client.resource_quotas().patch(name, mutate, namespace=ns)

    def _refund(self, quota: ResourceQuota, delta: Dict[str, Quantity],
                keys: List[str]) -> None:
        def mutate(live):
            used = dict(live.status.used)
            zero = Quantity(0)
            for k in keys:
                cur = used.get(k, zero) - delta[k]
                used[k] = cur if cur > zero else Quantity(0)
            live.status.used = used
            return live
        try:
            self.client.resource_quotas().patch(
                quota.metadata.name, mutate,
                namespace=quota.metadata.namespace)
        except Exception:
            pass  # the controller's recalculation is the backstop


# ------------------------------------------------------------------ webhooks

class WebhookDispatcher:
    """Out-of-process admission over HTTP (ref: apiserver/pkg/admission/
    plugin/webhook/{mutating,validating}/plugin.go): webhook endpoints are
    registered as STORED Mutating/ValidatingWebhookConfiguration objects;
    each matching webhook receives an AdmissionReview POST

        {"request": {"uid", "operation", "resource", "namespace",
                     "object": <encoded>}}

    and answers {"response": {"allowed": bool, "message"?,
    "patch"?: base64 RFC6902, "patchType"?: "JSONPatch"}}. Mutating
    webhooks run between the in-process mutators and the validators;
    validating webhooks run last. A webhook that errors or times out
    follows its failurePolicy: Fail denies the request (the v1 default),
    Ignore skips the webhook."""

    def __init__(self, client, around_call=nullcontext):
        self.client = client
        #: context manager factory around each remote round trip: the
        #: apiserver passes its create gate's `released`, so a slow
        #: webhook never holds the single-writer section
        self._around_call = around_call

    # ---- mutating (returns the possibly-patched object)

    def _empty(self, kind_resource: str) -> bool:
        store = getattr(self.client, "store", None)
        return store is not None and store.count(kind_resource) == 0

    def _group_version_of(self, resource: str) -> str:
        """Registered groupVersion of a resource plural ("apps/v1", "v1"),
        or "" when unresolvable (matches() then under-matches safely)."""
        scheme = getattr(self.client, "scheme", None)
        if scheme is None:
            return ""
        cls = scheme.type_for_resource(resource)
        if cls is None:
            return ""
        try:
            return scheme.gvk_for(cls)[0]
        except KeyError:
            return ""

    def admit(self, operation: str, resource: str, obj: Any):
        if self._empty("mutatingwebhookconfigurations"):
            return obj  # O(1) fast path: no webhooks registered
        from ..api.admissionregistration import MutatingWebhookConfiguration
        gv = self._group_version_of(resource)
        for cfg in self.client.resource(
                MutatingWebhookConfiguration).list():
            for wh in cfg.webhooks:
                if not wh.matches(operation, resource, gv):
                    continue
                resp = self._call(wh, operation, resource, obj)
                if resp is None:
                    continue  # failurePolicy=Ignore swallowed an error
                if not resp.get("allowed", False):
                    self._deny(wh, resp)
                patch_b64 = resp.get("patch")
                if patch_b64:
                    obj = self._apply_patch(obj, patch_b64)
        return obj

    # ---- validating

    def validate(self, operation: str, resource: str, obj: Any) -> None:
        if self._empty("validatingwebhookconfigurations"):
            return
        from ..api.admissionregistration import (
            ValidatingWebhookConfiguration)
        gv = self._group_version_of(resource)
        for cfg in self.client.resource(
                ValidatingWebhookConfiguration).list():
            for wh in cfg.webhooks:
                if not wh.matches(operation, resource, gv):
                    continue
                resp = self._call(wh, operation, resource, obj)
                if resp is None:
                    continue
                if not resp.get("allowed", False):
                    self._deny(wh, resp)

    # ---- plumbing

    def _deny(self, wh, resp) -> None:
        from .server import AdmissionDenied
        msg = (resp.get("status") or {}).get("message") \
            or resp.get("message") or "denied"
        raise AdmissionDenied(
            f'admission webhook "{wh.name}" denied the request: {msg}')

    def _call(self, wh, operation: str, resource: str, obj: Any):
        """One AdmissionReview round trip, or None when an erroring
        webhook's failurePolicy says Ignore."""
        import json as _json
        import uuid
        from urllib import request as urlrequest
        from ..api import serde
        review = {
            "apiVersion": "admission.k8s.io/v1",
            "kind": "AdmissionReview",
            "request": {
                "uid": str(uuid.uuid4()),
                "operation": operation,
                "resource": resource,
                "namespace": getattr(getattr(obj, "metadata", None),
                                     "namespace", ""),
                "object": serde.encode(obj),
            }}
        try:
            req = urlrequest.Request(
                wh.client_config.url,
                data=_json.dumps(review).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with self._around_call(), urlrequest.urlopen(
                    req, timeout=max(1, wh.timeout_seconds)) as r:
                body = r.read()
            body = _json.loads(body)
            resp = body.get("response")
            if not isinstance(resp, dict):
                # a 200 without a usable response is a BROKEN webhook, not
                # a verdict — it must follow failurePolicy like any error
                raise ValueError("AdmissionReview reply has no response")
            return resp
        except Exception as e:
            if wh.failure_policy == "Ignore":
                return None
            from .server import AdmissionDenied
            raise AdmissionDenied(
                f'admission webhook "{wh.name}" failed and '
                f"failurePolicy is Fail: {e}")

    def _apply_patch(self, obj: Any, patch_b64: str):
        import base64
        import json as _json
        from ..api import serde
        from ..api.patch import json_patch
        ops = _json.loads(base64.b64decode(patch_b64))
        merged = json_patch(serde.encode(obj), ops)
        return serde.decode(type(obj), merged)


# -------------------------------------------------------------- noderestriction

class NodeRestriction:
    """Validating plugin scoping what a NODE identity may create/modify
    (ref: plugin/pkg/admission/noderestriction/admission.go:53): mirror
    pods only onto itself, and only its own Node object. Complements the
    Node authorizer — authorization can't inspect request BODIES, so a
    node could otherwise create a pod bound to a different node."""

    def __init__(self, server):
        self._server = server

    def validate(self, operation: str, resource: str, obj: Any) -> None:
        user = self._server.current_user()
        if user is None or not user.name.startswith("system:node:") or \
                "system:nodes" not in getattr(user, "groups", ()):
            return
        node = user.name[len("system:node:"):]
        from .server import AdmissionDenied
        if resource == "pods" and operation == "CREATE" and \
                obj.spec.node_name != node:
            raise AdmissionDenied(
                f"node {node!r} may only create mirror pods bound to "
                f"itself, not {obj.spec.node_name!r}")
        if resource == "nodes" and obj.metadata.name != node:
            raise AdmissionDenied(
                f"node {node!r} may not modify node "
                f"{obj.metadata.name!r}")


# ------------------------------------------------------------------- priority

class PriorityAdmission:
    """Mutating plugin resolving spec.priorityClassName -> spec.priority at
    pod CREATE (ref: plugin/pkg/admission/priority/admission.go:83-90).
    Without it PriorityClass objects are decorative: the queue and
    preemption read only the resolved integer. A named class must exist
    (reject otherwise); with no name, the cluster's global-default class
    applies, else priority 0."""

    def __init__(self, client):
        self.client = client

    def admit(self, operation: str, resource: str, obj: Any):
        if operation != "CREATE" or resource != "pods":
            return obj
        name = obj.spec.priority_class_name
        store = getattr(self.client, "store", None)
        if not name and store is not None and \
                store.count("priorityclasses") == 0:
            # O(1) fast path for the overwhelmingly common case
            if obj.spec.priority is None:
                obj.spec.priority = 0
            return obj
        from ..state.store import NotFoundError
        if name:
            if name in ("system-cluster-critical", "system-node-critical"):
                # the built-in system classes (ref: scheduling/v1 defaults)
                obj.spec.priority = 2000000000 if \
                    name == "system-cluster-critical" else 2000001000
                return obj
            try:
                pc = self.client.priority_classes().get(name)
            except NotFoundError:
                from .server import AdmissionDenied
                raise AdmissionDenied(
                    f"no PriorityClass with name {name} was found")
            obj.spec.priority = pc.value
            return obj
        if obj.spec.priority is None:
            default = next(
                (pc for pc in self.client.priority_classes().list()
                 if pc.global_default), None)
            if default is not None:
                obj.spec.priority_class_name = default.metadata.name
                obj.spec.priority = default.value
            else:
                obj.spec.priority = 0
        return obj


# -------------------------------------------------------------- serviceaccount

class ServiceAccountAdmission:
    """Ref: plugin/pkg/admission/serviceaccount — default the pod's
    serviceAccountName and require the account to exist (the mutating
    half; token volume projection has no analog without a kubelet token
    path)."""

    def __init__(self, client):
        self.client = client

    def admit(self, operation: str, resource: str, obj: Any):
        if operation == "CREATE" and resource == "pods" and \
                not obj.spec.service_account_name:
            obj.spec.service_account_name = "default"
        return obj

    def validate(self, operation: str, resource: str, obj: Any) -> None:
        if operation != "CREATE" or resource != "pods":
            return
        ns = obj.metadata.namespace
        name = obj.spec.service_account_name
        if not ns or not name:
            return
        from ..state.store import NotFoundError
        try:
            self.client.service_accounts(ns).get(name)
        except NotFoundError:
            from .server import AdmissionDenied
            raise AdmissionDenied(
                f'pod rejected: service account {name!r} not found in '
                f'namespace "{ns}"')


# ----------------------------------------------------------------- limitranger

class LimitRanger:
    """Mutate-then-validate plugin: default container requests/limits from
    the namespace's LimitRange items, then enforce min/max and
    maxLimitRequestRatio (ref: plugin/pkg/admission/limitranger)."""

    def __init__(self, client):
        self.client = client

    def _ranges(self, ns: str) -> List[LimitRange]:
        return self.client.limit_ranges().list(namespace=ns)

    # ---- Admit (mutating): apply defaults

    def admit(self, operation: str, resource: str, obj: Any):
        if operation != "CREATE" or resource != "pods":
            return obj
        ns = obj.metadata.namespace
        if not ns:
            return obj
        for lr in self._ranges(ns):
            for item in lr.spec.limits:
                if item.type != "Container":
                    continue
                for c in obj.spec.containers + obj.spec.init_containers:
                    for name, q in item.default_request.items():
                        c.resources.requests.setdefault(name, Quantity(q))
                    for name, q in item.default.items():
                        c.resources.limits.setdefault(name, Quantity(q))
                    # defaulted limits imply requests when absent (the
                    # reference derives request from limit for Burstable)
                    for name, q in c.resources.limits.items():
                        c.resources.requests.setdefault(name, Quantity(q))
        return obj

    # ---- Validate: enforce constraints

    def validate(self, operation: str, resource: str, obj: Any) -> None:
        if operation != "CREATE" or resource != "pods":
            return
        ns = obj.metadata.namespace
        if not ns:
            return
        for lr in self._ranges(ns):
            for item in lr.spec.limits:
                if item.type == "Container":
                    for c in obj.spec.containers + obj.spec.init_containers:
                        self._check(item, c.resources.requests,
                                    c.resources.limits,
                                    f"container {c.name!r}")
                elif item.type == "Pod":
                    req: Dict[str, Quantity] = {}
                    lim: Dict[str, Quantity] = {}
                    for c in obj.spec.containers:
                        for name, q in c.resources.requests.items():
                            req[name] = req.get(name, Quantity(0)) + q
                        for name, q in c.resources.limits.items():
                            lim[name] = lim.get(name, Quantity(0)) + q
                    self._check(item, req, lim, "pod")

    @staticmethod
    def _check(item, requests: Dict[str, Quantity],
               limits: Dict[str, Quantity], what: str) -> None:
        from .server import AdmissionDenied
        for name, lo in item.min.items():
            got = requests.get(name, limits.get(name))
            if got is not None and got < lo:
                raise AdmissionDenied(
                    f"minimum {name} usage per {item.type} is {lo}, but "
                    f"{what} requests {got}")
        for name, hi in item.max.items():
            got = limits.get(name, requests.get(name))
            if got is not None and got > hi:
                raise AdmissionDenied(
                    f"maximum {name} usage per {item.type} is {hi}, but "
                    f"{what} uses {got}")
        for name, ratio in item.max_limit_request_ratio.items():
            r = requests.get(name)
            l = limits.get(name)
            if r is not None and l is not None and not r.is_zero():
                if l.as_fraction() / r.as_fraction() > ratio.as_fraction():
                    raise AdmissionDenied(
                        f"{name} max limit to request ratio per {item.type} "
                        f"is {ratio}, but provided ratio is "
                        f"{l.as_fraction() / r.as_fraction()}")
