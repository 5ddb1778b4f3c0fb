"""ktpulint rules — the repo's contracts as AST checks.

Each rule is a class with an id, a one-line title, an optional
`prepare(modules)` global pass (cross-file context: registered metric
families, the lock graph), and a per-file `check(module)` returning
Findings. Rules never import kubernetes_tpu and never execute repo
code — everything is derived from the AST plus import-alias
resolution, so the whole walk stays tier-1 cheap.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .engine import Finding, Module


# --------------------------------------------------------------- helpers

def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain; None for anything computed."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def import_aliases(tree: ast.Module) -> Dict[str, str]:
    """Local name -> fully-dotted origin, from this module's imports.
    `import time as _time` -> {_time: time}; `from datetime import
    datetime as dt` -> {dt: datetime.datetime}; `from time import
    time` -> {time: time.time}."""
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module \
                and node.level == 0:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def resolve_name(expr: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
    """A Name/Attribute chain's fully-dotted origin, or None when the
    base is not an imported name (a local/instance receiver is someone
    else's problem — this keeps `rng.random()` from matching
    `random.random`)."""
    name = dotted_name(expr)
    if name is None:
        return None
    first, _, rest = name.partition(".")
    origin = aliases.get(first)
    if origin is None:
        return None
    return f"{origin}.{rest}" if rest else origin


def resolve_call(node: ast.Call, aliases: Dict[str, str]) -> Optional[str]:
    """The call target's fully-dotted origin (see resolve_name)."""
    return resolve_name(node.func, aliases)


def enclosing_map(tree: ast.Module, kinds) -> Dict[ast.AST, ast.AST]:
    """node -> nearest enclosing node of one of `kinds` (lexical)."""
    out: Dict[ast.AST, ast.AST] = {}

    def visit(node: ast.AST, current: Optional[ast.AST]) -> None:
        for child in ast.iter_child_nodes(node):
            out[child] = current if not isinstance(child, kinds) else child
            visit(child, out[child])
    visit(tree, None)
    return out


class Rule:
    id = ""
    title = ""

    def prepare(self, modules: List[Module]) -> None:  # global context
        pass

    def check(self, module: Module) -> List[Finding]:
        raise NotImplementedError


# --------------------------------------------------------------- KTPU001

class SwallowedException(Rule):
    """A broad handler (bare / Exception / BaseException) whose body
    only drops the error (pass / continue / return-a-plain-value) hides
    failures from logs AND metrics — the class of bug PRs 2, 4, and 8
    each paid satellite budget to retrofit. Handlers that log, count,
    re-raise, or compute a fallback (return with a call) are fine."""

    id = "KTPU001"
    title = "swallowed-exception"

    @staticmethod
    def _broad(t: Optional[ast.expr]) -> bool:
        if t is None:
            return True
        if isinstance(t, ast.Name):
            return t.id in ("Exception", "BaseException")
        if isinstance(t, ast.Tuple):
            return any(SwallowedException._broad(e) for e in t.elts)
        return False

    @staticmethod
    def _silent_stmt(s: ast.stmt) -> bool:
        if isinstance(s, (ast.Pass, ast.Continue)):
            return True
        if isinstance(s, ast.Return):
            # `return self._fallback()` computes a recovery -> handling;
            # `return False` just drops the error -> swallowing
            if s.value is None:
                return True
            return not any(isinstance(n, ast.Call)
                           for n in ast.walk(s.value))
        return False

    def check(self, module: Module) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ExceptHandler) \
                    and self._broad(node.type) \
                    and all(self._silent_stmt(s) for s in node.body):
                out.append(Finding(
                    module.path, node.lineno, self.id,
                    "broad except handler swallows the error (no log, "
                    "metric, or re-raise); route through "
                    "utils.errlog.SwallowedErrors or utils.backoff.retry"))
        return out


# --------------------------------------------------------------- KTPU002

class WallClock(Rule):
    """Direct wall-clock reads/sleeps outside utils/clock.py break the
    FakeClock determinism contract (same seed => identical event logs):
    every component takes an injectable Clock; call clock.now() /
    clock.sleep() instead, or take a `clock: Clock = REAL_CLOCK`
    parameter for loops that must wait REAL time."""

    id = "KTPU002"
    title = "wall-clock"

    FORBIDDEN = {
        "time.time", "time.sleep",
        "datetime.datetime.now", "datetime.datetime.utcnow",
        "datetime.datetime.today", "datetime.date.today",
    }

    EXEMPT_SUFFIX = "utils/clock.py"

    def check(self, module: Module) -> List[Finding]:
        if module.path.endswith(self.EXEMPT_SUFFIX):
            return []
        aliases = import_aliases(module.tree)
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            target = resolve_call(node, aliases)
            if target in self.FORBIDDEN:
                out.append(Finding(
                    module.path, node.lineno, self.id,
                    f"direct {target}() bypasses the injectable "
                    "utils.clock.Clock (FakeClock determinism contract)"))
        return out


# --------------------------------------------------------------- KTPU003

class UnseededRandom(Rule):
    """Module-level random.* / numpy.random.* calls draw from global,
    unseeded state — a hole in the same-seed => identical-logs contract.
    Construct a seeded generator instead (random.Random(seed),
    np.random.default_rng(seed)) like chaos/injector and utils/backoff
    do."""

    id = "KTPU003"
    title = "unseeded-randomness"

    #: generator CONSTRUCTORS are the sanctioned path (they take seeds)
    ALLOWED_RANDOM = {"Random", "SystemRandom"}
    ALLOWED_NP = {"default_rng", "RandomState", "Generator", "SeedSequence",
                  "PCG64", "Philox"}

    def check(self, module: Module) -> List[Finding]:
        aliases = import_aliases(module.tree)
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            target = resolve_call(node, aliases)
            if target is None:
                continue
            bad = None
            if target.startswith("random.") \
                    and target.count(".") == 1 \
                    and target.split(".")[1] not in self.ALLOWED_RANDOM:
                bad = target
            elif target.startswith("numpy.random.") \
                    and target.split(".")[2] not in self.ALLOWED_NP:
                bad = target
            if bad is not None:
                out.append(Finding(
                    module.path, node.lineno, self.id,
                    f"{bad}() draws from global unseeded state; use a "
                    "seeded generator (random.Random(seed) / "
                    "np.random.default_rng(seed))"))
        return out


# --------------------------------------------------------------- KTPU004

_METRIC_FACTORIES = {"counter": "counter", "gauge": "gauge",
                     "histogram": "histogram"}
_METRIC_CTORS = {"Counter": "counter", "Gauge": "gauge",
                 "Histogram": "histogram"}


class MetricNaming(Rule):
    """Prometheus naming discipline (ref: instrumentation guidelines the
    reference's metrics linters enforce): counter families end `_total`,
    histogram families end `_seconds`/`_bytes`. Cross-file: the same
    family name must not be registered with two different kinds (the
    static twin of the runtime registry-collision test), and a literal
    metric name incremented via lookup must resolve to a family some
    *Metrics class registers."""

    id = "KTPU004"
    title = "metric-naming"

    def __init__(self):
        #: family name -> sorted set of kinds seen anywhere
        self._kinds: Dict[str, Set[str]] = {}
        #: families registered inside a *Metrics class (the universe
        #: literal increments must resolve against)
        self._registered: Set[str] = set()

    @staticmethod
    def _registrations(module: Module):
        """Yield (name, kind, lineno, in_metrics_class) for every metric
        family registration in this module."""
        enclosing = enclosing_map(module.tree, (ast.ClassDef,))
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            arg0 = node.args[0]
            if not (isinstance(arg0, ast.Constant)
                    and isinstance(arg0.value, str)):
                continue
            kind = None
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _METRIC_FACTORIES:
                kind = _METRIC_FACTORIES[node.func.attr]
            elif isinstance(node.func, ast.Name) \
                    and node.func.id in _METRIC_CTORS:
                kind = _METRIC_CTORS[node.func.id]
            if kind is None:
                continue
            cls = enclosing.get(node)
            in_metrics = isinstance(cls, ast.ClassDef) \
                and cls.name.endswith("Metrics")
            yield arg0.value, kind, node.lineno, in_metrics

    def prepare(self, modules: List[Module]) -> None:
        self._kinds.clear()
        self._registered.clear()
        for m in modules:
            for name, kind, _line, in_metrics in self._registrations(m):
                self._kinds.setdefault(name, set()).add(kind)
                if in_metrics:
                    self._registered.add(name)

    @staticmethod
    def _literal_lookup_name(call: ast.Call) -> Optional[str]:
        """The literal family name when `.inc()`/`.observe()`/`.set()`
        is chained onto a lookup: `families["x_total"].inc()` or
        `metrics.family("x_total").inc()`. Attribute-held metrics
        (`self.metrics.api_retries.inc()`) resolve at registration
        time and are not checked here."""
        if not (isinstance(call.func, ast.Attribute)
                and call.func.attr in ("inc", "observe", "set")):
            return None
        recv = call.func.value
        if isinstance(recv, ast.Subscript):
            sl = recv.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                return sl.value
        if isinstance(recv, ast.Call) and recv.args \
                and isinstance(recv.func, ast.Attribute) \
                and recv.func.attr not in _METRIC_FACTORIES \
                and isinstance(recv.args[0], ast.Constant) \
                and isinstance(recv.args[0].value, str) \
                and re.search(r"_(total|seconds|bytes)$",
                              str(recv.args[0].value)):
            return recv.args[0].value
        return None

    def check(self, module: Module) -> List[Finding]:
        out: List[Finding] = []
        for name, kind, line, _in_metrics in self._registrations(module):
            if kind == "counter" and not name.endswith("_total"):
                out.append(Finding(
                    module.path, line, self.id,
                    f"counter family '{name}' must end '_total'"))
            if kind == "histogram" and not name.endswith(
                    ("_seconds", "_bytes")):
                out.append(Finding(
                    module.path, line, self.id,
                    f"histogram family '{name}' must end '_seconds' "
                    "or '_bytes'"))
            if len(self._kinds.get(name, ())) > 1:
                kinds = ",".join(sorted(self._kinds[name]))
                out.append(Finding(
                    module.path, line, self.id,
                    f"family '{name}' registered with conflicting kinds "
                    f"({kinds}) — the aggregating registry would refuse "
                    "the merge at runtime"))
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self._literal_lookup_name(node)
            if name is not None and name not in self._registered:
                out.append(Finding(
                    module.path, node.lineno, self.id,
                    f"literal metric name '{name}' does not resolve to "
                    "a family registered by any *Metrics class"))
        return out


# --------------------------------------------------------------- KTPU005

_CAP_NAME_RE = re.compile(r".*(_CAP|_LIMIT)$")

_LOG_METHODS = {"warning", "info", "error", "debug", "exception",
                "critical", "log"}


class SilentCap(Rule):
    """The 'no silent caps' contract (PR 5): truncating work at a named
    `*_CAP`/`*_LIMIT` constant is fine only when the enclosing function
    makes the truncation visible — a fallback/overflow counter (.inc /
    .observe), a log call, or a *count*/*fallback*/*capped* helper."""

    id = "KTPU005"
    title = "silent-cap"

    @staticmethod
    def _cap_name(node: ast.expr) -> Optional[str]:
        name = dotted_name(node)
        if name is None:
            return None
        last = name.rsplit(".", 1)[-1]
        return name if _CAP_NAME_RE.match(last) else None

    @classmethod
    def _cap_uses(cls, fn: ast.AST):
        for node in ast.walk(fn):
            if isinstance(node, ast.Subscript) \
                    and isinstance(node.slice, ast.Slice):
                for bound in (node.slice.lower, node.slice.upper):
                    if bound is not None:
                        cap = cls._cap_name(bound)
                        if cap:
                            yield node.lineno, cap, "slice"
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id in ("min", "max"):
                for arg in node.args:
                    cap = cls._cap_name(arg)
                    if cap:
                        yield node.lineno, cap, "clamp"

    @staticmethod
    def _mitigated(fn: ast.AST) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute):
                attr = node.func.attr
                if attr in ("inc", "observe") or attr in _LOG_METHODS \
                        or "fallback" in attr or "capped" in attr \
                        or "count" in attr:
                    return True
        return False

    def check(self, module: Module) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            uses = list(self._cap_uses(node))
            if uses and not self._mitigated(node):
                for line, cap, how in uses:
                    out.append(Finding(
                        module.path, line, self.id,
                        f"{how} against {cap} with no fallback counter "
                        "or log in the enclosing function — capped "
                        "work must be visible (the PR 5 contract)"))
        return out


# --------------------------------------------------------------- KTPU006

_LOCKISH_RE = re.compile(r".*(lock|cond|mutex).*", re.IGNORECASE)

_THREADING_LOCKS = {"threading.Lock", "threading.RLock",
                    "threading.Condition", "Lock", "RLock", "Condition"}


class LockOrder(Rule):
    """Acquires-while-holding cycles across the scheduler/cache/queue
    deadlock under exactly the thread interleavings the chaos harness
    cannot reproduce deterministically. The graph is built from nested
    `with <lock>` statements, with lock identity resolved to
    `OwningClass.attr` (one level of `self.member = Class(...)`
    inference); unresolvable bases are skipped — precision over
    recall."""

    id = "KTPU006"
    title = "lock-order"

    def __init__(self):
        #: (class, attr) -> member's class name, from self.X = Cls(...)
        self._member_class: Dict[Tuple[str, str], str] = {}
        #: (class, attr) -> "Lock"|"RLock"|"Condition" where known
        self._lock_kind: Dict[Tuple[str, str], str] = {}
        self._class_names: Set[str] = set()
        #: edge (held, acquired) -> earliest (path, line)
        self._edges: Dict[Tuple[str, str], Tuple[str, int]] = {}

    # ---- pass 1: class/member discovery

    def _scan_classes(self, module: Module) -> None:
        aliases = import_aliases(module.tree)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            self._class_names.add(node.name)
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Assign) \
                        or not isinstance(sub.value, ast.Call):
                    continue
                for tgt in sub.targets:
                    if not (isinstance(tgt, ast.Attribute)
                            and isinstance(tgt.value, ast.Name)
                            and tgt.value.id == "self"):
                        continue
                    ctor = dotted_name(sub.value.func)
                    if ctor is None:
                        continue
                    resolved = resolve_call(sub.value, aliases) or ctor
                    last = ctor.rsplit(".", 1)[-1]
                    if resolved in _THREADING_LOCKS \
                            or last in ("Lock", "RLock", "Condition"):
                        self._lock_kind[(node.name, tgt.attr)] = last
                    elif isinstance(sub.value.func, ast.Name):
                        self._member_class[(node.name, tgt.attr)] = \
                            sub.value.func.id

    # ---- pass 2: nested-with edges

    def _lock_node(self, expr: ast.expr, cls: Optional[str]
                   ) -> Optional[Tuple[str, bool]]:
        """(lock id, is_exact_self_attr) or None. `self.X` -> `Cls.X`;
        `self.member.X` -> `MemberCls.X` when the member's class is
        known; anything else is skipped."""
        name = dotted_name(expr)
        if name is None or cls is None:
            return None
        parts = name.split(".")
        if not _LOCKISH_RE.match(parts[-1]):
            return None
        if len(parts) == 2 and parts[0] == "self":
            return f"{cls}.{parts[1]}", True
        if len(parts) == 3 and parts[0] == "self":
            member_cls = self._member_class.get((cls, parts[1]))
            if member_cls:
                return f"{member_cls}.{parts[2]}", False
        return None

    def _walk_withs(self, module: Module) -> None:
        enclosing_cls = enclosing_map(module.tree, (ast.ClassDef,))

        def visit(node: ast.AST, held: List[Tuple[str, bool]]) -> None:
            if isinstance(node, ast.With):
                cls_node = enclosing_cls.get(node)
                cls = cls_node.name if isinstance(
                    cls_node, ast.ClassDef) else None
                acquired: List[Tuple[str, bool]] = []
                for item in node.items:
                    ln = self._lock_node(item.context_expr, cls)
                    if ln is not None:
                        # earlier items of THIS statement are already
                        # held when this one acquires (`with a, b:` is
                        # sugar for nesting) — check against both
                        for h, h_self in held + acquired:
                            if h == ln[0] and not (h_self and ln[1]):
                                continue  # ambiguous non-self same-name
                            if h == ln[0]:
                                kind = self._lock_kind.get(
                                    tuple(h.split(".", 1)))
                                if kind != "Lock":
                                    continue  # reentrant or unknown
                            site = (module.path, node.lineno)
                            self._edges.setdefault((h, ln[0]), site)
                        acquired.append(ln)
                for child in node.body:
                    visit(child, held + acquired)
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                held = []  # a nested def runs later, not while holding
            for child in ast.iter_child_nodes(node):
                visit(child, held)

        visit(module.tree, [])

    def prepare(self, modules: List[Module]) -> None:
        self.__init__()
        for m in modules:
            self._scan_classes(m)
        for m in modules:
            self._walk_withs(m)
        self._cycles = self._find_cycles()

    def _find_cycles(self) -> List[Tuple[Tuple[str, ...],
                                         Tuple[str, int]]]:
        """Elementary cycles via DFS over the (small) lock graph; each
        reported once in canonical rotation with its earliest site."""
        graph: Dict[str, List[str]] = {}
        for (a, b) in self._edges:
            graph.setdefault(a, []).append(b)
        for v in graph.values():
            v.sort()
        seen: Set[Tuple[str, ...]] = set()
        cycles = []

        def canonical(path: Tuple[str, ...]) -> Tuple[str, ...]:
            i = path.index(min(path))
            return path[i:] + path[:i]

        def dfs(start: str, node: str, path: List[str]) -> None:
            for nxt in graph.get(node, ()):  # sorted -> deterministic
                if nxt == start and len(path) >= 1:
                    cyc = canonical(tuple(path))
                    if cyc not in seen:
                        seen.add(cyc)
                        sites = [self._edges[(path[i],
                                              path[(i + 1) % len(path)])]
                                 for i in range(len(path))]
                        cycles.append((cyc, min(sites)))
                elif nxt not in path and nxt > start:
                    # only explore nodes > start: each cycle found once,
                    # from its smallest node
                    dfs(start, nxt, path + [nxt])
            # self-edges: path length 1 handled by nxt == start above

        for start in sorted(graph):
            dfs(start, start, [start])
        return sorted(cycles, key=lambda c: c[1])

    def check(self, module: Module) -> List[Finding]:
        out: List[Finding] = []
        for cyc, (path, line) in self._cycles:
            if path != module.path:
                continue
            order = " -> ".join(cyc + (cyc[0],))
            out.append(Finding(
                path, line, self.id,
                f"lock-order cycle: {order} (acquire locks in one "
                "global order or drop the outer lock first)"))
        return out


# --------------------------------------------------------------- KTPU007

class EnvironmentRead(Rule):
    """An environment variable read inside the library selects the
    program that runs without any caller, test or configuration file
    saying so: an exported `KTPU_CLASS_SCAN=0` once changed what every
    benchmark cell timed. Deployment settings (paths, addresses,
    credentials) enter through `cmd/`'s flags and files; a component
    takes the rest as arguments. Flags `os.environ.get`, `os.environ[...]`
    and `os.getenv` under kubernetes_tpu/, except in `cmd/`,
    `utils/certs.py` and for `JAX_COMPILATION_CACHE_DIR` (where the
    compile cache lives is the deployment's to say)."""

    id = "KTPU007"
    title = "environment-read"

    EXEMPT_PREFIXES = ("kubernetes_tpu/cmd/",)
    EXEMPT_PATHS = ("kubernetes_tpu/utils/certs.py",)
    EXEMPT_VARIABLES = {"JAX_COMPILATION_CACHE_DIR"}

    def check(self, module: Module) -> List[Finding]:
        if not module.path.startswith("kubernetes_tpu/") \
                or module.path.startswith(self.EXEMPT_PREFIXES) \
                or module.path in self.EXEMPT_PATHS:
            return []
        aliases = import_aliases(module.tree)
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) and resolve_call(
                    node, aliases) in ("os.environ.get", "os.getenv"):
                key = node.args[0] if node.args else None
            elif isinstance(node, ast.Subscript) \
                    and isinstance(node.ctx, ast.Load) \
                    and resolve_name(node.value, aliases) == "os.environ":
                key = node.slice
            else:
                continue
            name = key.value if isinstance(key, ast.Constant) else None
            if name in self.EXEMPT_VARIABLES:
                continue
            out.append(Finding(
                module.path, node.lineno, self.id,
                f"environment read ({name or 'computed name'}) outside "
                "cmd/: take it as an argument or a configuration key"))
        return out


ALL_RULES = (SwallowedException, WallClock, UnseededRandom, MetricNaming,
             SilentCap, LockOrder, EnvironmentRead)

RULE_INDEX = {r.id: r for r in ALL_RULES}
