"""The plain reference: the serial scheduler that the configurations
state (scheduler_perf's default provider on these pods), written out
with numpy over the node axis and imported from nowhere in the program.

It replays the hub's binds in the order the pods were created (the
queue is FIFO inside one priority, so that is the order of the
decisions) and, before it applies each bind, asks the two questions a
serial scheduler answers for that pod against everything bound before
it:

  fit     PodFitsResources (cpu, memory, pod count against allocatable)
          and required hostname anti-affinity, both ways (the pod's own
          terms against pods on the node, their terms against the pod).
  score   LeastRequestedPriority + BalancedResourceAllocation, weight 1
          each, in upstream's arithmetic (int64 floors, float64
          fractions). Every other default priority is the same on every
          node for these pods (no Service, no preferred terms, no taints,
          no images) and cannot move an argmax.

A decision is right when its node fits and no fitting node scores
higher: `gap` is best fitting score minus the chosen node's score. The
tie-break among equal scores is the implementation's own (upstream
rotates round-robin; the program hashes row and sequence) and is not
compared.

`precision` swaps the arithmetic of fit and score for a lower one
(float32, bfloat16: every operand and result in that type;
int8: the fractions requested / allocatable held in steps of 1/127);
that is the control (decide() then serves as the scheduler put in the
program's place), never the check.

**The contract of a reference.** A configuration's file names the module
that decides `correct` for it: `"reference": "<name>"` is
benchmarks/references/<name>.py, and a file without the key has this
one (`harness.cluster.load_reference`). Such a module holds

  PodFacts(manifest), with `anti` (its required hostname anti-affinity
      terms) and `extra_words` (see below)
  Reference(nodes, precision="exact", objects=()), with fits, scores,
      judge, decide, bind, over_allocatable; `objects` are the
      manifests of the configuration's `setup_objects`
  replay(nodes, pods_in_order, bound_node, precision="exact", objects=())
      returning the keys it returns here

and imports nothing of `kubernetes_tpu`. It may import this module and
extend its classes, so that a new predicate or priority is a subclass
and not a copy: `replay` is a class method that builds the class it is
called on and that class's `Facts`, so `replay = Reference.replay` under
the subclasses is the whole of it.

**It reads by whitelist.** `PodFacts.reads` and `Reference.reads` list,
path by path, every key of a pod and of a node manifest that this
module reads or knows to be the same on every node (an image no node
lists, a containerPort that is not a hostPort). Any other key that says
something raises `ValueError` with its path (`admit`): nodeSelector,
nodeName, node and pod affinity, preferred terms, tolerations, priority,
volumes, hostPort, topologySpreadConstraints, initContainers, overhead,
a request or an allocatable other than cpu, memory and pods,
matchExpressions or namespaces in a term, taints, unschedulable, node
images, and whatever else a later variant thinks of: a variant is never
judged by a reference that does not know what it carries. So is a
topology key other than hostname, a namespace other than `default` (a
term matches inside one namespace and this module holds one), a node
condition other than Ready=True, and any set-up object (`read_objects`).
A key whose value is what the API's omitempty drops (null, "", 0, false,
[], {}) says nothing and is let through: `priority: 0` is no priority.
A reference that extends this one adds to `reads` the keys it answers
for (`extended`).

**The scan's least bytes are not here.** harness/roofline.py holds the
one byte model (24 B a node and 4 B for each word more) over two facts
a reference exposes for a pod: `len(pod.anti)` and `pod.extra_words`,
the count of further f32 words a node that the chip has to read to
decide this pod under the predicates and priorities the reference
added (0 here).
"""

import numpy as np

HOSTNAME = "kubernetes.io/hostname"
MAX_PRIORITY = 10

_SUFFIX = {"Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30, "Ti": 1 << 40,
           "k": 10 ** 3, "M": 10 ** 6, "G": 10 ** 9}


def milli(q):
    q = str(q)
    return int(q[:-1]) if q.endswith("m") else int(float(q) * 1000)


def quantity(q):
    q = str(q)
    for suffix, mult in _SUFFIX.items():
        if q.endswith(suffix):
            return int(q[:-len(suffix)]) * mult
    return int(q)


REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
_ANTI = "spec.affinity.podAntiAffinity"


def says_nothing(value):
    """What the API's omitempty drops: null, "", 0, false, [], {}."""
    return value is None or (
        isinstance(value, (str, int, float, list, dict)) and not value)


def admit(at, reads, who, path=""):
    """Walk a manifest against a whitelist {path: keys}: raise, naming
    the path, on a key that says something and is not listed. A list is
    walked item by item under its own path; a listed key whose path has
    no entry of its own is taken whole (labels, matchLabels)."""
    if isinstance(at, list):
        for item in at:
            admit(item, reads, who, path)
        return
    if not isinstance(at, dict) or path not in reads:
        return
    known = reads[path]
    for key, value in at.items():
        if key in known:
            if isinstance(value, (dict, list)):
                admit(value, reads, who, f"{path}.{key}" if path else key)
        elif not says_nothing(value):
            raise ValueError(f"{path}.{key}".lstrip(".") + f": the reference "
                             f"{who} does not read it, and a decision can "
                             f"turn on it")


def extended(reads, more):
    """`reads` with the keys of `more` added path by path: what a
    reference that extends this one answers for."""
    out = {path: set(keys) for path, keys in reads.items()}
    for path, keys in more.items():
        out.setdefault(path, set()).update(keys)
    return out


class PodFacts:
    """What the reference reads from a pod manifest."""
    __slots__ = ("name", "cpu", "mem", "labels", "anti")

    #: path -> the keys there that this class reads, or knows to be the
    #: same on every node; any other key that says something is refused
    reads = {
        "": {"apiVersion", "kind", "metadata", "spec"},
        "metadata": {"name", "namespace", "labels"},
        "spec": {"containers", "affinity"},
        "spec.containers": {"name", "image", "ports", "resources"},
        "spec.containers.ports": {"containerPort"},
        "spec.containers.resources": {"requests", "limits"},
        "spec.containers.resources.requests": {"cpu", "memory"},
        "spec.containers.resources.limits": {"cpu", "memory"},
        "spec.affinity": {"podAntiAffinity"},
        _ANTI: {REQUIRED},
        f"{_ANTI}.{REQUIRED}": {"labelSelector", "topologyKey"},
        f"{_ANTI}.{REQUIRED}.labelSelector": {"matchLabels"},
    }
    #: f32 words a node, beyond the six every pod needs and one for each
    #: term of `anti`, that the chip has to read to decide this pod
    #: (harness/roofline.py); a reference that adds a predicate counts
    #: its words here
    extra_words = 0

    def __init__(self, manifest):
        who = type(self).__module__
        admit(manifest, self.reads, who)
        meta = manifest["metadata"]
        if meta.get("namespace", "default") != "default":
            raise ValueError(f"metadata.namespace {meta['namespace']!r}: "
                             f"the reference {who} holds one namespace")
        self.name = meta["name"]
        self.labels = meta.get("labels", {})
        self.cpu = self.mem = 0
        for c in manifest["spec"]["containers"]:
            req = c.get("resources", {}).get("requests", {})
            self.cpu += milli(req.get("cpu", "0"))
            self.mem += quantity(req.get("memory", "0"))
        self.anti = []
        anti = (manifest["spec"].get("affinity") or {}).get(
            "podAntiAffinity") or {}
        for t in anti.get(REQUIRED) or []:
            if t["topologyKey"] != HOSTNAME:
                raise ValueError(
                    f"{_ANTI}.{REQUIRED}.topologyKey {t['topologyKey']!r}: "
                    f"the reference {who} holds hostname anti-affinity only")
            self.anti.append(tuple(sorted(
                t["labelSelector"]["matchLabels"].items())))


class Reference:
    #: what it reads from a pod manifest
    Facts = PodFacts
    #: path -> the keys of a node manifest it reads (see PodFacts.reads):
    #: nothing under `spec`, so no taint and no `unschedulable`
    reads = {
        "": {"apiVersion", "kind", "metadata", "spec", "status"},
        "metadata": {"name", "labels"},
        "spec": set(),
        "status": {"capacity", "allocatable", "conditions"},
        "status.capacity": {"cpu", "memory", "pods"},
        "status.allocatable": {"cpu", "memory", "pods"},
        "status.conditions": {"type", "status"},
    }

    def __init__(self, nodes, precision="exact", objects=()):
        who = type(self).__module__
        for n in nodes:
            admit(n, self.reads, who)
            for c in n["status"].get("conditions", []):
                if (c.get("type"), c.get("status")) != ("Ready", "True"):
                    raise ValueError(
                        f"status.conditions {c}: the reference {who} "
                        f"holds nodes that are Ready and nothing else")
        self.read_objects(objects)
        self.names = [n["metadata"]["name"] for n in nodes]
        self.row = {name: i for i, name in enumerate(self.names)}
        alloc = [n["status"]["allocatable"] for n in nodes]
        self.cap_cpu = np.array([milli(a["cpu"]) for a in alloc], np.int64)
        self.cap_mem = np.array([quantity(a["memory"]) for a in alloc],
                                np.int64)
        self.cap_pods = np.array([int(a["pods"]) for a in alloc], np.int64)
        n = len(nodes)
        self.cpu = np.zeros(n, np.int64)
        self.mem = np.zeros(n, np.int64)
        self.pods = np.zeros(n, np.int64)
        #: selector -> [N] some pod on the node matches it / carries it
        self.matched = {}
        self.carried = {}
        #: labels -> (selectors known then, those of them the labels match)
        self._match_memo = {}
        self.precision = precision

    def read_objects(self, objects):
        """The configuration's set-up objects (a Service, a PriorityClass):
        this class reads none, so it takes none."""
        if objects:
            raise ValueError(
                f"set-up objects {[o.get('kind') for o in objects]}: the "
                f"reference {type(self).__module__} reads none")

    # ------------------------------------------------------------ fit

    def _selector_row(self, table, sel):
        row = table.get(sel)
        if row is None:
            row = table[sel] = np.zeros(len(self.names), bool)
        return row

    def _matching(self, labels):
        """The selectors seen so far that these labels satisfy."""
        key = tuple(sorted(labels.items()))
        known = len(self.matched)
        memo = self._match_memo.get(key)
        if memo is None or memo[0] != known:
            memo = (known, [sel for sel in self.matched if all(
                labels.get(k) == v for k, v in sel)])
            self._match_memo[key] = memo
        return memo[1]

    def fits(self, pod):
        if self.precision in ("exact", "int8"):
            ok = (self.cpu + pod.cpu <= self.cap_cpu) \
                & (self.mem + pod.mem <= self.cap_mem)
        else:
            t = _dtype(self.precision)
            ok = ((self.cpu.astype(t) + t(pod.cpu)) <= self.cap_cpu.astype(t)) \
                & ((self.mem.astype(t) + t(pod.mem))
                   <= self.cap_mem.astype(t))
        ok &= self.pods + 1 <= self.cap_pods
        for sel in pod.anti:
            ok &= ~self._selector_row(self.matched, sel)
        for sel in self._matching(pod.labels):
            row = self.carried.get(sel)
            if row is not None:
                ok &= ~row
        return ok

    # ---------------------------------------------------------- score

    def scores(self, pod):
        if self.precision == "int8":
            return self._scores_int8(pod)
        if self.precision != "exact":
            return self._scores_low(pod, _dtype(self.precision))
        cpu = self.cpu + pod.cpu
        mem = self.mem + pod.mem
        lr_c = np.where(cpu > self.cap_cpu, 0,
                        (self.cap_cpu - cpu) * MAX_PRIORITY // self.cap_cpu)
        lr_m = np.where(mem > self.cap_mem, 0,
                        (self.cap_mem - mem) * MAX_PRIORITY // self.cap_mem)
        least = (lr_c + lr_m) // 2
        cf = cpu.astype(np.float64) / self.cap_cpu.astype(np.float64)
        mf = mem.astype(np.float64) / self.cap_mem.astype(np.float64)
        balanced = ((1.0 - np.abs(cf - mf)) * float(MAX_PRIORITY)) \
            .astype(np.int64)
        balanced = np.where((cf >= 1) | (mf >= 1), 0, balanced)
        return least + balanced

    def _scores_low(self, pod, t):
        """The same formulas with every operand and result in `t`."""
        ten = t(MAX_PRIORITY)
        cap_c, cap_m = self.cap_cpu.astype(t), self.cap_mem.astype(t)
        cpu = self.cpu.astype(t) + t(pod.cpu)
        mem = self.mem.astype(t) + t(pod.mem)
        lr_c = np.where(cpu > cap_c, t(0),
                        np.floor((cap_c - cpu) * ten / cap_c))
        lr_m = np.where(mem > cap_m, t(0),
                        np.floor((cap_m - mem) * ten / cap_m))
        least = np.floor((lr_c + lr_m) / t(2))
        cf, mf = cpu / cap_c, mem / cap_m
        balanced = np.floor((t(1) - np.abs(cf - mf)) * ten)
        balanced = np.where((cf >= t(1)) | (mf >= t(1)), t(0), balanced)
        return (least + balanced).astype(np.float64).astype(np.int64)

    def _scores_int8(self, pod):
        """The same formulas over fractions held in 8 bits: requested /
        allocatable rounded to steps of 1/127, float32 around them."""
        t = np.float32
        steps, ten = t(127), t(MAX_PRIORITY)
        cf = np.round((self.cpu + pod.cpu).astype(t)
                      / self.cap_cpu.astype(t) * steps) / steps
        mf = np.round((self.mem + pod.mem).astype(t)
                      / self.cap_mem.astype(t) * steps) / steps
        lr_c = np.where(cf > 1, t(0), np.floor((t(1) - cf) * ten))
        lr_m = np.where(mf > 1, t(0), np.floor((t(1) - mf) * ten))
        least = np.floor((lr_c + lr_m) / t(2))
        balanced = np.floor((t(1) - np.abs(cf - mf)) * ten)
        balanced = np.where((cf >= 1) | (mf >= 1), t(0), balanced)
        return (least + balanced).astype(np.int64)

    # --------------------------------------------------------- replay

    def judge(self, pod, node_name):
        """(fits, gap) of binding `pod` to `node_name` now."""
        row = self.row.get(node_name)
        if row is None:
            return False, 0
        ok = self.fits(pod)
        if not ok[row]:
            return False, 0
        s = self.scores(pod)
        return True, int(s[ok].max() - s[row])

    def decide(self, pod):
        """The serial scheduler's own pick: first node of the best score
        among those that fit, or None."""
        ok = self.fits(pod)
        if not ok.any():
            return None
        s = np.where(ok, self.scores(pod), -1)
        return self.names[int(np.argmax(s))]

    def bind(self, pod, node_name):
        row = self.row[node_name]
        self.cpu[row] += pod.cpu
        self.mem[row] += pod.mem
        self.pods[row] += 1
        for sel in pod.anti:
            self._selector_row(self.carried, sel)[row] = True
            self._selector_row(self.matched, sel)
        for sel in self._matching(pod.labels):
            self.matched[sel][row] = True

    def over_allocatable(self):
        return int(((self.cpu > self.cap_cpu) | (self.mem > self.cap_mem)
                    | (self.pods > self.cap_pods)).sum())

    @classmethod
    def replay(cls, nodes, pods_in_order, bound_node, precision="exact",
               objects=()):
        """Replay binds in decision order. pods_in_order: manifests,
        creation order; bound_node: name -> node the hub lists. Returns
        the numbers compared: the widest score gap, how many binds did not
        fit, how many pods have no node, and nodes over allocatable at the
        end."""
        ref = cls(nodes, precision, objects)
        gap_max = misfit = unbound = gapped = 0
        worst = None
        for m in pods_in_order:
            pod = cls.Facts(m)
            node = bound_node.get(pod.name)
            if not node:
                unbound += 1
                continue
            fit, gap = ref.judge(pod, node)
            if not fit:
                misfit += 1
                if node not in ref.row:
                    continue
            elif gap > 0:
                gapped += 1
                if gap > gap_max:
                    gap_max, worst = gap, (pod.name, node)
            ref.bind(pod, node)
        return {"score_gap_max": gap_max, "binds_with_gap": gapped,
                "binds_that_do_not_fit": misfit, "pods_without_node": unbound,
                "nodes_over_allocatable": ref.over_allocatable(),
                "replayed": len(pods_in_order), "worst": worst}


def _dtype(precision):
    if precision == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    return {"float32": np.float32}[precision]


replay = Reference.replay
