"""The plain reference of `e2e-load-5000n-services`: harness/reference.py
extended by one priority, SelectorSpreadPriority, as a serial scheduler
computes it for pods that a Service selects, and imported from nowhere
in the program. The first reference with a priority that reads other
pods' labels, and the first that takes set-up objects.

  fit     nothing added.
  score   LeastRequested + BalancedResourceAllocation (the base's) +
          SelectorSpreadPriority, weight 1 each as the default provider
          has them. For the incoming pod, its selectors are those of
          every Service of its namespace whose `spec.selector` its labels
          satisfy (selector_spreading.go getSelectors; no controller
          object exists here). Without one the priority is 10 on every
          node and moves no argmax. With some:

            n     the pods on the node, in the pod's namespace and not
                  terminating, that match EVERY selector of the pod
                  (countMatchingPods); nothing terminates in a replay
            maxN  the largest n over the fitting nodes (the priorities
                  run over the filtered nodes)
            z     the sum of n over the fitting nodes of the node's zone
                  (the zone label), maxZ the largest zone's
            fScore    = 10 * (float64(maxN - n) / float64(maxN)), 10 if
                        maxN is 0
            zoneScore = 10 * (float64(maxZ - z) / float64(maxZ)), 10 if
                        maxZ is 0
            int(fScore * (1 - 2.0/3.0) + 2.0/3.0 * zoneScore)

          in float64, upstream's operand order, int() truncating. No
          tolerance: one point off is a different decision.

Counts are kept sparse, label set -> {row: pods}: a group is at most as
many entries as it has pods (16,400 dense rows of 5,000 would not fit a
sensible verdict), and a pod finds its Services, and a selector set its
label sets, by a label item and not by a walk.

What it answers for beyond the base's whitelist: set-up objects of kind
Service in namespace `default` with `spec.selector` (a map) and
`spec.ports[].{port, targetPort}` (which no decision reads: the pod's
containerPort is no hostPort). Any other kind, another key of a Service
that says something, an empty selector (it selects no pod upstream) and
a node without the zone label (upstream leaves such a node's score
unblended; no deployment here has one) are refused with their path.

`extra_words`: two f32 a node where the pod carries labels, the group's
count on the node and the node's zone id: what the chip has to read
beyond the base's six words to score such a pod. Counts, never bytes:
harness/roofline.py keeps the byte model.

`precision` below exact computes the priority's fractions and blend in
that type (int8: in float32, beside the base's fractions in steps of
1/127); the base's scores carry the rest of the control. This is the
first configuration in which float32 alone reads gaps: the blend's
whole-number values land on either side of int() by the type.
"""

import numpy as np

from harness import reference as base

ZONE = "failure-domain.beta.kubernetes.io/zone"
ZONE_WEIGHTING = 2.0 / 3.0

_SERVICE_READS = {
    "": {"apiVersion", "kind", "metadata", "spec"},
    "metadata": {"name", "namespace"},
    "spec": {"selector", "ports"},
    "spec.ports": {"port", "targetPort"},
}


def _items(labels):
    return tuple(sorted(labels.items()))


class PodFacts(base.PodFacts):
    __slots__ = ()

    @property
    def extra_words(self):
        """Two more f32 a node where the pod can be selected: the
        group's count there and the node's zone id."""
        return 2 if self.labels else 0


class Reference(base.Reference):
    Facts = PodFacts

    def __init__(self, nodes, precision="exact", objects=()):
        super().__init__(nodes, precision, objects)
        who = type(self).__module__
        zones = [n["metadata"].get("labels", {}).get(ZONE) for n in nodes]
        if self.services and not all(zones):
            raise ValueError(
                f"metadata.labels: a node without {ZONE}: the reference "
                f"{who} holds zoned nodes alone")
        ids = {}
        #: [N] the node's zone id
        self.zone = np.array([ids.setdefault(z, len(ids)) for z in zones],
                             np.int64)
        self.n_zones = max(1, len(ids))
        #: label items -> {row: pods with exactly these labels}
        self.on_node = {}
        #: one label item -> the label sets that hold it
        self.sets_with = {}
        #: the most pods of one label set on one node, ever
        self.node_count_max = 0

    # -------------------------------------------------------- Services

    def read_objects(self, objects):
        """Services of namespace `default`, filed under the first of the
        sorted items of their selector."""
        who = type(self).__module__
        #: item -> [selector items], of the Services whose selector's
        #: first sorted item it is
        self.services = {}
        for o in objects:
            if o.get("kind") != "Service":
                raise ValueError(f"set-up object of kind {o.get('kind')!r}: "
                                 f"the reference {who} reads Services alone")
            base.admit(o, _SERVICE_READS, who)
            ns = o["metadata"].get("namespace", "default")
            if ns != "default":
                raise ValueError(f"Service metadata.namespace {ns!r}: the "
                                 f"reference {who} holds one namespace")
            selector = o.get("spec", {}).get("selector")
            if not selector:
                raise ValueError(
                    f"Service {o['metadata'].get('name')!r} spec.selector: "
                    f"empty; the reference {who} holds map selectors")
            items = _items(selector)
            self.services.setdefault(items[0], []).append(items)

    def selectors(self, pod):
        """The selector of every Service the pod's labels satisfy."""
        labels = pod.labels
        return [items for item in _items(labels)
                for items in self.services.get(item, ())
                if all(labels.get(k) == v for k, v in items)]

    def counts(self, selectors):
        """[N] pods on the node whose labels satisfy every selector."""
        out = np.zeros(len(self.names), np.int64)
        for key in self.sets_with.get(selectors[0][0], ()):
            labels = dict(key)
            if all(labels.get(k) == v for items in selectors
                   for k, v in items):
                for row, c in self.on_node[key].items():
                    out[row] += c
        return out

    # ----------------------------------------------------------- score

    def spread(self, pod, ok):
        """[N] SelectorSpreadPriority over the fitting nodes `ok`, or
        None where no Service selects the pod (10 everywhere)."""
        selectors = self.selectors(pod)
        if not selectors:
            return None
        n = self.counts(selectors)
        t = np.float64 if self.precision == "exact" else \
            np.float32 if self.precision == "int8" else \
            base._dtype(self.precision)
        ten = t(base.MAX_PRIORITY)
        fit_n = np.where(ok, n, 0)
        max_n = int(fit_n.max()) if ok.any() else 0
        f = np.full(len(n), ten, t)
        if max_n > 0:
            f = ten * ((t(max_n) - n.astype(t)) / t(max_n))
        by_zone = np.bincount(self.zone, weights=fit_n,
                              minlength=self.n_zones).astype(np.int64)
        max_z = int(by_zone.max())
        zone_score = np.full(len(n), ten, t)
        if max_z > 0:
            zone_score = ten * ((t(max_z) - by_zone[self.zone].astype(t))
                                / t(max_z))
        w = t(ZONE_WEIGHTING)
        blended = (f * (t(1.0) - w)) + (w * zone_score)
        return np.trunc(blended.astype(np.float64)).astype(np.int64)

    def scores(self, pod):
        s = super().scores(pod)
        spread = self.spread(pod, self.fits(pod))
        return s if spread is None else s + spread

    # ---------------------------------------------------------- replay

    def bind(self, pod, node_name):
        super().bind(pod, node_name)
        if not pod.labels:
            return
        key = _items(pod.labels)
        per_node = self.on_node.get(key)
        if per_node is None:
            per_node = self.on_node[key] = {}
            for item in key:
                self.sets_with.setdefault(item, set()).add(key)
        row = self.row[node_name]
        per_node[row] = per_node.get(row, 0) + 1
        self.node_count_max = max(self.node_count_max, per_node[row])

    @classmethod
    def replay(cls, nodes, pods_in_order, bound_node, precision="exact",
               objects=()):
        """The base's replay, and what the configuration's size rests on,
        counted off the manifests apart from the scores: the Services
        that selected a bound pod, and the most pods of one group that
        one node and one zone took (the counts the kernel's score has to
        hold exactly)."""
        out = super().replay(nodes, pods_in_order, bound_node, precision,
                             objects)
        zone_of = {n["metadata"]["name"]:
                   n["metadata"].get("labels", {}).get(ZONE) for n in nodes}
        per_node, per_zone = {}, {}
        for m in pods_in_order:
            node = bound_node.get(m["metadata"]["name"])
            group = _items(m["metadata"].get("labels") or {})
            if not node or not group:
                continue
            per_node[group, node] = per_node.get((group, node), 0) + 1
            z = group, zone_of.get(node)
            per_zone[z] = per_zone.get(z, 0) + 1
        out["spread_groups_bound"] = len({g for g, _ in per_zone})
        out["group_pods_on_one_node_max"] = max(per_node.values(), default=0)
        out["group_pods_in_one_zone_max"] = max(per_zone.values(), default=0)
        return out


replay = Reference.replay
