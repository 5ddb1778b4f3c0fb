"""The plain reference of `sched-perf-5000n-nodeaffinity`:
harness/reference.py extended by one predicate, as a serial scheduler
computes it for pods that carry a required node-affinity term on the
zone label, and imported from nowhere in the program.

  fit     PodMatchNodeSelector (inside GeneralPredicates), the required
          node-affinity side: the node's labels satisfy at least one of
          the pod's nodeSelectorTerms (terms are ORed), and a term is
          satisfied when every one of its matchExpressions is (ANDed).
          The one expression held here is `In` on the zone label: the
          node carries the label and its value is in the list. A node
          without the label satisfies no such expression; a term without
          expressions, and a required field without terms, match no
          node.
  score   nothing added. NodeAffinityPriority sums the weights of the
          preferred terms a node matches; these pods have none, so it is
          0 on every node and moves no argmax. A preferred term is
          refused, never scored as 0.

What it answers for beyond the base's whitelist: `spec.affinity.
nodeAffinity.requiredDuringSchedulingIgnoredDuringExecution.
nodeSelectorTerms[].matchExpressions[]` with `key` the zone label,
`operator` `In` and `values`. Any other operator (NotIn, Exists,
DoesNotExist, Gt, Lt), any other key, `matchFields`, a preferred term
and `spec.nodeSelector` are refused with their path, and so is every
set-up object (the base's `read_objects`).

`extra_words`: one f32 a node where the pod names zones, the node's
zone id, which is what the chip has to read beyond the base's six words
to decide such a pod. Counts, never bytes: harness/roofline.py keeps
the byte model.

`precision` below exact leaves the predicate as it is (a zone id is in
a list or it is not); the base's scores carry the control.
"""

import numpy as np

from harness import reference as base

ZONE = "failure-domain.beta.kubernetes.io/zone"
_NODE = "spec.affinity.nodeAffinity"
_TERMS = f"{_NODE}.{base.REQUIRED}.nodeSelectorTerms"
_EXPR = f"{_TERMS}.matchExpressions"


class PodFacts(base.PodFacts):
    __slots__ = ("terms",)
    reads = base.extended(base.PodFacts.reads, {
        "spec.affinity": {"nodeAffinity"},
        _NODE: {base.REQUIRED},
        f"{_NODE}.{base.REQUIRED}": {"nodeSelectorTerms"},
        _TERMS: {"matchExpressions"},
        _EXPR: {"key", "operator", "values"}})

    def __init__(self, manifest):
        super().__init__(manifest)
        who = type(self).__module__
        #: None without a required node-affinity field; else its terms
        #: (ORed), each the value lists of its `In` expressions on the
        #: zone label (ANDed), as sorted tuples
        self.terms = None
        required = ((manifest["spec"].get("affinity") or {}).get(
            "nodeAffinity") or {}).get(base.REQUIRED)
        if required is None:
            return
        terms = []
        for term in required.get("nodeSelectorTerms") or []:
            lists = []
            for e in term.get("matchExpressions") or []:
                if e.get("operator") != "In":
                    raise ValueError(
                        f"{_EXPR}.operator {e.get('operator')!r}: the "
                        f"reference {who} holds `In` alone")
                if e.get("key") != ZONE:
                    raise ValueError(
                        f"{_EXPR}.key {e.get('key')!r}: the reference "
                        f"{who} holds the zone label alone")
                lists.append(tuple(sorted(e.get("values") or [])))
            terms.append(tuple(lists))
        self.terms = tuple(terms)

    @property
    def extra_words(self):
        """One more f32 a node where the pod names zones: the node's
        zone id."""
        return 0 if self.terms is None else 1


class Reference(base.Reference):
    Facts = PodFacts

    def __init__(self, nodes, precision="exact", objects=()):
        super().__init__(nodes, precision, objects)
        #: zone name -> id; [N] the node's id, -1 without the label
        self.zone_ids = {}
        self.zone = np.array([
            self.zone_ids.setdefault(z, len(self.zone_ids))
            if z is not None else -1
            for z in (n["metadata"].get("labels", {}).get(ZONE)
                      for n in nodes)], np.int64)
        #: terms -> [N] the nodes that satisfy them (labels never change
        #: in a replay, and a deployment has as many as it has selectors)
        self._allowed = {}

    def allowed(self, terms):
        """[N] nodes whose zone label satisfies one of `terms`."""
        row = self._allowed.get(terms)
        if row is None:
            row = np.zeros(len(self.names), bool)
            for lists in terms:         # ORed
                if not lists:
                    continue            # an empty term matches no node
                term_ok = np.ones(len(self.names), bool)
                for values in lists:    # ANDed
                    ids = [self.zone_ids[v] for v in values
                           if v in self.zone_ids]
                    term_ok &= np.isin(self.zone, ids)
                row |= term_ok
            self._allowed[terms] = row
        return row

    def fits(self, pod):
        ok = super().fits(pod)
        if pod.terms is not None:
            ok &= self.allowed(pod.terms)
        return ok

    @classmethod
    def replay(cls, nodes, pods_in_order, bound_node, precision="exact",
               objects=()):
        """The base's replay, and the guarantee the configuration adds,
        counted apart from `fits` and straight off the manifests: bound
        pods whose node's zone label is in no `values` list of theirs
        (limit 0; each is also a bind that does not fit)."""
        out = super().replay(nodes, pods_in_order, bound_node, precision,
                             objects)
        zone_of = {n["metadata"]["name"]:
                   n["metadata"].get("labels", {}).get(ZONE)
                   for n in nodes}
        outside = 0
        for m in pods_in_order:
            node = bound_node.get(m["metadata"]["name"])
            required = ((m["spec"].get("affinity") or {}).get(
                "nodeAffinity") or {}).get(base.REQUIRED)
            if not node or required is None:
                continue
            named = {v for t in required.get("nodeSelectorTerms") or []
                     for e in t.get("matchExpressions") or []
                     for v in e.get("values") or []}
            if zone_of.get(node) not in named:
                outside += 1
        out["pods_outside_their_zones"] = outside
        return out


replay = Reference.replay
