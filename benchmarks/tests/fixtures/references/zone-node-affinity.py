"""A test's reference (benchmarks/tests/test_seams.py; no configuration
names it): harness/reference.py extended by one predicate, required node
affinity on the zone label with operator In, and by a Service it takes
as a set-up object. It shows what a configuration's own reference has to
write: the keys it answers for, added to `reads`; the predicate; the
words a node that the predicate needs (`extra_words`, which
harness/roofline.py turns into bytes); and `replay` bound to its
classes."""

import numpy as np

from harness import reference as base

ZONE = "failure-domain.beta.kubernetes.io/zone"
_NODE = "spec.affinity.nodeAffinity"
_FIELD = f"{_NODE}.{base.REQUIRED}"


class PodFacts(base.PodFacts):
    __slots__ = ("zones",)
    reads = base.extended(base.PodFacts.reads, {
        "spec.affinity": {"nodeAffinity"},
        _NODE: {base.REQUIRED},
        _FIELD: {"nodeSelectorTerms"},
        f"{_FIELD}.nodeSelectorTerms": {"matchExpressions"},
        f"{_FIELD}.nodeSelectorTerms.matchExpressions":
            {"key", "operator", "values"}})

    def __init__(self, manifest):
        super().__init__(manifest)
        terms = manifest["spec"].get("affinity", {}).get(
            "nodeAffinity", {}).get(base.REQUIRED, {}).get(
            "nodeSelectorTerms", [])
        self.zones = None
        for term in terms:      # terms are ORed, a term's expressions ANDed
            exprs = term.get("matchExpressions", [])
            if len(exprs) != 1 or exprs[0]["key"] != ZONE \
                    or exprs[0]["operator"] != "In":
                raise ValueError(f"{_FIELD}: one `In` on the zone label a "
                                 f"term is all this reference holds")
            self.zones = (self.zones or set()) | set(exprs[0]["values"])

    @property
    def extra_words(self):
        """One more f32 a node where the pod names zones: the node's
        zone id."""
        return 0 if self.zones is None else 1


class Reference(base.Reference):
    Facts = PodFacts

    def __init__(self, nodes, precision="exact", objects=()):
        super().__init__(nodes, precision, objects)
        self.zone = np.array([n["metadata"]["labels"].get(ZONE, "")
                              for n in nodes])

    def read_objects(self, objects):
        # a Service selects pods for SelectorSpread; with one Service over
        # every pod and no preferred weight it moves no argmax here
        other = [o["kind"] for o in objects if o["kind"] != "Service"]
        if other:
            raise ValueError(f"set-up objects {other}: not read")
        self.services = list(objects)

    def fits(self, pod):
        ok = super().fits(pod)
        if pod.zones is not None:
            ok &= np.isin(self.zone, sorted(pod.zones))
        return ok


replay = Reference.replay

