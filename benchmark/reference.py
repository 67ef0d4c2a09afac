"""The plain reference: a sequential scheduler over wire objects, in numpy.

It imports nothing of the program and takes nothing the program made.  It
follows the published semantics of the default provider of
``plugin/pkg/scheduler`` (predicates.go, priorities/, generic_scheduler.go
``selectHost``) in the fixed-point form this repository documents as its
spec (``kubernetes_tpu/scheduler/units.py``, ``priorities.py`` docstrings):
integer millicores and MiB (rounded up), 0..10 integer scores, 10-bit fixed
point for fractions, ties broken round-robin over the tied nodes in node-name
order by a counter that advances once per pod that had two or more feasible
nodes.

One decision is vectorised over the node axis; pods are taken one at a time,
in the order given.  Pods are grouped into classes (namespace, labels and the
scheduling part of the spec) so that a label selector is matched once per
class, never once per pod.

``request_bits`` is the control of "How ``correct`` is decided": the pod's own
request vector is rounded to that many mantissa bits (8 = bfloat16) wherever
it enters a decision, the rounding Mosaic's one-pass f32 matmul applies to a
one-hot gather (PERF.md section 6, PR 21).  ``None`` is exact.

Not implemented, and refused loudly rather than ignored: host ports, a
pre-set ``spec.nodeName``, PVC-backed volumes, node affinity, the
prefer-avoid-pods annotation, image locality.  No configuration of the
benchmark uses them.
"""

from __future__ import annotations

import json
from fractions import Fraction
from typing import Optional

import numpy as np

MAX_PRIORITY = 10
FIXED = 1024
ZONE_LABEL = "failure-domain.beta.kubernetes.io/zone"
REGION_LABEL = "failure-domain.beta.kubernetes.io/region"
HARD_POD_AFFINITY_WEIGHT = 1
READONLY_SHARED_KINDS = ("gce-pd", "iscsi")
VOLUME_COUNT_LIMITS = {"aws-ebs": 39, "gce-pd": 16, "azure-disk": 16}
RESOURCES = ("cpu", "memory", "ephemeral-storage", "nvidia.com/gpu")
_SUFFIX = {"Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40, "Pi": 2**50,
           "k": 10**3, "M": 10**6, "G": 10**9, "T": 10**12, "P": 10**15}


class Unsupported(ValueError):
    """The object uses a feature the reference does not implement."""


def parse_quantity(text) -> Fraction:
    s = str(text).strip()
    if s.endswith("m"):
        return Fraction(s[:-1]) / 1000
    for suffix, mult in _SUFFIX.items():
        if s.endswith(suffix):
            return Fraction(s[: -len(suffix)]) * mult
    return Fraction(s)


def _ceil(f: Fraction) -> int:
    return -((-f.numerator) // f.denominator)


def to_units(resource: str, text) -> int:
    """cpu -> millicores, memory/storage -> MiB rounded up, else a count."""
    q = parse_quantity(text)
    if resource == "cpu":
        return _ceil(q * 1000)
    if resource in ("memory", "ephemeral-storage"):
        return _ceil(q / 2**20)
    return _ceil(q)


def round_to_bits(value: int, bits: Optional[int]) -> int:
    """Round a non-negative integer to ``bits`` mantissa bits, ties to even."""
    if bits is None or value < (1 << bits):
        return value
    shift = value.bit_length() - bits
    q, r = value >> shift, value & ((1 << shift) - 1)
    half = 1 << (shift - 1)
    if r > half or (r == half and q & 1):
        q += 1
    return q << shift


def _selector_matches(sel: Optional[dict], labels: dict) -> bool:
    """metav1.LabelSelector; ``None`` matches nothing, ``{}`` everything."""
    if sel is None:
        return False
    for k, v in (sel.get("matchLabels") or {}).items():
        if labels.get(k) != v:
            return False
    for req in sel.get("matchExpressions") or []:
        key, op, vals = req["key"], req["operator"], req.get("values") or []
        if op == "In":
            ok = labels.get(key) in vals and key in labels
        elif op == "NotIn":
            ok = labels.get(key) not in vals
        elif op == "Exists":
            ok = key in labels
        elif op == "DoesNotExist":
            ok = key not in labels
        else:
            raise Unsupported(f"selector operator {op!r}")
        if not ok:
            return False
    return True


def _tolerates(tol: dict, taint: dict) -> bool:
    if tol.get("effect") and tol["effect"] != taint.get("effect", "NoSchedule"):
        return False
    if tol.get("key") and tol["key"] != taint.get("key", ""):
        return False
    if tol.get("operator") == "Exists":
        return True
    return tol.get("value", "") == taint.get("value", "")


class PodClass:
    """What every pod with the same namespace, labels and scheduling spec
    shares.  Volumes are per pod and stay outside."""

    def __init__(self, cid: int, ns: str, labels: dict, spec: dict,
                 request_bits: Optional[int]):
        self.cid = cid
        self.ns = ns
        self.labels = labels
        if spec.get("nodeName"):
            raise Unsupported("spec.nodeName is set")
        req = [0, 0, 0, 0]
        nz = [0, 0]
        any_request = False
        for c in spec.get("containers") or []:
            if c.get("ports"):
                for p in c["ports"]:
                    if p.get("hostPort"):
                        raise Unsupported("hostPort")
            res = c.get("resources") or {}
            requests = res.get("requests") or {}
            units = [0, 0, 0, 0]
            for name, q in requests.items():
                if name in RESOURCES:
                    units[RESOURCES.index(name)] += to_units(name, q)
            any_request |= any(units[:2]) or any(
                to_units(n, q) for n, q in (res.get("limits") or {}).items()
                if n in ("cpu", "memory"))
            for i in range(4):
                req[i] += units[i]
            nz[0] += units[0] or 100
            nz[1] += units[1] or 200
        self.req = [round_to_bits(v, request_bits) for v in req]
        self.nz = [round_to_bits(v, request_bits) for v in nz]
        self.exact_req, self.exact_nz = req, nz
        self.best_effort = not any_request
        self.node_selector = spec.get("nodeSelector") or {}
        self.tolerations = spec.get("tolerations") or []
        # what the node masks and taint scores depend on: classes that share
        # them share one memoised array
        self.taint_key = json.dumps(self.tolerations, sort_keys=True)
        self.static_key = json.dumps(
            [self.taint_key, self.best_effort, self.node_selector], sort_keys=True)
        aff = spec.get("affinity") or {}
        if aff.get("nodeAffinityRequired") or aff.get("nodeAffinityPreferred"):
            raise Unsupported("node affinity")
        self.terms = (
            [Term(self, t, AFF_REQUIRED, HARD_POD_AFFINITY_WEIGHT)
             for t in aff.get("podAffinityRequired") or []]
            + [Term(self, t, ANTI_REQUIRED, 0)
               for t in aff.get("podAntiAffinityRequired") or []]
            + [Term(self, w["podAffinityTerm"], AFF_PREFERRED, w["weight"])
               for w in aff.get("podAffinityPreferred") or []]
            + [Term(self, w["podAffinityTerm"], ANTI_PREFERRED, -w["weight"])
               for w in aff.get("podAntiAffinityPreferred") or []])
        # kept by the Reference as classes appear and pods are placed
        self.services: tuple = ()       # indices of the services selecting it
        self.selected_by: list = []     # every Term whose scope holds it
        self.placed: list = []          # node of each placed pod
        self.counted_in: list = []      # per-node counts that hold its pods
        self.own: Optional[np.ndarray] = None   # its pods per node, if it owns a term


AFF_REQUIRED, ANTI_REQUIRED, AFF_PREFERRED, ANTI_PREFERRED = range(4)


class Term:
    """One inter-pod (anti-)affinity term of a class.  ``weight`` is signed
    (anti-affinity negative; a required affinity term's is the symmetric
    weight of priorities/interpod_affinity.go); ``count`` is the per-node
    number of placed pods in the term's scope, kept by the Reference."""

    def __init__(self, owner: PodClass, term: dict, kind: int, weight: int):
        self.owner, self.term, self.kind, self.weight = owner, term, kind, weight
        self.key = term.get("topologyKey", "")
        self.count: Optional[np.ndarray] = None
        self.selects_owner = False

    def selects(self, cand: PodClass) -> bool:
        """PodMatchesTermsNamespaceAndSelector: is ``cand`` in the scope?"""
        if cand.ns not in (self.term.get("namespaces") or [self.owner.ns]):
            return False
        return _selector_matches(self.term.get("labelSelector"), cand.labels)


class Reference:
    """Cluster state and one-pod decisions.  ``nodes`` and ``services`` are
    wire objects; the node axis is sorted by name.

    A decision reads per-node counts that ``place`` keeps current, so it
    walks no list of classes or services: the count of the pods the class's
    services select (made when a class with those services is first scored),
    each term's count and each term owner's own.  A count made after pods were
    placed sums their classes once; ``visits`` counts the classes so summed
    or checked against a new term."""

    def __init__(self, nodes: list, services: list,
                 request_bits: Optional[int] = None):
        nodes = sorted(nodes, key=lambda n: n["metadata"]["name"])
        self.request_bits = request_bits
        self.names = [n["metadata"]["name"] for n in nodes]
        self.index = {name: i for i, name in enumerate(self.names)}
        n = self.n = len(nodes)
        self.labels = [n_["metadata"].get("labels") or {} for n_ in nodes]
        alloc = np.zeros((4, n), np.int64)
        self.alloc_pods = np.zeros(n, np.int64)
        usable = np.ones(n, bool)
        self.mem_pressure = np.zeros(n, bool)
        taint_sets: dict = {}
        self.taint_set_of = np.zeros(n, np.int64)
        for i, node in enumerate(nodes):
            if (node["metadata"].get("annotations") or {}).get(
                    "scheduler.alpha.kubernetes.io/preferAvoidPods"):
                raise Unsupported("preferAvoidPods annotation")
            status = node.get("status") or {}
            if status.get("images"):
                raise Unsupported("node images (image locality)")
            al = status.get("allocatable") or {}
            for r, name in enumerate(RESOURCES):
                if name in al:
                    alloc[r, i] = to_units(name, al[name])
            self.alloc_pods[i] = to_units("pods", al["pods"]) if "pods" in al else 110
            spec = node.get("spec") or {}
            if spec.get("unschedulable"):
                usable[i] = False
            for cond in status.get("conditions") or []:
                if cond["type"] == "Ready" and cond["status"] != "True":
                    usable[i] = False
                if cond["type"] == "DiskPressure" and cond["status"] == "True":
                    usable[i] = False
                if cond["type"] == "MemoryPressure" and cond["status"] == "True":
                    self.mem_pressure[i] = True
            key = json.dumps(spec.get("taints") or [], sort_keys=True)
            self.taint_set_of[i] = taint_sets.setdefault(key, len(taint_sets))
        self.taint_sets = [json.loads(k) for k in taint_sets]
        self.alloc = alloc
        self.usable = usable
        self.free = alloc.copy()            # allocatable - requested
        self.nz_used = np.zeros((2, n), np.int64)
        self.count = np.zeros(n, np.int64)
        self.services = [((s["metadata"].get("namespace") or "default"),
                          (s.get("spec") or {}).get("selector") or {})
                         for s in services]
        self.services = [s for s in self.services if s[1]]
        # a service is found through one (namespace, key, value) of its
        # selector; its classes and the spread counts it takes part in are
        # extended as classes appear
        self._services_by_pair: dict = {}
        for s, (ns, sel) in enumerate(self.services):
            self._services_by_pair.setdefault((ns, *min(sel.items())), []).append(s)
        self._classes_of_service = [[] for _ in self.services]
        self._spreads_of_service = [[] for _ in self.services]
        self._spreads: dict = {}            # services -> per-node count
        self._terms: list = []
        self.visits = 0
        self.round_robin = 0
        self._classes: dict = {}
        self._class_of_pod: dict = {}
        self.classes: list = []
        self._static: dict = {}
        self._topo: dict = {}
        self._memo: dict = {}
        # disks: (kind, id) -> [(node, read_only)], kind -> ids per node
        self.disk_users: dict = {}
        self.kind_ids = {k: [set() for _ in range(n)] for k in VOLUME_COUNT_LIMITS}
        # zone key of utilnode.GetZoneKey: "" when neither label is there
        zone_ids: dict = {}
        self.zone = np.full(n, -1, np.int64)
        for i, lab in enumerate(self.labels):
            region, zone = lab.get(REGION_LABEL, ""), lab.get(ZONE_LABEL, "")
            if region or zone:
                self.zone[i] = zone_ids.setdefault(f"{region}:{zone}", len(zone_ids))
        self.n_zones = len(zone_ids)

    # -- classes and memoised per-class facts --------------------------------
    def class_of(self, pod: dict) -> PodClass:
        meta, spec = pod["metadata"], pod["spec"]
        pod_key = (meta.get("namespace") or "default", meta["name"])
        cls = self._class_of_pod.get(pod_key)
        if cls is None:
            cls = self._class_of_pod[pod_key] = self._class_of_spec(meta, spec)
        return cls

    def _class_of_spec(self, meta: dict, spec: dict) -> PodClass:
        key = json.dumps(
            [meta.get("namespace") or "default", meta.get("labels") or {},
             [(c.get("resources") or {}, c.get("ports") or [])
              for c in spec.get("containers") or []],
             spec.get("nodeName") or "", spec.get("nodeSelector") or {},
             spec.get("affinity"), spec.get("tolerations") or []],
            sort_keys=True)
        cls = self._classes.get(key)
        if cls is None:
            cls = PodClass(len(self.classes), meta.get("namespace") or "default",
                           meta.get("labels") or {}, spec, self.request_bits)
            self._add_class(cls)
            self._classes[key] = cls
        return cls

    def _add_class(self, cls: PodClass) -> None:
        """Enter a new class (it has no pods placed yet) in the counts that
        hold it, and make the counts of its own terms."""
        found = [s for k, v in cls.labels.items()
                 for s in self._services_by_pair.get((cls.ns, k, v), ())
                 if all(cls.labels.get(a) == b
                        for a, b in self.services[s][1].items())]
        cls.services = tuple(sorted(found))
        joined: set = set()
        for s in cls.services:
            self._classes_of_service[s].append(cls)
            for count in self._spreads_of_service[s]:
                if id(count) not in joined:
                    joined.add(id(count))
                    cls.counted_in.append(count)
        for term in self._terms:
            if term.selects(cls):
                cls.selected_by.append(term)
                cls.counted_in.append(term.count)
        self.classes.append(cls)
        for term in cls.terms:
            self.visits += len(self.classes)
            scope = [c for c in self.classes if term.selects(c)]
            term.count = self._count(scope)
            term.selects_owner = cls in scope
            for c in scope:
                c.selected_by.append(term)
            self._terms.append(term)
        if cls.terms:
            cls.own = self._count([cls])

    def _count(self, classes: list) -> np.ndarray:
        """Per-node number of placed pods of ``classes``, kept by ``place``
        from now on."""
        count = np.zeros(self.n, np.int32)
        for c in classes:
            np.add.at(count, c.placed, 1)
            c.counted_in.append(count)
        return count

    def _topology(self, key: str) -> tuple:
        """(value id per node or -1, number of values) for a label key."""
        got = self._topo.get(key)
        if got is None:
            ids: dict = {}
            vals = np.full(self.n, -1, np.int64)
            for i, lab in enumerate(self.labels):
                if key in lab:
                    vals[i] = ids.setdefault(lab[key], len(ids))
            got = self._topo[key] = (vals, len(ids))
        return got

    def _static_mask(self, cls: PodClass) -> np.ndarray:
        """Node conditions, taints and the node selector: what no placement
        changes."""
        got = self._static.get(cls.static_key)
        if got is None:
            set_ok = np.array([
                all(any(_tolerates(t, taint) for t in cls.tolerations)
                    for taint in taints
                    if taint.get("effect", "NoSchedule") in ("NoSchedule", "NoExecute"))
                for taints in self.taint_sets], bool)
            got = self.usable & set_ok[self.taint_set_of]
            if cls.best_effort:
                got = got & ~self.mem_pressure
            if cls.node_selector:
                got = got & np.array([
                    all(lab.get(k) == v for k, v in cls.node_selector.items())
                    for lab in self.labels], bool)
            self._static[cls.static_key] = got
        return got

    def _taint_score_counts(self, cls: PodClass) -> np.ndarray:
        key = ("taintscore", cls.taint_key)
        got = self._memo.get(key)
        if got is None:
            per_set = np.array([
                sum(1 for taint in taints
                    if taint.get("effect", "NoSchedule") == "PreferNoSchedule"
                    and not any(_tolerates(t, taint) for t in cls.tolerations))
                for taints in self.taint_sets], np.int64)
            got = self._memo[key] = per_set[self.taint_set_of]
        return got

    # -- feasibility ---------------------------------------------------------
    def feasible(self, pod: dict) -> np.ndarray:
        cls = self.class_of(pod)
        ok = self._static_mask(cls) & (self.count < self.alloc_pods)
        for r in range(4):
            if cls.req[r] > 0:
                ok &= self.free[r] >= cls.req[r]
        volumes = (pod["spec"].get("volumes") or [])
        if volumes:
            ok = ok & self._volumes_ok(volumes)
        # inter-pod affinity: symmetry first (existing pods' required
        # anti-affinity that selects this pod), then the pod's own terms
        for term in cls.selected_by:
            if term.kind == ANTI_REQUIRED:
                hosts = term.owner.own > 0
                if hosts.any():
                    ok = ok & ~self._same_domain(term.key, hosts,
                                                 empty_key_everywhere=True)
        for term in cls.terms:
            if term.kind not in (AFF_REQUIRED, ANTI_REQUIRED):
                continue
            if not term.key:
                return np.zeros(self.n, bool)
            # nodes that hold a pod in the term's scope
            hosts = term.count > 0
            if term.kind == ANTI_REQUIRED:
                ok = ok & ~self._same_domain(term.key, hosts)
            elif hosts.any() or not term.selects_owner:
                ok = ok & self._same_domain(term.key, hosts)
        return ok

    def _same_domain(self, key: str, hosts: np.ndarray,
                     empty_key_everywhere: bool = False) -> np.ndarray:
        """Nodes that carry ``key`` with a value some node of ``hosts``
        carries too (NodesHaveSameTopologyKey)."""
        if not key:
            return np.full(self.n, empty_key_everywhere)
        vals, n_vals = self._topology(key)
        taken = np.zeros(n_vals + 1, bool)
        taken[vals[hosts & (vals >= 0)]] = True
        taken[n_vals] = False
        return taken[np.where(vals >= 0, vals, n_vals)]

    def _volumes_ok(self, volumes: list) -> np.ndarray:
        ok = np.ones(self.n, bool)
        new_ids: dict = {}
        for vol in volumes:
            if vol.get("pvcName"):
                raise Unsupported("PVC-backed volume")
            disk, kind = vol.get("diskID"), vol.get("diskKind", "")
            if not disk:
                continue
            for node, read_only in self.disk_users.get((kind, disk), ()):
                if not (kind in READONLY_SHARED_KINDS and vol.get("readOnly")
                        and read_only):
                    ok[node] = False
            if kind in VOLUME_COUNT_LIMITS:
                new_ids.setdefault(kind, set()).add(disk)
        for kind, ids in new_ids.items():
            limit = VOLUME_COUNT_LIMITS[kind]
            have = self.kind_ids[kind]
            # only nodes near the limit can fail; most hold none
            for node in self._memo.get(("kindnodes", kind), ()):
                if len(have[node] | ids) > limit:
                    ok[node] = False
            if len(ids) > limit:
                ok[:] = False
        return ok

    # -- scores --------------------------------------------------------------
    def scores(self, pod: dict, feas: np.ndarray) -> np.ndarray:
        """Weighted integer total per node (meaningful where ``feas``)."""
        cls = self.class_of(pod)
        total = self._spread(cls, feas) + self._interpod(cls, feas)
        # least requested + balanced allocation, on non-zero requests
        cap_c, cap_m = self.alloc[0], self.alloc[1]
        req_c = self.nz_used[0] + cls.nz[0]
        req_m = self.nz_used[1] + cls.nz[1]
        safe_c, safe_m = np.maximum(cap_c, 1), np.maximum(cap_m, 1)
        least_c = np.where((cap_c == 0) | (req_c > cap_c), 0,
                           ((cap_c - req_c) * MAX_PRIORITY) // safe_c)
        least_m = np.where((cap_m == 0) | (req_m > cap_m), 0,
                           ((cap_m - req_m) * MAX_PRIORITY) // safe_m)
        total = total + (least_c + least_m) // 2
        diff = np.abs((req_c * FIXED) // safe_c - (req_m * FIXED) // safe_m)
        balanced = (MAX_PRIORITY * FIXED - diff * MAX_PRIORITY) // FIXED
        total = total + np.where(
            (cap_c == 0) | (cap_m == 0) | (req_c >= cap_c) | (req_m >= cap_m),
            0, balanced)
        # prefer-avoid-pods: no annotation anywhere, every node scores 10
        total = total + 10000 * MAX_PRIORITY
        # node affinity: refused above, scores 0
        # taint toleration: fewer intolerable PreferNoSchedule taints is better
        counts = self._taint_score_counts(cls)
        max_c = int(counts[feas].max()) if feas.any() else 0
        if max_c == 0:
            total = total + MAX_PRIORITY
        else:
            total = total + (MAX_PRIORITY * (max_c - counts)) // max_c
        return total

    def _spread_count(self, cls: PodClass) -> np.ndarray:
        """Per node, the placed pods that any service selecting ``cls``
        selects (in its namespace, since a service selects there only)."""
        got = self._spreads.get(cls.services)
        if got is None:
            members = {c.cid: c for s in cls.services
                       for c in self._classes_of_service[s]}
            self.visits += len(members)
            got = self._spreads[cls.services] = self._count(list(members.values()))
            for s in cls.services:
                self._spreads_of_service[s].append(got)
        return got

    def _spread(self, cls: PodClass, feas: np.ndarray) -> np.ndarray:
        cnt = self._spread_count(cls).astype(np.int64)
        cnt_f = np.where(feas, cnt, 0)
        max_n = int(cnt_f.max())
        full = MAX_PRIORITY * FIXED
        node_fp = ((max_n - cnt) * full) // max_n if max_n > 0 else np.full(self.n, full)
        zoned = feas & (self.zone >= 0)
        if not zoned.any():
            return node_fp // FIXED
        zone_cnt = np.bincount(self.zone[zoned], weights=cnt[zoned],
                               minlength=self.n_zones).astype(np.int64)
        max_z = int(zone_cnt.max())
        per_zone = (((max_z - zone_cnt) * full) // max_z if max_z > 0
                    else np.full(self.n_zones, full))
        zone_fp = per_zone[np.maximum(self.zone, 0)]
        blended = (node_fp + 2 * zone_fp) // 3
        return np.where(self.zone >= 0, blended, node_fp) // FIXED

    def _interpod(self, cls: PodClass, feas: np.ndarray) -> np.ndarray:
        """The pod's preferred terms weigh the pods in their scope; the terms
        of placed pods that hold this pod in theirs (required affinity with
        the symmetric weight, both preferred kinds) weigh their owners."""
        counts = np.zeros(self.n, np.int64)
        touched = False
        weighed = [(t, t.count) for t in cls.terms
                   if t.kind in (AFF_PREFERRED, ANTI_PREFERRED)]
        weighed += [(t, t.owner.own) for t in cls.selected_by
                    if t.kind != ANTI_REQUIRED]
        for term, here in weighed:
            if not term.key or not here.any():
                continue
            vals, n_vals = self._topology(term.key)
            on = vals >= 0
            weights = here[on].astype(np.int64) * term.weight
            per_value = np.bincount(vals[on], weights=weights,
                                    minlength=n_vals).astype(np.int64)
            counts += np.where(on, per_value[np.maximum(vals, 0)], 0)
            touched = True
        if not touched:
            return counts
        among = counts[feas]
        max_c = max(int(among.max()), 0) if among.size else 0
        min_c = min(int(among.min()), 0) if among.size else 0
        if max_c == min_c:
            return np.zeros(self.n, np.int64)
        return (MAX_PRIORITY * (counts - min_c)) // (max_c - min_c)

    # -- one decision --------------------------------------------------------
    def choose(self, pod: dict, feas: np.ndarray) -> tuple:
        """(node index or -1, whether the tie counter advances)."""
        n_feas = int(np.count_nonzero(feas))
        if n_feas == 0:
            return -1, False
        if n_feas == 1:
            return int(np.flatnonzero(feas)[0]), False
        total = np.where(feas, self.scores(pod, feas), -1)
        ties = np.flatnonzero(total == total.max())
        return int(ties[self.round_robin % len(ties)]), True

    def place(self, pod: dict, node: int, advances: bool) -> None:
        """Record a binding.  The state is exact whatever ``request_bits``:
        the host keeps exact sums, only the gathered request was rounded."""
        cls = self.class_of(pod)
        for r in range(4):
            self.free[r, node] -= cls.exact_req[r]
        self.nz_used[0, node] += cls.exact_nz[0]
        self.nz_used[1, node] += cls.exact_nz[1]
        self.count[node] += 1
        cls.placed.append(node)
        for count in cls.counted_in:
            count[node] += 1
        for vol in pod["spec"].get("volumes") or []:
            disk, kind = vol.get("diskID"), vol.get("diskKind", "")
            if not disk:
                continue
            self.disk_users.setdefault((kind, disk), []).append(
                (node, bool(vol.get("readOnly"))))
            if kind in VOLUME_COUNT_LIMITS:
                self.kind_ids[kind][node].add(disk)
                self._memo.setdefault(("kindnodes", kind), set()).add(node)
        if advances:
            self.round_robin += 1

    def schedule(self, pod: dict) -> Optional[str]:
        """Decide and record: the sequential scheduler itself."""
        node, advances = self.choose(pod, self.feasible(pod))
        if node < 0:
            return None
        self.place(pod, node, advances)
        return self.names[node]
