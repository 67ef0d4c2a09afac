"""Wire objects of a deployment, made from its configuration file and a seed.

Copied in shape from ``bench.py:48-172`` (``make_nodes``, ``make_services``,
``make_pods``; later PRs may change that script, this copy is the
yardstick), with two differences: the objects are wire dicts built from
templates, not ``testutil`` objects, so 150,000 of them cost a fraction of a
second; and every share is an exact count that the seed only permutes, so
each seed schedules the same multiset of nodes and pods in another order.
A template may deal its pods into replica groups with their services and
namespaces, as kubernetes ``test/e2e/scalability/load.go`` makes them
(``_group_labels`` and below).

Nothing here imports the program.
"""

from __future__ import annotations

import json
import os
import random

ZONE_LABEL = "failure-domain.beta.kubernetes.io/zone"
HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return load_json("configs", f"{name}.json")


def load_traffic(name: str) -> dict:
    return load_json("traffic", f"{name}.json")


def resolve(config_name: str, traffic_name: str, seconds: float,
            rehearse: "str | None", traffic_set: "list | None" = None) -> tuple:
    """(configuration, traffic mix) as run.  ``rehearse`` ("NODES,PODS") cuts
    both to a CPU rehearsal's size; the harness and the load generator both
    come through here, so they build the same world."""
    config, mix = load_config(config_name), load_traffic(traffic_name)
    for item in traffic_set or []:
        key, _, value = item.partition("=")
        mix[key] = json.loads(value)
    if rehearse is not None:
        nodes, pods = (int(x) for x in rehearse.split(","))
        config["nodes"]["count"] = nodes
        config["pods"]["count"] = pods
        if mix["kind"] == "arrivals":
            mix["rate_pods_per_s"] = pods / seconds
            mix["warm_waves"] = [{"pods": 64, "collide_disks": True}, {"pods": 24}]
    return config, mix


def exact_counts(shares: list, total: int) -> list:
    """Largest-remainder split of ``total`` by ``shares`` (which sum to 1)."""
    raw = [s * total for s in shares]
    counts = [int(r) for r in raw]
    by_remainder = sorted(range(len(raw)), key=lambda i: raw[i] - counts[i],
                          reverse=True)
    for i in by_remainder[: total - sum(counts)]:
        counts[i] += 1
    return counts


def _meta(name: str, namespace: str, labels: dict) -> dict:
    return {"name": name, "namespace": namespace, "uid": "",
            "resourceVersion": 0, "creationRevision": 0, "generation": 0,
            "labels": labels}


def make_nodes(config: dict, rng: random.Random) -> list:
    """Sizes, labels and taints are dealt by a fixed hand (the same joint
    multiset for every seed); the seed only decides which node gets which."""
    spec = config["nodes"]
    n = spec["count"]
    fixed = random.Random(0xC0FFEE)
    shapes = [(c, m) for c in spec["cpu"] for m in spec["memory"]]
    label_of: list = [dict() for _ in range(n)]
    for rule in spec.get("labels") or []:
        chosen = fixed.sample(range(n), round(rule["share"] * n))
        for k, i in enumerate(chosen):
            label_of[i][rule["key"]] = rule["values"][k % len(rule["values"])]
    taints_of: list = [[] for _ in range(n)]
    for rule in spec.get("taints") or []:
        for i in fixed.sample(range(n), round(rule["share"] * n)):
            taints_of[i].append({"key": rule["key"], "value": rule["value"],
                                 "effect": rule["effect"]})
    kinds = [(shapes[i % len(shapes)], label_of[i], taints_of[i]) for i in range(n)]
    rng.shuffle(kinds)
    shape_of = [k[0] for k in kinds]
    label_of = [k[1] for k in kinds]
    taints_of = [k[2] for k in kinds]
    nodes = []
    for i in range(n):
        name = f"node-{i:05d}"
        labels = {"kubernetes.io/hostname": name,
                  ZONE_LABEL: f"zone-{i % spec['zones']}", **label_of[i]}
        cpu, memory = shape_of[i]
        resources = {"cpu": cpu, "memory": memory, "pods": spec["pods"]}
        nodes.append({
            "kind": "Node", "metadata": _meta(name, "", labels),
            "spec": {"taints": taints_of[i], "unschedulable": False,
                     "providerID": "", "podCIDR": ""},
            "status": {"capacity": dict(resources), "allocatable": dict(resources),
                       "conditions": [{"type": "Ready", "status": "True",
                                       "heartbeatRevision": 0, "heartbeatTime": 0.0}],
                       "images": [], "volumesAttached": [], "kubeletURL": "",
                       "volumesInUse": [], "addresses": []}})
    return nodes


def _service(name: str, namespace: str, labels: dict, selector: dict) -> dict:
    return {"kind": "Service", "metadata": _meta(name, namespace, labels),
            "spec": {"selector": selector, "ports": [], "clusterIP": "",
                     "type": "ClusterIP", "sessionAffinity": "None"},
            "status": {"loadBalancer": {"ingress": []}}}


def make_services(config: dict) -> list:
    """``services`` (one per ``app`` label, in ``default``), then those of
    the replica groups (``_group_services``)."""
    return ([_service(app, "default", {}, {"app": app})
             for app in config.get("services") or []]
            + [svc for tpl in config["pods"]["templates"] if tpl.get("groups")
               for svc in _group_services(config, tpl)])


def make_namespaces(config: dict) -> list:
    """The namespaces the replica groups are dealt over (``pods.namespaces``),
    named ``ns-0`` ... ``ns-<n-1>``."""
    return [{"kind": "Namespace", "metadata": _meta(f"ns-{k}", "", {}),
             "spec": {"finalizers": ["kubernetes"]}, "status": {"phase": "Active"}}
            for k in range(config["pods"].get("namespaces") or 0)]


# Replica groups, after load.go's ``GenerateConfigsForGroup`` and
# ``generateServicesForConfigs``: a template's ``groups`` is {"count": c,
# "replicas": r} and optionally "groups_per_service": k.  Group i (1..c) is
# named ``<prefix>-<i>``, holds r pods labelled ``name: <prefix>-<i>``, and
# lives in namespace ``ns-<i mod n>``; with k, its pods also carry
# ``svc-label: <prefix>-<ceil(i / k)>``, and each value has one service,
# named after the first group that carries it and in that group's namespace,
# selecting the label there (so with k = 2 and more than one namespace it
# selects that first group only, as upstream's does).  Group i takes the
# template's variant (i - 1) mod len(variants).
SVC_LABEL = "svc-label"


def _group_namespace(config: dict, i: int) -> str:
    n = config["pods"].get("namespaces")
    return f"ns-{i % n}" if n else "default"


def _group_labels(tpl: dict, i: int) -> dict:
    groups, labels = tpl["groups"], {"name": f"{tpl['prefix']}-{i}"}
    k = groups.get("groups_per_service")
    if k:
        labels[SVC_LABEL] = f"{tpl['prefix']}-{(i + k - 1) // k}"
    app = tpl["variants"][(i - 1) % len(tpl["variants"])].get("app")
    if app is not None:
        labels["app"] = app
    return labels


def _group_services(config: dict, tpl: dict) -> list:
    k = tpl["groups"].get("groups_per_service")
    if not k:
        return []
    out = []
    for first in range(1, tpl["groups"]["count"] + 1, k):
        labels = _group_labels(tpl, first)
        out.append(_service(
            f"{labels['name']}-svc", _group_namespace(config, first),
            {"name": labels["name"], SVC_LABEL: labels[SVC_LABEL]},
            {SVC_LABEL: labels[SVC_LABEL]}))
    return out


def _group_slots(tpl: dict, n: int, rng: random.Random) -> list:
    """Which group each of the template's ``n`` pods joins: every group
    holds its ``replicas`` (the first groups, where ``n`` is smaller, as in
    a rehearsal or a warm-up wave), and the seed only permutes."""
    groups = tpl["groups"]
    if n > groups["count"] * groups["replicas"]:
        raise ValueError(f"template {tpl['prefix']!r}: {n} pods, but its groups "
                         f"hold {groups['count'] * groups['replicas']}")
    slots = [i // groups["replicas"] + 1 for i in range(n)]
    rng.shuffle(slots)
    return slots


_POD_STATUS = {"phase": "Pending", "conditions": [], "hostIP": "", "podIP": "",
               "startRevision": 0}


def _pod_spec(template: dict, variant: dict, volumes: list) -> dict:
    return {
        "containers": [{"name": "c0", "image": "img", "resources": {
            "requests": {"cpu": variant["cpu"], "memory": variant["memory"]},
            "limits": {}}, "ports": []}],
        "nodeName": "", "nodeSelector": dict(template.get("nodeSelector") or {}),
        "affinity": template.get("affinity"),
        "tolerations": list(template.get("tolerations") or []),
        "volumes": volumes, "priority": 0, "priorityClassName": "",
        "schedulerName": "default-scheduler", "restartPolicy": "Always",
        "serviceAccountName": "", "terminationGracePeriodSeconds": 30,
        "activeDeadlineSeconds": None, "hostPID": False, "hostIPC": False,
        "hostNetwork": False}


def make_pods(config: dict, rng: random.Random, count: int, tag: str = "",
              only: "list | None" = None, collide_disks: bool = False) -> list:
    """``count`` pending pods in creation order.  A pod's name is its
    template's prefix, ``tag`` and its position, as in ``bench.make_pods``;
    specs of one template and variant are one shared dict (read-only to
    callers).  Disk ids are drawn from a range sized by the deployment, not
    by ``count``, so a small wave collides no more than the full backlog.
    ``only`` keeps the templates with those prefixes (at least one pod of
    each); ``collide_disks`` gives the first two volume pods one disk (a
    warm-up wave uses both to reach a shape bucket on purpose)."""
    templates = config["pods"]["templates"]
    shares = [t["share"] if only is None or t["prefix"] in only else 0.0
              for t in templates]
    shares = [x / sum(shares) for x in shares]
    counts = exact_counts(shares, count)
    if only is not None:
        for t, share in enumerate(shares):
            if share and not counts[t]:
                counts[t] += 1
                counts[counts.index(max(counts))] -= 1
    which = [t for t, c in enumerate(counts) for _ in range(c)]
    rng.shuffle(which)
    shared = {(t, v): _pod_spec(tpl, var, [])
              for t, tpl in enumerate(templates)
              for v, var in enumerate(tpl["variants"])}
    # disks are dealt by a fixed hand too: the same ids and kinds, hence the
    # same collisions, for every seed; the seed decides which pod gets which
    disks: dict = {}
    for t, tpl in enumerate(templates):
        vol = tpl.get("volume")
        if vol:
            fixed = random.Random(0xD15C + count)
            span = vol["id_range_per_pod"] * config["pods"]["count"]
            disks[t] = [(fixed.randrange(span), fixed.choice(vol["kinds"]))
                        for _ in range(counts[t])]
            rng.shuffle(disks[t])
            if collide_disks and len(disks[t]) >= 2:
                disks[t][1] = disks[t][0]
    # replica groups: each pod takes the next slot of its template's dealt
    # list; a group's labels and namespace are one shared dict and string
    slots = {t: iter(_group_slots(tpl, counts[t], rng))
             for t, tpl in enumerate(templates) if tpl.get("groups")}
    group_meta: dict = {}
    pods = []
    for i, t in enumerate(which):
        tpl = templates[t]
        namespace, labels = "default", None
        if t in slots:
            g = next(slots[t])
            v = (g - 1) % len(tpl["variants"])
            if (t, g) not in group_meta:
                group_meta[(t, g)] = (_group_namespace(config, g),
                                      _group_labels(tpl, g))
            namespace, labels = group_meta[(t, g)]
        else:
            v = i % len(tpl["variants"])
        variant = tpl["variants"][v]
        spec = shared[(t, v)]
        if t in disks:
            disk, kind = disks[t].pop()
            spec = _pod_spec(tpl, variant, [{
                "name": "v", "diskID": f"pd-{disk}", "diskKind": kind,
                "readOnly": False, "pvcName": "", "secretName": "",
                "configMapName": ""}])
        pods.append({"kind": "Pod",
                     "metadata": _meta(f"{tpl['prefix']}-{tag}{i:06d}", namespace,
                                       labels or {"app": variant["app"]}),
                     "spec": spec, "status": _POD_STATUS})
    return pods


def pod_key(pod: dict) -> str:
    meta = pod["metadata"]
    return f"{meta.get('namespace') or 'default'}/{meta['name']}"


class World:
    """Every object of one run, the same in the load generator's process
    and in the harness's: both build it from the configuration, the plan
    and the seed, so only commands cross the pipe, never objects."""

    def __init__(self, config: dict, seed: int, plan: dict):
        rng = random.Random(seed)
        self.config = config
        self.nodes = make_nodes(config, rng)
        self.namespaces = make_namespaces(config)
        self.services = make_services(config)
        self.preload = make_pods(config, rng, plan["preload"])
        self.window = make_pods(config, rng, plan["window_pods"], tag="a")
        self._warm_rng = random.Random(seed + 1)
        self.warm: list = []

    def warm_wave(self, wave: dict) -> list:
        """``wave``: {"pods": n, "only": [prefixes], "collide_disks": bool}."""
        pods = make_pods(self.config, self._warm_rng, wave["pods"],
                         tag=f"w{len(self.warm)}x", only=wave.get("only"),
                         collide_disks=wave.get("collide_disks", False))
        self.warm.append(pods)
        return pods

    def all_pods(self) -> dict:
        return {pod_key(p): p
                for group in (self.preload, self.window, *self.warm)
                for p in group}
