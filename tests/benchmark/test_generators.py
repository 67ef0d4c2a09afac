"""The benchmark's own generators: deterministic in the seed, exact in
their shares, odd in their request values, Poisson in shape."""

import collections
import hashlib
import json
import copy
import random

import pytest

from benchmark import cluster, reference, traffic


def _small(name: str, nodes: int = 90, pods: int = 2_000) -> dict:
    config = copy.deepcopy(cluster.load_config(name))
    config["nodes"]["count"] = nodes
    config["pods"]["count"] = pods
    return config


@pytest.mark.parametrize("name", ["density-5k", "perf-2k"])
def test_world_is_a_function_of_the_seed(name):
    config = _small(name)
    plan = {"preload": 2_000, "window_pods": 300}
    a = cluster.World(config, 2**31 + 7, plan)
    b = cluster.World(config, 2**31 + 7, plan)
    c = cluster.World(config, 2**31 + 8, plan)
    assert a.nodes == b.nodes and a.preload == b.preload and a.window == b.window
    assert a.warm_wave({"pods": 50}) == b.warm_wave({"pods": 50})
    assert a.preload != c.preload
    # another seed is another order of the same multiset
    def shape(pods):
        return collections.Counter(
            (p["metadata"]["name"].split("-")[0],
             p["spec"]["containers"][0]["resources"]["requests"]["cpu"])
            for p in pods)
    by_template = lambda pods: collections.Counter(
        p["metadata"]["name"].split("-")[0] for p in pods)
    assert by_template(a.preload) == by_template(c.preload)
    assert set(shape(a.preload)) == set(shape(c.preload))
    node_shape = lambda ns: collections.Counter(
        (n["status"]["allocatable"]["cpu"], n["status"]["allocatable"]["memory"],
         len(n["spec"]["taints"]), n["metadata"]["labels"].get("disk"))
        for n in ns)
    assert node_shape(a.nodes) == node_shape(c.nodes)


def test_density_shares_are_exact_counts():
    config = _small("density-5k", pods=10_000)
    pods = cluster.make_pods(config, random.Random(1), 10_000)
    got = collections.Counter(p["metadata"]["name"].split("-")[0] for p in pods)
    assert got == {"soft": 1_000, "lonely": 1_000, "vol": 1_000, "ssd": 500,
                   "tol": 500, "pod": 6_000}
    nodes = cluster.make_nodes(config, random.Random(1))
    assert sum(1 for n in nodes if n["spec"]["taints"]) == 9
    assert sum(1 for n in nodes if "disk" in n["metadata"]["labels"]) == 27
    assert {n["metadata"]["labels"][cluster.ZONE_LABEL] for n in nodes} == {
        "zone-0", "zone-1", "zone-2"}


@pytest.mark.parametrize("name", ["density-5k", "perf-2k"])
def test_requests_need_more_than_eight_mantissa_bits(name):
    """Values that fit bfloat16 cannot show a rounded gather (PERF.md
    section 6, PR 21): the plain templates carry some that do not."""
    config = cluster.load_config(name)
    values = set()
    for tpl in config["pods"]["templates"]:
        for v in tpl["variants"]:
            values.add(reference.to_units("cpu", v["cpu"]))
            values.add(reference.to_units("memory", v["memory"]))
    odd = {v for v in values if reference.round_to_bits(v, 8) != v}
    assert {257, 513, 1001, 1100, 1131} <= odd


def test_pod_names_are_unique_across_preload_window_and_warm_waves():
    config = _small("density-5k")
    world = cluster.World(config, 5, {"preload": 500, "window_pods": 500})
    world.warm_wave({"pods": 100, "collide_disks": True})
    only = world.warm_wave({"pods": 6, "only": ["pod", "vol"]})
    assert {p["metadata"]["name"].split("-")[0] for p in only} == {"pod", "vol"}
    disks = [p["spec"]["volumes"][0]["diskID"] for p in world.warm[0] if p["spec"]["volumes"]]
    assert len(disks) - len(set(disks)) >= 1
    assert len(world.all_pods()) == 1_106


def test_arrivals_have_the_stated_rate_and_burst_sizes():
    config = cluster.load_config("density-5k")
    mix = dict(cluster.load_traffic("arrivals"), rate_pods_per_s=1_000)
    plan = traffic.plan(mix, config, seed=11, seconds=20.0)
    bursts = plan["bursts"]
    assert plan["window_pods"] == 20_000
    assert sum(last - first for _, first, last in bursts) == 20_000
    assert bursts[0][1] == 0 and all(a[2] == b[1] for a, b in zip(bursts, bursts[1:]))
    assert all(0.0 <= at < 20.0 for at, _, _ in bursts)
    sizes = collections.Counter(last - first for _, first, last in bursts)
    stated = {b["size"] for b in mix["bursts"]}
    assert sum(n for s, n in sizes.items() if s in stated) >= len(bursts) - 1
    # load.go: half of the pods in 5-pod groups, a quarter each in 30- and
    # 250-pod groups
    pod_share = {s: s * sizes[s] / 20_000 for s in stated}
    assert pod_share == pytest.approx({5: 0.5, 30: 0.25, 250: 0.25}, abs=0.005)
    for b in mix["bursts"]:
        assert pod_share[b["size"]] == pytest.approx(b["pod_share"], abs=0.005)
    # the gaps are an exponential's: mean = window / bursts, and about
    # 1 - 1/e of them shorter than the mean
    gaps = [b[0] - a[0] for a, b in zip(bursts, bursts[1:])]
    mean = 20.0 / len(bursts)
    assert sum(gaps) / len(gaps) == pytest.approx(mean, rel=0.02)
    assert sum(g < mean for g in gaps) / len(gaps) == pytest.approx(0.632, abs=0.02)


def test_every_seed_offers_the_same_bursts_in_another_order():
    config = cluster.load_config("density-5k")
    mix = cluster.load_traffic("arrivals")
    a = traffic.plan(mix, config, seed=1, seconds=10.0)["bursts"]
    b = traffic.plan(mix, config, seed=2**31 + 5, seconds=10.0)["bursts"]
    assert a != b
    assert sorted(l - f for _, f, l in a) == sorted(l - f for _, f, l in b)
    # the same gaps too, save the last one, which follows the last burst
    gaps = lambda bs: collections.Counter(
        round(y[0] - x[0], 7) for x, y in zip(bs, bs[1:]))
    assert sum((gaps(a) - gaps(b)).values()) <= 1


def test_backlog_preloads_the_whole_deployment():
    config = cluster.load_config("perf-2k")
    plan = traffic.plan(cluster.load_traffic("backlog"), config, 3, 20.0)
    assert plan == {"preload": 60_000, "bursts": [], "window_pods": 0,
                    "warm_waves": []}
    with pytest.raises(ValueError):
        traffic.plan({"kind": "replay"}, config, 3, 20.0)


# sha256 of each object's canonical JSON (nodes, services, preload, window),
# read from the generator before replica groups existed: a configuration
# that uses none of their keys builds the same world, byte for byte
WORLD_DIGESTS = [
    ("perf-2k", 1, "426163dc32e39207"), ("perf-2k", 2**31 + 5, "d2471dae8078613f"),
    ("density-5k", 1, "cfd71e37683cdd0d"), ("density-5k", 2**31 + 5, "d0639097adcb1266"),
    ("density-2k", 1, "f90a85ffceab1e48"), ("density-2k", 2**31 + 5, "e44de5368cbc6d85")]


@pytest.mark.parametrize("name,seed,digest", WORLD_DIGESTS)
def test_a_world_without_replica_groups_is_unchanged(name, seed, digest):
    config = cluster.load_config(name)
    plan = traffic.plan(cluster.load_traffic("backlog"), config, seed, 20.0)
    world = cluster.World(config, seed, plan)
    encode = json.JSONEncoder(sort_keys=True, separators=(",", ":")).encode
    h = hashlib.sha256()
    for group in (world.nodes, world.services, world.preload, world.window):
        for obj in group:
            h.update(encode(obj).encode())
    assert h.hexdigest()[:16] == digest
    assert world.namespaces == []


def _grouped(namespaces: int = 4, groups_per_service: int = 2) -> dict:
    """load.go's three group sizes at 2,000 pods: 2 x 250, 16 x 30, 204 x 5."""
    config = _small("density-5k", nodes=70)
    config["services"] = []
    config["pods"] = {"count": 2_000, "namespaces": namespaces, "templates": [
        {"prefix": f"load-{size}", "share": count * replicas / 2_000,
         "variants": [{"cpu": "10m", "memory": "25Mi"},
                      {"cpu": "257m", "memory": "513Mi"}],
         "groups": {"count": count, "replicas": replicas,
                    "groups_per_service": groups_per_service}}
        for size, count, replicas in (("big", 2, 250), ("medium", 16, 30),
                                      ("small", 204, 5))]}
    return config


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 977])
def test_replica_groups_services_and_namespaces_are_exact(seed):
    config = _grouped()
    world = cluster.World(config, seed, {"preload": 2_000, "window_pods": 0})
    assert [n["metadata"]["name"] for n in world.namespaces] == [
        "ns-0", "ns-1", "ns-2", "ns-3"]
    want, services = {}, 0
    for tpl in config["pods"]["templates"]:
        groups = tpl["groups"]
        for i in range(1, groups["count"] + 1):
            want[(f"ns-{i % 4}", f"{tpl['prefix']}-{i}",
                  f"{tpl['prefix']}-{(i + 1) // 2}",
                  tpl["variants"][(i - 1) % 2]["cpu"])] = groups["replicas"]
        services += (groups["count"] + 1) // 2
    got = collections.Counter(
        (p["metadata"]["namespace"], p["metadata"]["labels"]["name"],
         p["metadata"]["labels"]["svc-label"],
         p["spec"]["containers"][0]["resources"]["requests"]["cpu"])
        for p in world.preload)
    assert got == want
    assert len(world.services) == services == 1 + 8 + 102
    # a service selects, in its namespace, the first of its two groups
    for svc in world.services:
        ns, sel = svc["metadata"]["namespace"], svc["spec"]["selector"]
        chosen = {p["metadata"]["labels"]["name"] for p in world.preload
                  if p["metadata"]["namespace"] == ns
                  and all(p["metadata"]["labels"].get(k) == v
                          for k, v in sel.items())}
        assert chosen == {svc["metadata"]["name"][:-len("-svc")]}
    other = cluster.World(config, seed + 1, {"preload": 2_000, "window_pods": 0})
    assert other.services == world.services and other.namespaces == world.namespaces
    assert [p["metadata"]["labels"] for p in other.preload] != [
        p["metadata"]["labels"] for p in world.preload]


def test_replica_groups_fill_in_order_where_fewer_pods_are_made():
    """A rehearsal or a warm-up wave makes fewer pods than the groups hold:
    the first groups are whole, and more pods than they hold is an error."""
    config = _grouped(namespaces=0, groups_per_service=1)
    pods = cluster.make_pods(config, random.Random(5), 200)
    got = collections.Counter(p["metadata"]["labels"]["name"] for p in pods)
    assert got == {"load-big-1": 50, "load-medium-1": 30, "load-medium-2": 18,
                   **{f"load-small-{i}": 5 for i in range(1, 21)}, "load-small-21": 2}
    assert {p["metadata"]["namespace"] for p in pods} == {"default"}
    assert all(p["metadata"]["labels"]["svc-label"] == p["metadata"]["labels"]["name"]
               for p in pods)
    with pytest.raises(ValueError):
        cluster.make_pods(config, random.Random(5), 2_001)
