"""The benchmark's own generators: deterministic in the seed, exact in
their shares, odd in their request values, Poisson in shape."""

import collections
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
