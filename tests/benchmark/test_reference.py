"""The plain reference against the program's sequential oracle (they share
no code), and the control: the reference in bfloat16's eight bits of
mantissa, put in the program's place, has to come out as not correct."""

import copy

import pytest

from benchmark import check, cluster, reference


def load_go(nodes: int, pods: int, namespaces: int, groups_per_service: int,
            app_services: bool = False) -> dict:
    """The objects of kubernetes test/e2e/scalability/load.go at ``nodes`` x
    about ``pods``: computePodCounts' groups of 250, 30 and 5 (a quarter,
    a quarter and the rest of the pods), one service per
    ``groups_per_service`` groups, the groups dealt over ``namespaces``, on
    density-5k's nodes.  load.go's pods all request 10m and 25Mi; groups
    take turns with two odd requests too, so that a rounded gather shows.
    ``app_services``: the groups live in ``default``, carry an ``app`` label
    each, and density-5k's services select them beside their own."""
    config = copy.deepcopy(cluster.load_config("density-5k"))
    config["nodes"]["count"] = nodes
    big = pods // 4 // 250
    medium = (pods - 250 * big) // 3 // 30
    small = (pods - 250 * big - 30 * medium) // 5
    total = 250 * big + 30 * medium + 5 * small
    variants = [{"cpu": "10m", "memory": "25Mi", "app": "web"},
                {"cpu": "257m", "memory": "513Mi", "app": "api"},
                {"cpu": "1100m", "memory": "1131Mi", "app": "db"}]
    if not app_services:
        config["services"] = []
        variants = [{k: v for k, v in var.items() if k != "app"} for var in variants]
    config["pods"] = {"count": total, "per_node": 30, "templates": [
        {"prefix": f"load-{size}", "share": count * replicas / total,
         "variants": variants,
         "groups": {"count": count, "replicas": replicas,
                    "groups_per_service": groups_per_service}}
        for size, count, replicas in (("big", big, 250), ("medium", medium, 30),
                                      ("small", small, 5)) if count]}
    if namespaces:
        config["pods"]["namespaces"] = namespaces
    return config


# load.go's shapes: a service per group, per two groups (which, over more
# than one namespace, selects the first only, as upstream's does), and per
# two groups in one namespace beside services that select by ``app``
LOAD = {"load.per-group": (3, 1, False), "load.per-two": (3, 2, False),
        "load.shared": (0, 2, True)}


def _world(name: str, nodes: int, pods: int, seed: int):
    if name in LOAD:
        config = load_go(nodes, pods, *LOAD[name])
    else:
        config = copy.deepcopy(cluster.load_config(name))
        config["nodes"]["count"] = nodes
        config["pods"]["count"] = pods
    return cluster.World(config, seed, {"preload": config["pods"]["count"],
                                        "window_pods": 0})


def _oracle(world):
    """Bindings, decision order and tie counter of the program's per-pod
    oracle on an in-process store."""
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store

    store = Store(event_log_window=100_000)
    for kind, objs in (("Node", world.nodes), ("Service", world.services),
                       ("Pod", world.preload)):
        for obj in objs:
            store.create(kind, obj)
    cs = Clientset(store)
    sched = Scheduler(cs, algorithm=GenericScheduler(), backend=None, emit_events=False)
    sched.start()
    order = []
    pop = sched.queue.pop

    def recording_pop(timeout=None):
        pod = pop(timeout)
        if pod is not None:
            order.append(pod.meta.key)
        return pod

    sched.queue.pop = recording_pop
    sched.run_pending()
    bindings = {p.meta.key: p.spec.node_name or None for p in cs.pods.list()[0]}
    return order, bindings, sched.algorithm._round_robin


@pytest.mark.parametrize("name,nodes,pods,seed", [
    ("density-5k", 40, 1_200, 1), ("density-5k", 24, 1_500, 2**31 + 3),
    ("perf-2k", 30, 1_500, 5), ("perf-2k", 12, 1_300, 6),
    ("load.per-group", 40, 1_500, 7), ("load.per-two", 40, 1_500, 2**31 + 9),
    ("load.shared", 40, 1_500, 11)])
def test_reference_equals_the_programs_oracle(name, nodes, pods, seed):
    world = _world(name, nodes, pods, seed)
    order, bindings, tie_counter = _oracle(world)
    by_key = world.all_pods()
    ref = reference.Reference(world.nodes, world.services)
    mine = {}
    for key in order:
        if mine.get(key) is None:
            mine[key] = ref.schedule(by_key[key])
    assert mine == bindings
    assert ref.round_robin == tie_counter
    # and the checker agrees, every decision scored
    numbers = check.compare(world, [order], bindings,
                            {k for k, v in bindings.items() if v is None},
                            tie_counter, 0, seed=seed)
    assert check.verdict(numbers), numbers
    assert numbers["scored"] == numbers["decisions"] == len(order)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", ["density-5k", "perf-2k", "load.per-two"])
def test_the_control_comes_out_as_not_correct(name, seed):
    """Gathering requests in eight bits of mantissa (257 -> 256, 1,001 ->
    1,000, 1,100 -> 1,104) moves scores and so bindings."""
    world = _world(name, 40, 1_200, seed)
    order = sorted(world.all_pods())
    drains, bindings, tie_counter = check.control_bindings(world, [order], 8, 10**9)
    numbers = check.compare(world, drains, bindings, set(bindings), tie_counter,
                            0, seed=seed)
    assert not check.verdict(numbers)
    assert numbers["choice_mismatches"] >= 3, numbers


def test_rounding_to_bits_is_bfloat16s():
    r = reference.round_to_bits
    assert [r(v, 8) for v in (100, 128, 255, 256, 257, 513, 1001, 1100, 1131)] == [
        100, 128, 255, 256, 256, 512, 1000, 1104, 1128]
    assert r(1001, None) == 1001 and r(385, 8) == 384 and r(387, 8) == 388


def test_sampling_keeps_the_head_the_tail_and_a_seeded_draw():
    take = check.sample_positions(50_000, 12_000, seed=9)
    assert take.sum() == 12_000 and take[:2_000].all() and take[-1_000:].all()
    assert (take == check.sample_positions(50_000, 12_000, seed=9)).all()
    assert (take != check.sample_positions(50_000, 12_000, seed=10)).any()
    assert check.sample_positions(100, 12_000, seed=1).all()


def test_quantities_and_unsupported_features():
    assert reference.to_units("cpu", "100m") == 100
    assert reference.to_units("cpu", "1") == 1_000
    assert reference.to_units("memory", "1Gi") == 1_024
    assert reference.to_units("memory", "1000k") == 1
    world = _world("perf-2k", 3, 3, 1)
    ref = reference.Reference(world.nodes, [])
    pod = copy.deepcopy(world.preload[0])
    pod["spec"]["nodeName"] = "node-00000"
    with pytest.raises(reference.Unsupported):
        ref.feasible(pod)


def test_a_decision_visits_only_the_classes_its_services_select():
    """With over 1,000 classes placed, a pod without inter-pod terms whose
    service is new to the reference sums the one class that service selects
    so far (its own), and its sibling group, which joins that service's
    count as it appears, sums none."""
    config = load_go(400, 12_000, 1, 2)
    world = cluster.World(config, 3, {"preload": config["pods"]["count"],
                                      "window_pods": 0})
    svc = world.preload[-1]["metadata"]["labels"]["svc-label"]
    held = [p for p in world.preload if p["metadata"]["labels"]["svc-label"] == svc]
    assert len({p["metadata"]["labels"]["name"] for p in held}) == 2
    ref = reference.Reference(world.nodes, world.services)
    for pod in world.preload:
        if pod["metadata"]["labels"]["svc-label"] != svc:
            ref.schedule(pod)
    assert len(ref.classes) >= 1_000
    ref.visits = 0
    feas = ref.feasible(held[0])
    assert ref.visits == 0
    node, advances = ref.choose(held[0], feas)
    assert feas.sum() >= 2 and ref.visits == 1
    ref.place(held[0], node, advances)
    for pod in held[1:]:
        assert ref.schedule(pod) is not None
    assert ref.visits == 1
    assert len(ref.classes) == len({p["metadata"]["labels"]["name"]
                                    for p in world.preload})
