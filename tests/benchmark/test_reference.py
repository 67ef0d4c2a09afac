"""The plain reference against the program's sequential oracle (they share
no code), and the control: the reference in bfloat16's eight bits of
mantissa, put in the program's place, has to come out as not correct."""

import copy

import pytest

from benchmark import check, cluster, reference


def _world(name: str, nodes: int, pods: int, seed: int):
    config = copy.deepcopy(cluster.load_config(name))
    config["nodes"]["count"] = nodes
    config["pods"]["count"] = pods
    return cluster.World(config, seed, {"preload": pods, "window_pods": 0})


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
    ("perf-2k", 30, 1_500, 5), ("perf-2k", 12, 1_300, 6)])
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
@pytest.mark.parametrize("name", ["density-5k", "perf-2k"])
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
