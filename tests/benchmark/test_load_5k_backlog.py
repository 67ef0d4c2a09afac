"""Cell ``load-5k.backlog``: load.go's deployment at kubemark-5000.  The
configuration is load.go's shape; the rehearsal prints one correct result
object with the cell's reader in the traced line; the readers on hand-made
facts and on the spans of a real wave of more than 512 signatures, whose
bindings and tie counter equal the plain reference's; and the
configuration's own control comes out as not correct."""

import copy
import json
import os

import pytest

from benchmark import check, cluster, run, trace_reduce
from tests.benchmark.test_rehearsal import ROOT, _run

CELL = "load-5k.backlog"
NEW = {"build_static_us_per_group": "us/group"}
LATER = "signature_rows_us_per_group"


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_and_its_readers_are_listed():
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("load-5k", "backlog", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "load-5k")
    assert entry["reduced"] == [] and entry["file"] == "benchmark/configs/load-5k.json"
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert {n: m["unit"] for n, m in mine.items()} == NEW
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "bound_pods_per_s"
        assert m["source"] == "program_span" and m["better"] == "lower"
    # its reader is in place; it is listed once a parent emits its span
    assert LATER not in {m["name"] for m in bench["per_layer"]}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics", f"{LATER}.py"))


def test_the_configuration_is_load_gos_shape_at_kubemark_5000():
    config = cluster.load_config("load-5k")
    pods = config["pods"]
    groups = {t["prefix"]: (t["groups"]["count"], t["groups"]["replicas"])
              for t in pods["templates"]}
    assert groups == {"load-small": (15_000, 5), "load-medium": (1_250, 30),
                      "load-big": (150, 250)}
    assert sum(c * r for c, r in groups.values()) == pods["count"] == 150_000
    for tpl in pods["templates"]:
        c, r = groups[tpl["prefix"]]
        assert tpl["share"] == c * r / pods["count"]
        assert tpl["groups"]["groups_per_service"] == 2
        # load.go's 10m / 25Mi, and two requests off bfloat16's grid
        assert [(v["cpu"], v["memory"]) for v in tpl["variants"]] == [
            ("10m", "25Mi"), ("257m", "513Mi"), ("1100m", "1131Mi")]
    assert pods["namespaces"] == 50 and pods["per_node"] == 30
    assert config["services"] == []
    density = cluster.load_config("density-5k")
    assert config["nodes"] == density["nodes"]
    assert config["guarantees"] == density["guarantees"]
    world = cluster.World(config, 2**31 + 43, {"preload": 0, "window_pods": 0})
    assert len(world.nodes) == 5_000 and len(world.namespaces) == 50
    assert len(world.services) == 7_500 + 625 + 75 == 8_200


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses_to_one_correct_result_object(trace):
    code, out, err = _run("benchmark.run", "--workload", CELL, "--seed",
                          str(2**31 + 43), "--seconds", "20", "--trace", trace,
                          "--rehearse-cpu", "200,6000")
    assert code == 0, err[-3000:]
    result = json.loads(out[-1])
    assert result["correct"] is True, err[-3000:]
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert result["attempted"] == 6_000 and result["failed"] == 0
    # 656 groups: the wave is cut at 512 signatures
    assert "kernel segments 2" in err
    metrics = result["metrics"]
    if trace == "0":
        assert set(metrics) == {"bound_pods_per_s", "setup_s"}
        return
    assert {n: metrics[n]["unit"] for n in NEW} == NEW
    assert metrics["build_static_us_per_group"]["value"] > 0
    assert LATER not in metrics


def _s(name, dur, parent, **attrs):
    return {"name": name, "cat": "phase", "t0": 1.0, "t1": 1.0 + dur, "dur": dur,
            "self_s": dur, "attrs": attrs, "wave": 1, "parent": parent}


# two segments of one wave and a rejected one (no groups: it is split and
# its halves tensorized again)
FACTS = {"spans": [
    _s("tensorize", 0.5, "wave-1", pods=600, groups=512, n_pad=5120),
    _s("tensorize.build_static", 0.3, "tensorize"),
    _s("tensorize.signature_rows", 0.2, "tensorize.build_static",
       groups=500, services=8200, selectors=250),
    _s("tensorize", 0.4, "wave-1", pods=400, groups=512, n_pad=5120),
    _s("tensorize.build_static", 0.212, "tensorize"),
    _s("tensorize.signature_rows", 0.1, "tensorize.build_static",
       groups=300, services=8200, selectors=150),
    _s("tensorize", 0.01, "wave-1", pods=1000, planned=0, rejected=True),
]}


@pytest.mark.parametrize("name,want,needs", [
    ("build_static_us_per_group", 0.512 * 1e6 / 1024,
     ("tensorize", "tensorize.build_static")),
    (LATER, 0.3 * 1e6 / 800, ("tensorize.signature_rows",)),
])
def test_each_reader_reads_what_it_says_and_nothing_without_its_span(name, want, needs):
    assert run.read_layer_metric(name, FACTS) == pytest.approx(want, rel=1e-9)
    for missing in needs:
        without = dict(FACTS, spans=[s for s in FACTS["spans"] if s["name"] != missing])
        assert run.read_layer_metric(name, without) is None, missing
    assert run.read_layer_metric(name, {"spans": []}) is None


def _cuts(order, group_of, max_groups=512):
    """The planner's cut, replayed over the drain order: each segment's
    groups, in the order they first appear."""
    segments, seen = [], {}
    for key in order:
        g = group_of[key]
        if g not in seen and len(seen) >= max_groups:
            segments.append(list(seen))
            seen = {}
        seen.setdefault(g, None)
    return segments + [list(seen)]


@pytest.mark.timeout(600)
def test_a_wave_of_more_than_512_signatures_binds_as_the_reference():
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.ops import TPUBatchBackend
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store
    from kubernetes_tpu.utils import tracing

    seed, pods = 2**31 + 431, 6_000
    config = copy.deepcopy(cluster.load_config("load-5k"))
    config["nodes"]["count"], config["pods"]["count"] = 200, pods
    world = cluster.World(config, seed, {"preload": pods, "window_pods": 0})
    store = Store()
    for kind, objs in (("Node", world.nodes), ("Service", world.services)):
        for obj in objs:
            store.create(kind, copy.deepcopy(obj))
    store.create_many("Pod", copy.deepcopy(world.preload))
    cs = Clientset(store)
    algo = GenericScheduler()
    backend = TPUBatchBackend(algorithm=algo)
    sched = Scheduler(cs, algorithm=algo, backend=backend, emit_events=False)
    sched.start()
    drains, drain = [], sched.queue.drain

    def recording_drain(max_n=None):
        got = drain(max_n)
        drains.append([p.meta.key for p in got])
        return got

    sched.queue.drain = recording_drain
    tr = tracing.enable()
    try:
        bound, failed = sched.schedule_pending_batch()
    finally:
        tracing.disable()
    bindings = {cluster.pod_key(p): p["spec"].get("nodeName") or None
                for p in store.list("Pod")[0]}
    numbers = check.compare(world, drains, bindings,
                            {k for k, v in bindings.items() if v is None},
                            int(algo._round_robin), 0, seed=seed)
    assert check.verdict(numbers), numbers
    assert numbers["scored"] == pods and bound + failed == pods

    # the cut: every group its own signature, a segment per 512 of them
    meta = {cluster.pod_key(p): p["metadata"] for p in world.preload}
    group_of = {k: (m["namespace"], m["labels"]["name"]) for k, m in meta.items()}
    (order,) = drains
    cuts = _cuts(order, group_of)
    assert len({group_of[k] for k in order}) > 512
    assert backend.stats["segments"] == len(cuts) >= 2

    spans: list = []
    root = tr.ring[-1]
    trace_reduce.flatten(root, root.attrs["wave"], spans, None)
    facts = {"spans": spans}
    named = {n: [s for s in spans if s["name"] == n] for n in (
        "tensorize", "tensorize.signature_rows", "tensorize.spread_counts")}
    assert [s["attrs"]["groups"] for s in named["tensorize"]] == [512] * len(cuts)
    assert [s["parent"] for s in named["tensorize.signature_rows"]] == \
        ["tensorize.build_static"] * len(cuts)
    assert [s["parent"] for s in named["tensorize.spread_counts"]] == \
        ["tensorize.initial_state"] * len(cuts)
    # the real signatures, the services walked, and the selected ones: a
    # service selects the first group of its pair (odd numbers) only
    assert [s["attrs"] for s in named["tensorize.signature_rows"]] == [
        {"groups": len(c), "services": 8_200,
         "selectors": sum(int(name.rsplit("-", 1)[1]) % 2 for _, name in c)}
        for c in cuts]
    assert [s["attrs"]["groups"] for s in named["tensorize.spread_counts"]] == \
        [len(c) for c in cuts]

    # the accepted plan reader counts the cut (the cell does not list it yet)
    assert run.read_layer_metric("segments_per_wave", facts) == len(cuts)
    build = sum(s["dur"] for s in spans if s["name"] == "tensorize.build_static")
    assert run.read_layer_metric("build_static_us_per_group", facts) == \
        pytest.approx(build * 1e6 / (512 * len(cuts)))
    rows = sum(s["dur"] for s in named["tensorize.signature_rows"])
    assert run.read_layer_metric(LATER, facts) == \
        pytest.approx(rows * 1e6 / sum(len(c) for c in cuts))


@pytest.mark.parametrize("seed", [3, 2**31 + 47])
def test_the_configurations_control_comes_out_as_not_correct(seed):
    """The reference in bfloat16's eight bits of mantissa, in the program's
    place, on the configuration's own requests at a small size."""
    config = copy.deepcopy(cluster.load_config("load-5k"))
    config["nodes"]["count"], config["pods"]["count"] = 40, 1_500
    world = cluster.World(config, seed, {"preload": 1_500, "window_pods": 0})
    order = sorted(world.all_pods())
    drains, bindings, tie_counter = check.control_bindings(world, [order], 8, 10**9)
    numbers = check.compare(world, drains, bindings, set(bindings), tie_counter,
                            0, seed=seed)
    assert not check.verdict(numbers)
    assert numbers["choice_mismatches"] > 0, numbers
