"""``SchedulerCache.assume_many`` takes a kernel segment's placement as the
backend grouped it (ISSUE 30): one ``add_pods_counted`` per touched node and
the two pod maps in bulk, instead of a ``NodeInfo`` write per pod.

The per-pod form (a plain list of the same entries) is the reference: the
grouped form must leave every field of the cache as it does, order and
``generation`` included, refuse what it refuses, and be forgotten,
confirmed and finished the same way afterwards."""

import copy
import random

import pytest

from kubernetes_tpu.ops import TPUBatchBackend
from kubernetes_tpu.scheduler import GenericScheduler, PriorityContext
from kubernetes_tpu.scheduler.nodeinfo import (
    PlacedSegment,
    SchedulerCache,
    pod_has_affinity,
)
from kubernetes_tpu.scheduler.units import (
    ResourceVec,
    pod_nonzero_request_vec,
    pod_request_vec,
)
from kubernetes_tpu.testutil import make_node

from tests.test_parity import build_cluster
from tests.test_place_by_node import (
    TEMPLATES,
    _info_fields,
    _mixed_batch,
    _pods,
    _sum_vec,
)

NODES = [f"n{j}" for j in range(7)]
# bound before any segment; the same objects in every cache, so two caches
# can be compared by identity
RESIDENTS = _pods(["plain", "port", "affinity"], tag="resident")
for _pod in RESIDENTS:
    _pod.spec.node_name = "n1"


def _cache(clock) -> SchedulerCache:
    cache = SchedulerCache(ttl=30.0, clock=lambda: clock[0])
    for name in NODES[:5]:      # the last two are assumed onto unseen
        cache.add_node(make_node(name, cpu="64", memory="256Gi", pods=500))
    for pod in RESIDENTS:
        cache.add_pod(pod)
    return cache


def _cache_fields(cache: SchedulerCache) -> dict:
    return {
        # in the map's own order: the snapshot's node axis follows it
        "nodes": [(name, _info_fields(info))
                  for name, info in cache._nodes.items()],
        "pod_states": [(key, id(pod), node, state) for key, (pod, node, state)
                       in cache._pod_states.items()],
        "deadlines": list(cache._assume_deadlines.items()),
    }


def _segment(rng, n, tag, unplaced=1) -> PlacedSegment:
    """A seeded segment as ``place`` hands it over: entries in pod order
    with per-signature vectors, ``unplaced`` of them without a node, and
    the groups by node in node order, each node's pods in pod order."""
    kinds = [rng.choice(list(TEMPLATES)) for _ in range(n)]
    pods = _pods(kinds, tag=tag)
    names = [rng.choice(NODES) for _ in pods]
    for k in rng.sample(range(n), min(unplaced, n)):
        names[k] = None
    vecs = {kind: (pod_request_vec(pod), pod_nonzero_request_vec(pod))
            for kind, pod in zip(kinds, pods)}
    entries = [(pod, name, *vecs[kind])
               for pod, name, kind in zip(pods, names, kinds)]
    by_node = []
    for node in sorted({name for name in names if name is not None}):
        on = [(pod, kind) for pod, name, kind in zip(pods, names, kinds)
              if name == node]
        by_node.append((
            node, [pod for pod, _ in on],
            _sum_vec(vecs[kind][0] for _, kind in on),
            _sum_vec(vecs[kind][1] for _, kind in on),
            [pod for pod, _ in on if pod_has_affinity(pod)],
            [port for pod, _ in on for port in pod.host_ports()]))
    return PlacedSegment(entries, by_node, len(entries))


def _assume(cache, segment, grouped: bool) -> tuple:
    """What ``commit_segment`` does with a segment: the unplaced pods
    leave, the rest is assumed; per pod when the groups are stripped."""
    placed = PlacedSegment.placed_of(segment)
    return cache.assume_many(placed if grouped else list(placed))


@pytest.mark.parametrize("seed", range(8))
def test_grouped_assume_equals_the_per_pod_form_over_two_segments(seed):
    rng = random.Random(seed)
    first = _segment(rng, rng.randrange(1, 60), "a")
    second = _segment(rng, rng.randrange(1, 60), "b")   # the same nodes again
    clock = [100.0]
    want, got = _cache(clock), _cache(clock)
    for segment in (first, second):
        placed = [e for e in segment if e[1] is not None]
        assert _assume(want, segment, grouped=False) == (0, 0)
        assert _assume(got, segment, grouped=True) == (
            len(segment.by_node), len(placed))
        clock[0] += 1.0
        assert _cache_fields(got) == _cache_fields(want)
    assert any(i.pods_with_affinity for i in got._nodes.values())
    assert any(i.used_ports for i in got._nodes.values())
    assert all(got._nodes[n].node is None and got._nodes[n].pods
               for n in NODES[5:] if n in got._nodes)


def test_entries_behind_the_grouped_ones_are_assumed_one_at_a_time():
    """An oracle segment's entries appended behind a kernel segment's, as
    the backend's pending list holds them between two commits: 2-tuples
    and ``None`` vectors take the per-pod path, in entry order."""
    rng = random.Random(30)
    segment = _segment(rng, 24, "k", unplaced=2)
    tail = _pods(["odd", "affinity", "port"], tag="oracle")
    segment.extend([(tail[0], "n0", None, None), (tail[1], None, None, None),
                    (tail[2], "n0")])
    assert segment.grouped == 24 and len(segment) == 27
    clock = [5.0]
    want, got = _cache(clock), _cache(clock)
    placed = PlacedSegment.placed_of(segment)
    assert (len(placed), placed.grouped) == (24, 22)
    assert _assume(want, segment, grouped=False) == (0, 0)
    assert _assume(got, segment, grouped=True) == (len(segment.by_node), 22)
    assert _cache_fields(got) == _cache_fields(want)
    assert [p.meta.key for p in got._nodes["n0"].pods][-2:] == [
        tail[0].meta.key, tail[2].meta.key]


def test_a_copy_or_a_slice_of_a_placed_segment_carries_no_groups():
    segment = _segment(random.Random(1), 10, "c", unplaced=0)
    assert PlacedSegment.placed_of(segment).by_node is segment.by_node
    for plain in (list(segment), segment[:5], segment + []):
        assert type(plain) is list
        assert PlacedSegment.placed_of(plain).grouped == 0
    cache = _cache([0.0])
    assert cache.assume_many(segment[:5]) == (0, 0)
    assert len(cache._pod_states) == 3 + 5


@pytest.mark.parametrize("where", ["held-by-the-cache", "twice-in-the-segment"])
def test_an_already_assumed_key_raises_and_writes_nothing(where):
    rng = random.Random(7)
    segment = _segment(rng, 30, "d", unplaced=0)
    cache = _cache([0.0])
    if where == "held-by-the-cache":
        cache.assume_pod(segment[17][0], "n2")
        culprit = segment[17][0].meta.key
    else:
        segment[20] = segment[4]
        culprit = segment[4][0].meta.key
    before = copy.deepcopy(_cache_fields(cache))
    with pytest.raises(ValueError, match=f"pod {culprit} already assumed/added"):
        cache.assume_many(segment)
    assert _cache_fields(cache) == before
    # the per-pod form refuses the same key with the same words
    with pytest.raises(ValueError, match=f"pod {culprit} already assumed/added"):
        cache.assume_many(list(segment))


@pytest.mark.parametrize("grouped", [True, False], ids=["by-node", "per-pod"])
def test_forget_confirm_and_finish_after_either_form(grouped):
    rng = random.Random(11)
    segment = _segment(rng, 40, "e", unplaced=3)
    placed = [e for e in segment if e[1] is not None]
    clock = [10.0]
    cache = _cache(clock)
    _assume(cache, segment, grouped)
    keys = [e[0].meta.key for e in placed]
    assert all(cache.is_assumed(k) for k in keys)

    # finish: the expiry clock restarts for the keys given, no others
    clock[0] = 20.0
    cache.finish_binding_many(keys[:10])
    assert [cache._assume_deadlines[k] for k in keys[:11]] == [50.0] * 10 + [40.0]

    # forget: the pod leaves its node and both maps, aggregates re-derived
    gone, node = placed[12][0], placed[12][1]
    info = cache._nodes[node]
    before = _info_fields(info)
    cache.forget_pod(gone)
    assert not cache.is_assumed(keys[12]) and keys[12] not in cache._assume_deadlines
    assert gone.meta.key not in [p.meta.key for p in info.pods]
    want = ResourceVec(before["requested"])
    want.sub(pod_request_vec(gone))
    assert list(info.requested.units) == list(want.units)
    assert info.used_ports == {p for q in info.pods for p in q.host_ports()}

    # confirm: the assumed object is swapped for the API truth in place;
    # a wrong node, a forgotten pod and an unknown key come back untouched
    confirmed = copy.deepcopy(placed[0][0])
    confirmed.spec.node_name = placed[0][1]
    elsewhere = "n0" if placed[1][1] != "n0" else "n1"
    entries = [(keys[0], placed[0][1], 0, confirmed),
               (keys[1], elsewhere, 0, placed[1][0]),
               (keys[12], node, 0, gone),
               ("default/nobody", "n0", 0, gone)]
    assert cache.confirm_many(entries) == entries[1:]
    assert cache._pod_states[keys[0]] == (confirmed, placed[0][1], "bound")
    assert keys[0] not in cache._assume_deadlines
    assert any(p is confirmed for p in cache._nodes[placed[0][1]].pods)

    # expiry takes what was neither finished later nor confirmed
    clock[0] = 45.0
    expired = cache.cleanup_expired()
    assert set(expired) == set(keys[10:]) - {keys[12], keys[0]}
    assert all(cache.is_assumed(k) for k in keys[1:10])


# -- the groups the backend really sends ------------------------------------


@pytest.mark.timeout(300)
def test_the_backends_segments_assume_by_node_as_they_do_per_pod():
    """Three kernel segments of mixed pods (affinity, host ports, disks,
    some unplaced) from ``schedule_batch`` itself: the groups ``place``
    kept are the ones the per-pod calls would have made."""
    rng = random.Random(30)
    node_info_map = build_cluster(rng, 24, zones=3, tainted_frac=0.1,
                                  existing_per_node=0)
    pods = _mixed_batch(rng, 90)
    backend = TPUBatchBackend(algorithm=GenericScheduler(), max_segment_pods=32)
    segments: list = []
    try:
        got = backend.schedule_batch(pods, node_info_map,
                                     PriorityContext(node_info_map),
                                     on_segment=segments.append)
    finally:
        backend._host_state.close()
    assert len(segments) >= 3 and backend.stats["oracle_pods"] == 0
    assert all(type(s) is PlacedSegment and s.grouped == len(s)
               for s in segments)
    assert [e[1] for s in segments for e in s] == got

    clock = [0.0]
    want = SchedulerCache(clock=lambda: clock[0])
    by_node = SchedulerCache(clock=lambda: clock[0])
    for cache in (want, by_node):
        for info in node_info_map.values():
            cache.add_node(info.node)
    n_placed = 0
    for segment in segments:
        placed = [e for e in segment if e[1] is not None]
        assert {name for name, *_ in segment.by_node} == {e[1] for e in placed}
        assert _assume(want, segment, grouped=False) == (0, 0)
        assert _assume(by_node, segment, grouped=True) == (
            len(segment.by_node), len(placed))
        n_placed += len(placed)
        assert _cache_fields(by_node) == _cache_fields(want)
    assert n_placed == sum(1 for n in got if n)
    assert any(i.pods_with_affinity for i in by_node._nodes.values())
    assert any(i.used_ports for i in by_node._nodes.values())
