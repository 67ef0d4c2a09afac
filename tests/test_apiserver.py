"""API server + remote client: the control plane over the wire."""

import json
import urllib.error
import urllib.request

import pytest

from kubernetes_tpu.api import Binding, BindingColumns, ObjectMeta, Pod
from kubernetes_tpu.apiserver import APIServer
from kubernetes_tpu.client import Clientset
from kubernetes_tpu.client.remote import RemoteStore
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.store import NotFoundError, Store
from kubernetes_tpu.testutil import make_node, make_pod


@pytest.fixture
def server():
    s = APIServer(Store())
    s.start()
    yield s
    s.stop()


@pytest.fixture
def remote(server):
    return Clientset(RemoteStore(server.url))


def test_healthz_metrics_version(server):
    for path, key in [("/healthz", "status"), ("/version", "version")]:
        with urllib.request.urlopen(server.url + path) as r:
            assert key in json.loads(r.read())
    with urllib.request.urlopen(server.url + "/metrics") as r:
        assert b"apiserver_request_count" in r.read()


def test_remote_crud(remote):
    remote.pods.create(make_pod("p1", cpu="1"))
    got = remote.pods.get("p1")
    assert got.meta.name == "p1" and got.meta.uid
    pods, rev = remote.pods.list()
    assert len(pods) == 1 and rev >= 1
    remote.pods.delete("p1")
    with pytest.raises(NotFoundError):
        remote.pods.get("p1")


def test_remote_cluster_scoped_node(remote):
    remote.nodes.create(make_node("n1"))
    assert remote.nodes.get("n1").meta.name == "n1"


def test_remote_cas_conflict(remote):
    remote.pods.create(make_pod("p1"))
    a = remote.pods.get("p1")
    b = remote.pods.get("p1")
    a.meta.annotations["x"] = "1"
    remote.pods.update(a)
    b.meta.annotations["x"] = "2"
    from kubernetes_tpu.store import ConflictError

    with pytest.raises(ConflictError):
        remote.pods.update(b)


def test_remote_bind_and_batch(remote):
    for i in range(3):
        remote.pods.create(make_pod(f"p{i}"))
    remote.pods.bind(Binding(pod_name="p0", node_name="n1"))
    assert remote.pods.get("p0").spec.node_name == "n1"
    errs = remote.pods.bind_many(
        BindingColumns(["default/p1", "default/p2"], ["n1", "n2"]))
    assert errs == [None, None]
    assert remote.pods.get("p2").spec.node_name == "n2"


@pytest.mark.parametrize("binary", [False, True])
def test_remote_bind_many_posts_two_columns(server, monkeypatch, binary):
    """The wire body of a batch bind is the verb's two columns as they are:
    about 40 bytes a row at the benchmark's names, where a dict a row
    took 80-94."""
    from kubernetes_tpu.utils import tracing

    n = 1_000
    keys = [f"default/lonely-a{i:06d}" for i in range(n)]
    node_names = [f"node-{i % 2000:05d}" for i in range(n)]
    server.store.create_many("Pod", [make_pod(k.split("/")[1]).to_dict()
                                     for k in keys])
    posted = []
    urlopen = urllib.request.urlopen

    def recording(req, *a, **kw):
        posted.append((req.get_full_url(), req.data))
        return urlopen(req, *a, **kw)

    monkeypatch.setattr(urllib.request, "urlopen", recording)
    remote = RemoteStore(server.url, binary=binary)
    tr = tracing.enable()
    try:
        assert remote.bind_many(keys, node_names) == [None] * n
    finally:
        tracing.disable()
    (url, data), = posted
    assert url.endswith("/api/v1/bindings:batch")
    if not binary:
        assert json.loads(data) == {"keys": keys, "nodeNames": node_names}
    sp, = (s for s in tr.background if s.name == "remote.request")
    assert sp.attrs["items"] == n and sp.attrs["bytes_out"] == len(data)
    assert sp.attrs["bytes_out"] / sp.attrs["items"] <= 55
    pods, _ = server.store.list("Pod")
    assert {p["metadata"]["name"]: p["spec"]["nodeName"] for p in pods} == {
        k.split("/")[1]: node for k, node in zip(keys, node_names)}


@pytest.mark.parametrize("body", [
    {"keys": ["default/p0", "default/p1"], "nodeNames": ["n0"]},
    {"keys": ["default/p0", 7], "nodeNames": ["n0", "n1"]},
    {"keys": ["default/p0"], "nodeNames": [None]},
    {"keys": ["default/p0"]},
    {"nodeNames": ["n0"]},
    {"keys": "default/p0", "nodeNames": "n0"},
    {"bindings": [{"podNamespace": "default", "podName": "p0",
                   "nodeName": "n0"}]},
    [],
], ids=["unequal", "non_string_key", "non_string_node", "no_node_names",
        "no_keys", "not_lists", "rows_form", "not_an_object"])
def test_a_malformed_bind_body_is_refused_and_nothing_commits(server, body):
    server.store.create_many("Pod", [make_pod(f"p{i}").to_dict()
                                     for i in range(2)])
    rev = server.store.revision
    req = urllib.request.Request(
        f"{server.url}/api/v1/bindings:batch", method="POST",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(req, timeout=5)
    assert err.value.code == 400
    assert json.loads(err.value.read())["reason"] == "BadRequest"
    assert server.store.revision == rev
    pods, _ = server.store.list("Pod")
    assert [p["spec"].get("nodeName", "") for p in pods] == ["", ""]


def _store_counters(url: str) -> dict:
    with urllib.request.urlopen(url + "/metrics") as r:
        lines = r.read().decode().splitlines()
    return {ln.split()[0]: float(ln.split()[1]) for ln in lines
            if ln.startswith("store_")}


def test_metrics_serve_the_stores_bind_counters(server, remote):
    """The store runs in the apiserver's process: `/metrics` carries its
    counters.  A batch bind defers every row's watch payload; a frames
    watcher's encode builds them, a piece at a time, after the answer."""
    for i in range(5):
        remote.pods.create(make_pod(f"p{i}"))
    _, rev = remote.pods.list()
    before = _store_counters(server.url)
    assert {"store_bind_rows_deferred_total",
            "store_event_payloads_built_total",
            "store_watch_frames_total"} <= set(before)
    errs = remote.pods.bind_many(
        BindingColumns([f"default/p{i}" for i in range(5)], ["n1"] * 5))
    assert errs == [None] * 5
    after = _store_counters(server.url)
    assert (after["store_bind_rows_deferred_total"]
            - before["store_bind_rows_deferred_total"]) == 5
    # nobody watched: no payload of the txn was built
    assert (after["store_event_payloads_built_total"]
            == before["store_event_payloads_built_total"])
    w = remote.pods.watch(from_revision=rev)  # a per-event reader resumes
    got = [w.get(timeout=5) for _ in range(5)]
    w.stop()
    assert [e.object["spec"]["nodeName"] for e in got] == ["n1"] * 5
    read = _store_counters(server.url)
    assert (read["store_event_payloads_built_total"]
            - before["store_event_payloads_built_total"]) == 5


@pytest.mark.parametrize("frames", [False, True])
def test_metrics_serve_the_watch_streams_time(server, remote, frames):
    """`apiserver_watch_serve_seconds_total`: what the watch streams spent
    encoding and writing their frames and lines, and of it
    `apiserver_watch_encode_seconds_total`, the encode: for operators, and
    the ``watch`` / ``watch_encode`` of a traced request's
    `Server-Timing`."""
    def served():
        with urllib.request.urlopen(server.url + "/metrics") as r:
            rows = dict(ln.split() for ln in r.read().decode().splitlines()
                        if ln.startswith("apiserver_watch_"))
        return (float(rows["apiserver_watch_serve_seconds_total"]),
                float(rows["apiserver_watch_encode_seconds_total"]))

    assert served() == (0.0, 0.0)
    _, rev = remote.pods.list()
    w = RemoteStore(server.url).watch("Pod", from_revision=rev, frames=frames)
    remote.pods.create_many([make_pod(f"w{i}") for i in range(300)])
    got = 0
    while got < 300:
        ev = w.get(timeout=5)
        assert ev is not None
        got += len(ev.keys) if frames else 1
    w.stop()
    serve, encode = served()
    assert 0.0 < encode <= serve


def test_remote_watch_stream(remote):
    pods, rev = remote.pods.list()
    w = remote.pods.watch(from_revision=rev)
    remote.pods.create(make_pod("w1"))
    ev = w.get(timeout=5)
    assert ev is not None and ev.type == "ADDED" and ev.key == "default/w1"
    w.stop()


def test_auth_rejects_bad_token():
    s = APIServer(Store(), tokens={"sekrit": "admin"})
    s.start()
    try:
        req = urllib.request.Request(s.url + "/api/v1/pods")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 401
        ok = Clientset(RemoteStore(s.url, token="sekrit"))
        ok.pods.create(make_pod("p"))
        assert ok.pods.get("p").meta.name == "p"
    finally:
        s.stop()


def test_scheduler_over_the_wire(server):
    """The full scheduler running against the apiserver via HTTP only."""
    local = Clientset(server.store)  # "kubectl" side writes in-proc
    remote = Clientset(RemoteStore(server.url))  # scheduler side is remote
    local.nodes.create(make_node("n1", cpu="4"))
    local.nodes.create(make_node("n2", cpu="4"))
    sched = Scheduler(remote, emit_events=False)
    sched.start()
    for i in range(6):
        local.pods.create(make_pod(f"p{i}", cpu="500m"))
    # the remote watch stream is asynchronous: poll until the events land
    import time

    deadline = time.time() + 10
    n = 0
    while time.time() < deadline and n < 6:
        sched.pump()
        n += sched.run_pending()
        time.sleep(0.05)
    assert n == 6
    pods, _ = local.pods.list()
    assert all(p.spec.node_name for p in pods)
    assert {p.spec.node_name for p in pods} == {"n1", "n2"}


def test_unknown_resource_404(server):
    req = urllib.request.Request(server.url + "/api/v1/widgets")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    assert ei.value.code == 404
    assert json.loads(ei.value.read())["reason"] == "NotFound"


def test_late_registered_kind_is_wire_addressable(server):
    """Kinds registered after server start (CRD-style) must resolve on the
    wire immediately — resource lookup is per-request, not an import-time
    snapshot."""
    from kubernetes_tpu.api.types import KIND_PLURALS, KINDS

    class Widget:
        KIND = "Widget"

    from kubernetes_tpu.api.types import register_kind

    register_kind(Widget)
    try:
        server.store.create("Widget", {"kind": "Widget",
                                       "metadata": {"name": "w", "namespace": "default"}})
        with urllib.request.urlopen(server.url + "/api/v1/widgets") as resp:
            items = json.loads(resp.read())["items"]
        assert [i["metadata"]["name"] for i in items] == ["w"]
    finally:
        KINDS.pop("Widget", None)
        KIND_PLURALS.pop("Widget", None)


# -- round-2: PATCH verb + LIST selectors on the wire ----------------------


def test_wire_list_selectors():
    from kubernetes_tpu.client.remote import RemoteStore
    from kubernetes_tpu.testutil import make_pod

    server = APIServer(Store())
    server.start()
    try:
        rs = RemoteStore(server.url)
        for i in range(6):
            pod = make_pod(f"p{i}", labels={"app": "web" if i % 2 else "db",
                                            "tier": "fe"})
            pod.spec.node_name = f"n{i % 3}"
            rs.create("Pod", pod.to_dict())
        items, _ = rs.list("Pod", None, label_selector="app=web")
        assert len(items) == 3
        items, _ = rs.list("Pod", None, field_selector="spec.nodeName=n0")
        assert {i["metadata"]["name"] for i in items} == {"p0", "p3"}
        # combined
        items, _ = rs.list("Pod", None, label_selector="app=web",
                           field_selector="spec.nodeName=n1")
        assert {i["metadata"]["name"] for i in items} == {"p1"}
        # set-based grammar
        items, _ = rs.list("Pod", None, label_selector="app in (web,db),tier")
        assert len(items) == 6
        # unsupported field key -> 400 (surfaced as an error)
        import pytest as _p

        with _p.raises(Exception):
            rs.list("Pod", None, field_selector="spec.bogus=1")
    finally:
        server.stop()


def test_wire_patch_verb():
    from kubernetes_tpu.client.remote import RemoteStore
    from kubernetes_tpu.testutil import make_node

    server = APIServer(Store())
    server.start()
    try:
        rs = RemoteStore(server.url)
        rs.create("Node", make_node("n1").to_dict())
        # merge patch adds a label server-side
        out = rs.patch("Node", "", "n1",
                       {"metadata": {"labels": {"pool": "gpu"}}})
        assert out["metadata"]["labels"]["pool"] == "gpu"
        # strategic patch merges container lists by name
        from kubernetes_tpu.testutil import make_pod

        pod = make_pod("p1")
        rs.create("Pod", pod.to_dict())
        out = rs.patch(
            "Pod", "default", "p1",
            {"spec": {"containers": [{"name": "c0", "image": "new:v2"}]}},
            patch_type="strategic")
        assert out["spec"]["containers"][0]["image"] == "new:v2"
        # json patch
        out = rs.patch("Pod", "default", "p1",
                       [{"op": "replace", "path": "/metadata/labels",
                         "value": {"patched": "yes"}}],
                       patch_type="json")
        assert out["metadata"]["labels"] == {"patched": "yes"}
        # bad json-patch op -> 422 error surfaced
        import pytest as _p

        with _p.raises(Exception):
            rs.patch("Pod", "default", "p1",
                     [{"op": "remove", "path": "/metadata/ghost"}],
                     patch_type="json")
    finally:
        server.stop()


def test_remote_kubelet_uses_field_selector():
    """A remote hollow kubelet lists only ITS pods via fieldSelector —
    never the whole cluster."""
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.client.remote import RemoteStore
    from kubernetes_tpu.kubelet.hollow import HollowKubelet
    from kubernetes_tpu.testutil import make_pod

    server = APIServer(Store())
    server.start()
    try:
        cs = Clientset(RemoteStore(server.url))
        kubelet = HollowKubelet(cs, "mine", pod_start_latency=0.0)
        kubelet.register()
        cs.pods.create(make_pod("ours", node_name="mine"))
        cs.pods.create(make_pod("theirs", node_name="other"))
        mine = kubelet._my_pods()
        assert [p.meta.name for p in mine] == ["ours"]
    finally:
        server.stop()


def test_openapi_document_served():
    """/openapi/v2 (and the era's /swagger.json): a machine-readable
    schema generated from the live type registry
    (api/openapi-spec/swagger.json; routes/openapi.go)."""
    import json
    import urllib.request

    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.store import Store

    server = APIServer(Store())
    server.start()
    try:
        for path in ("/openapi/v2", "/swagger.json"):
            with urllib.request.urlopen(server.url + path, timeout=5) as r:
                doc = json.loads(r.read())
            assert doc["swagger"] == "2.0"
            pod = doc["definitions"]["io.k8s.api.core.v1.Pod"]
            assert pod["type"] == "object"
            assert "spec" in pod["properties"]
            assert "containers" in pod["properties"]["spec"]["properties"]
            # paths cover collection + item scope with the right verbs
            item = doc["paths"]["/api/v1/namespaces/{namespace}/pods/{name}"]
            assert set(item) == {"get", "put", "patch", "delete"}
            coll = doc["paths"]["/api/v1/namespaces/{namespace}/pods"]
            assert set(coll) == {"get", "post"}
            # cluster-scoped kinds skip the namespace segment
            assert "/api/v1/nodes/{name}" in doc["paths"]
    finally:
        server.stop()


def test_namespaced_collection_path_routes():
    """The OpenAPI-advertised canonical collection path really routes:
    POST/GET /api/v1/namespaces/{ns}/pods."""
    import json
    import urllib.request

    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.store import Store
    from kubernetes_tpu.testutil import make_pod

    server = APIServer(Store())
    server.start()
    try:
        body = json.dumps(make_pod("via-path").to_dict()).encode()
        req = urllib.request.Request(
            server.url + "/api/v1/namespaces/default/pods", data=body,
            method="POST", headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=5) as r:
            assert r.status == 201
        with urllib.request.urlopen(
                server.url + "/api/v1/namespaces/default/pods", timeout=5) as r:
            items = json.loads(r.read())["items"]
        assert [i["metadata"]["name"] for i in items] == ["via-path"]
        # another namespace's collection is empty
        with urllib.request.urlopen(
                server.url + "/api/v1/namespaces/other/pods", timeout=5) as r:
            assert json.loads(r.read())["items"] == []
    finally:
        server.stop()
