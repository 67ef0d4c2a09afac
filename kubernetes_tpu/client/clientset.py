"""Typed client layer over the store.

The capability of the reference's generated clientsets
(``staging/src/k8s.io/client-go/kubernetes``): typed create/get/list/
update/delete/watch per kind, plus the two special verbs the control plane
runs on:

- ``PodClient.bind`` — the Binding subresource
  (``pkg/registry/core/pod/storage/storage.go:128 BindingREST``): the ONLY
  way a placement is committed; a CAS update that sets ``spec.nodeName``
  and fails if the pod is already bound to a different node.
- ``update_status`` — status subresource semantics (spec untouched).

In-process today (function calls instead of HTTPS+protobuf), but the
interface is transport-shaped: everything passes through serialization, so
a wire transport can be slotted under ``Clientset`` without touching
callers.
"""

from __future__ import annotations

import copy
from typing import Callable, Optional, Type

from ..api import lazy as lazy_mod
from ..api import types as api
from ..store.store import Store, Watch


# Kinds whose objects live outside any namespace (reference: node is
# cluster-scoped; its store key is the bare name).  Populated by the type
# registry (api.types.register_kind).
from ..api.types import CLUSTER_SCOPED_KINDS  # noqa: E402


class TypedClient:
    def __init__(self, store: Store, kind: str, cls: Type):
        self._store = store
        self.kind = kind
        self._cls = cls
        self.default_namespace = "" if kind in CLUSTER_SCOPED_KINDS else "default"
        import inspect

        def _takes_trusted(fn) -> bool:
            if fn is None:
                return False
            try:
                return "_trusted" in inspect.signature(fn).parameters
            except (TypeError, ValueError):
                return False

        self._trusted_create = _takes_trusted(store.create)
        self._trusted_create_many = _takes_trusted(
            getattr(store, "create_many", None))

        def _takes_frames(fn) -> bool:
            try:
                return "frames" in inspect.signature(fn).parameters
            except (TypeError, ValueError):
                return False

        # column-packed watch delivery (store/frames.py): opt-in per
        # watcher, and only when the transport speaks it — a pre-frame
        # store silently degrades to per-event delivery
        self._watch_frames = _takes_frames(store.watch)

    def _ns(self, namespace: Optional[str]) -> str:
        """Resolve the effective namespace.  Cluster-scoped kinds ignore any
        caller/object namespace (reference: the registry's scope strategy,
        not the caller, decides key shape) — otherwise an ObjectMeta
        carrying the "default" namespace stores the object where
        cluster-scoped get/update can never find it."""
        if self.default_namespace == "":
            return ""
        return self.default_namespace if namespace is None else namespace

    def _to_wire(self, obj) -> dict:
        d = obj.to_dict()
        meta = d.setdefault("metadata", {})
        meta["namespace"] = self._ns(meta.get("namespace"))
        return d

    def _decode(self, d: dict):
        """Decode a store response: a lazy view on the zero-copy path
        (callers that never read the result — fire-and-forget creates,
        heartbeat updates — pay nothing; readers promote on touch), the
        eager typed decode on the compatibility path."""
        if lazy_mod.ENABLED:
            return lazy_mod.lazy_class(self._cls)(d)
        return self._cls.from_dict(d)

    def _create_raw(self, obj) -> dict:
        """One store create over the freshly built wire dict.  Stores
        whose create accepts ``_trusted`` (the in-process one) take it
        without a defensive deep copy — ``to_dict`` output is private by
        construction; other transports get the plain call."""
        if self._trusted_create:
            return self._store.create(self.kind, self._to_wire(obj),
                                      _trusted=True)
        return self._store.create(self.kind, self._to_wire(obj))

    def create(self, obj):
        return self._decode(self._create_raw(obj))

    def create_nowait(self, obj) -> None:
        """``create`` without decoding the stored object back — for
        fire-and-forget writers (the event sink) where the return decode
        is pure overhead on a contended thread."""
        self._create_raw(obj)

    def _create_many_raw(self, objs) -> list:
        """Batch create through the store's one-txn path when the
        transport offers it (``Store.create_many``: one lock/WAL/fanout
        pass for the whole list), else a per-object loop with identical
        semantics.  Items that fail (already exists) come back as None;
        the rest commit — the best-effort contract batch writers want."""
        wires = [self._to_wire(o) for o in objs]
        fn = getattr(self._store, "create_many", None)
        if fn is not None:
            if self._trusted_create_many:
                return fn(self.kind, wires, _trusted=True)
            return fn(self.kind, wires)
        out = []
        for w in wires:
            try:
                out.append(self._store.create(self.kind, w))
            except Exception:  # noqa: BLE001 - per-item best effort
                out.append(None)
        return out

    def create_many(self, objs) -> list:
        """Batch create; one decoded object (or None) per input, in order."""
        return [self._decode(d) if d is not None else None
                for d in self._create_many_raw(objs)]

    def create_many_nowait(self, objs) -> None:
        """Batch create for fire-and-forget writers (the event sink's
        whole drained chunk, a bench wave's arrivals): no return decode."""
        self._create_many_raw(objs)

    def get(self, name: str, namespace: Optional[str] = None):
        return self._decode(self._store.get(self.kind, self._ns(namespace), name))

    def list(self, namespace: Optional[str] = None):
        if namespace is not None:
            namespace = self._ns(namespace)
        dicts, rev = self._store.list(self.kind, namespace)
        return [self._cls.from_dict(d) for d in dicts], rev

    def list_lazy(self, namespace: Optional[str] = None):
        """LIST into decode-on-access views (``api/lazy.py``): same
        objects semantically, but ``from_dict`` is deferred until a field
        is actually read — the informer seed path's zero-copy arm."""
        if namespace is not None:
            namespace = self._ns(namespace)
        dicts, rev = self._store.list(self.kind, namespace)
        cls = lazy_mod.lazy_class(self._cls)
        return [cls(d) for d in dicts], rev

    def list_columns(self):
        """Packed column batch for kinds with a columnar emitter (Pod),
        when the transport supports it; None otherwise (callers fall
        back to :meth:`list_lazy`/:meth:`list`)."""
        fn = getattr(self._store, "list_columns", None)
        if fn is None:
            return None
        return fn(self.kind)

    def update(self, obj):
        return self._decode(self._store.update(self.kind, self._to_wire(obj)))

    def guaranteed_update(self, name: str, mutate: Callable, namespace: Optional[str] = None):
        """mutate receives a typed object, returns the new typed object."""
        namespace = self._ns(namespace)

        def _mutate_dict(d: dict) -> dict:
            return mutate(self._cls.from_dict(d)).to_dict()

        return self._cls.from_dict(
            self._store.guaranteed_update(self.kind, namespace, name, _mutate_dict)
        )

    def update_status(self, obj):
        """Write only .status (+ heartbeat metadata), preserving concurrent
        spec/label changes, like the /status subresource."""
        status = obj.to_dict().get("status")

        def _mutate(cur):
            d = cur.to_dict()
            d["status"] = copy.deepcopy(status)
            return self._cls.from_dict(d)

        return self.guaranteed_update(obj.meta.name, _mutate, obj.meta.namespace)

    def delete(self, name: str, namespace: Optional[str] = None):
        return self._cls.from_dict(self._store.delete(self.kind, self._ns(namespace), name))

    def watch(self, from_revision: Optional[int] = None,
              frames: bool = False) -> Watch:
        """``frames=True`` requests column-packed batch delivery (a
        correlated store txn as WatchFrames of at most
        ``frames.FRAME_MAX_ROWS`` rows) when the transport supports
        it; per-event otherwise.  Only frame-aware consumers (the
        informer's batch apply) should opt in."""
        if frames and self._watch_frames:
            return self._store.watch(self.kind, from_revision, frames=True)
        return self._store.watch(self.kind, from_revision)


class PodClient(TypedClient):
    def __init__(self, store: Store):
        super().__init__(store, "Pod", api.Pod)

    def bind(self, binding: api.Binding) -> None:
        """Commit a placement (BindingREST.Create → assignPod →
        setPodHostAndAnnotations, ``storage.go:141,157,191``).

        Operates at the wire-dict level — no typed round-trip.  This is the
        scheduler's hottest write (one per scheduled pod; the batch path
        issues hundreds of thousands), so it must stay O(small-dict-copy)."""

        def _assign(d: dict) -> dict:
            cur = (d.get("spec") or {}).get("nodeName", "")
            if cur and cur != binding.node_name:
                raise BindConflictError(
                    f"pod {binding.pod_namespace}/{binding.pod_name} already bound to {cur}"
                )
            d.setdefault("spec", {})["nodeName"] = binding.node_name
            return d

        self._store.guaranteed_update(
            "Pod", binding.pod_namespace, binding.pod_name, _assign
        )

    def bind_many(self, bindings: api.BindingColumns) -> list[Optional[str]]:
        """Batch placement commit (one store txn): the two columns of
        ``bindings`` go to the store's ``bind_many`` as they are; per-row
        error or None."""
        return self._store.bind_many(bindings.keys, bindings.node_names)

    def evict(self, name: str, namespace: Optional[str] = None) -> None:
        """PDB-aware voluntary eviction — the ``pods/eviction`` subresource
        (reference ``pkg/registry/core/pod/rest/eviction.go``): every PDB
        selecting the pod must have ``disruptionsAllowed > 0``; the budget
        is CAS-decremented before the delete so racing evictions cannot
        overdraw it (the disruption controller replenishes)."""
        from ..api.selectors import LabelSelector
        from ..store.store import ConflictError

        if namespace is None:
            namespace = self.default_namespace
        pod = self.get(name, namespace)
        pdbs, _ = self._store.list("PodDisruptionBudget", namespace)
        charged: list[str] = []
        try:
            for pdb in pdbs:
                sel = LabelSelector.from_dict((pdb.get("spec") or {}).get("selector"))
                if not sel.matches(pod.meta.labels):
                    continue
                pdb_name = pdb["metadata"]["name"]

                def _decrement(cur: dict) -> dict:
                    status = cur.setdefault("status", {})
                    allowed = int(status.get("disruptionsAllowed", 0))
                    if allowed <= 0:
                        raise EvictionDisallowed(
                            f"cannot evict {namespace}/{name}: PDB {pdb_name} "
                            "allows no disruptions"
                        )
                    status["disruptionsAllowed"] = allowed - 1
                    return cur

                self._store.guaranteed_update(
                    "PodDisruptionBudget", namespace, pdb_name, _decrement
                )
                charged.append(pdb_name)
            self.delete(name, namespace)
        except Exception:
            # roll the budget back for any PDB already charged
            for pdb_name in charged:
                def _refund(cur: dict) -> dict:
                    status = cur.setdefault("status", {})
                    status["disruptionsAllowed"] = int(status.get("disruptionsAllowed", 0)) + 1
                    return cur

                try:
                    self._store.guaranteed_update(
                        "PodDisruptionBudget", namespace, pdb_name, _refund
                    )
                except KeyError:
                    pass
            raise


class BindConflictError(Exception):
    pass


class EvictionDisallowed(Exception):
    """Eviction refused by a PodDisruptionBudget (HTTP 429 in the
    reference's eviction subresource)."""


class Clientset:
    """One handle per registered kind (``clientset.Interface`` analogue),
    exposed under the kind's plural resource name (``cs.pods``,
    ``cs.daemonsets``, …).  Kinds registered later (e.g. CRDs) are
    reachable via ``client_for``."""

    def __init__(self, store: Store):
        self.store = store
        self.pods = PodClient(store)
        self._by_kind: dict[str, TypedClient] = {"Pod": self.pods}
        for kind, cls in api.KINDS.items():
            if kind == "Pod":
                continue
            client = TypedClient(store, kind, cls)
            self._by_kind[kind] = client
            setattr(self, api.KIND_PLURALS[kind], client)

    def client_for(self, kind: str) -> TypedClient:
        if kind not in self._by_kind:
            # kind registered after construction (CRD): build on demand
            cls = api.KINDS.get(kind)
            if cls is None:
                raise KeyError(kind)
            self._by_kind[kind] = TypedClient(self.store, kind, cls)
        return self._by_kind[kind]
