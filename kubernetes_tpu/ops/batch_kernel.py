"""The batched scheduling kernel: a `lax.scan` over the pod batch where
every step is fully vectorized over the node axis.

This replaces the reference's per-pod ``scheduleOne`` loop
(``scheduler.go:253``) + 16-goroutine node parallel-for
(``generic_scheduler.go:204``, SURVEY.md P1): the node axis becomes the
TPU's vector axis (and the sharded mesh axis for multi-chip), and the
sequential-greedy cache feedback the oracle gets from ``assume`` becomes
the scan carry.  Bit-parity with the oracle holds because every operation
is int32 fixed-point (see ``scheduler/units.py``) and the selection rule
(feasibility mask → integer weighted score → argmax with round-robin
tie-break in node-axis order, counter bumped only when ≥2 nodes are
feasible — the reference's ``selectHost``/``lastNodeIndex`` semantics) is
identical on both paths.

Memory shape: dynamic state is O(N·R + G·N); per-pod static data is
O(G·N) via equivalence signatures — nothing is ever [P, N].
"""

from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.snapshot import BatchStatic, InitialState
from ..scheduler.units import FIXED_POINT_ONE, MAX_PRIORITY
from ..utils import tracing

INT32_MIN = jnp.int32(-(2**31))
INT32_MAX = jnp.int32(2**31 - 1)

WEIGHT_KEYS = ("least", "most", "balanced", "spread", "node_affinity", "taint", "interpod")


class ScanState(NamedTuple):
    requested: jnp.ndarray  # [N, R] int32
    nonzero_requested: jnp.ndarray  # [N, 2] int32
    pod_count: jnp.ndarray  # [N] int32
    ports_used: jnp.ndarray  # [N, Pv] bool
    spread_counts: jnp.ndarray  # [G, N] int32
    round_robin: jnp.ndarray  # [] int32
    # phase B: affinity-term domain counters + volume occupancy
    dm: jnp.ndarray  # [T, N] int32 pods matching term t in node n's domain
    downer: jnp.ndarray  # [T, N] int32 placed term owners in node n's domain
    total_match: jnp.ndarray  # [T] int32 pods matching term t anywhere
    vol_any: jnp.ndarray  # [V, N] bool
    vol_ns: jnp.ndarray  # [V, N] bool non-sharable instance present
    nk: jnp.ndarray  # [K, N] int32 distinct limited-kind disks
    # frontier mode: per-signature monotone-feasibility plane.  Row g is
    # ANDed each step a sig-g pod is processed with the MONOTONE filter
    # components (resource fit, pod-count, ports, required-anti-affinity
    # hits) — once a column goes infeasible for g it can never come back
    # within the segment, so still_ok over-approximates every FUTURE
    # pod's feasibility and its G-union is a safe compaction mask.  The
    # non-monotone terms (own required-affinity / first-pod rule, which
    # dm growth can turn BACK on) deliberately stay out.  None outside
    # frontier mode (an empty pytree leaf: zero carry cost).
    still_ok: "jnp.ndarray | None" = None  # [G, N] bool


class StaticArrays(NamedTuple):
    """Device-resident static arrays (a pytree of arrays only — scalars that
    change compilation live in the cached-runner key instead)."""

    node_exists: jnp.ndarray  # [N] bool
    node_alloc: jnp.ndarray  # [N, R] int32
    node_alloc_pods: jnp.ndarray  # [N] int32
    node_zone: jnp.ndarray  # [N] int32
    static_ok: jnp.ndarray  # [G, N] bool
    node_aff_raw: jnp.ndarray  # [G, N] int32
    taint_intol_raw: jnp.ndarray  # [G, N] int32
    static_score: jnp.ndarray  # [G, N] int32
    interpod_raw: jnp.ndarray  # [G, N] int32
    g_request: jnp.ndarray  # [G, R] int32
    g_nonzero: jnp.ndarray  # [G, 2] int32
    g_ports: jnp.ndarray  # [G, Pv] bool
    g_has_spread: jnp.ndarray  # [G] bool
    spread_inc: jnp.ndarray  # [G, G] int32
    # phase B: the batch's own (anti)affinity terms
    term_matches_sig: jnp.ndarray  # [T, G] bool
    sym_w: jnp.ndarray  # [T] int32
    own_w: jnp.ndarray  # [G, T] int32
    own_ra: jnp.ndarray  # [G, T] bool
    own_raa: jnp.ndarray  # [G, T] bool
    own_all: jnp.ndarray  # [G, T] bool
    is_raa: jnp.ndarray  # [T] bool
    self_match: jnp.ndarray  # [T] bool
    node_domain: jnp.ndarray  # [T, N] int32 (trash slot id where key absent)
    dom_valid: jnp.ndarray  # [T, N] bool
    # phase B: volumes (identity rides the per-pod xs slots, not here)
    vol_limits: jnp.ndarray  # [K] int32


class DeviceNodeCache:
    """Device-resident node-axis static tensors, kept across segments and
    waves.

    ``BatchStatic.node_token`` — (instance nonce, epoch, version) stamped
    by the tensorizer's ``NodeStaticRows`` — names the node-axis state
    the host arrays were built from; the nonce keeps tokens from a
    swapped-in tensorizer (fresh epoch counter) from aliasing a stale
    cache.  Same token → the previous device
    buffers are reused with NO host→device transfer (every segment of a
    wave, and every wave against an unchanged fleet: the arrays are pure
    functions of the node objects, which the token versions).  On a new
    token the incremental path diffs each HOST array against the cached
    host copy and writes only the changed columns (``.at[js].set``) —
    diffing the arrays themselves, not trusting the dirty-node list,
    because a single node change can move OTHER columns' values (e.g. a
    zone relabel shifts the first-occurrence zone_vocab ids of every
    node).  Bulk changes fall back to a full upload — always correct,
    just not incremental."""

    FIELDS = ("node_exists", "node_alloc", "node_alloc_pods", "node_zone")

    def __init__(self):
        self._token = None
        self._arrays = None
        self._host = None  # host-side copies backing the device arrays
        self._mesh = None
        self._mesh_key = None
        self.stats = {"reuses": 0, "col_updates": 0, "uploads": 0,
                      "dirty_cols": 0, "cols_total": 0,
                      "shard_dirty_cols": [], "shard_cols_total": []}

    def set_mesh(self, mesh) -> None:
        """Bind (or clear, ``mesh=None``) the node-axis mesh uploads are
        committed to.  The mesh identity joins the cache token, so
        sharded and single-device entries never alias; binding a
        different mesh simply misses on the next lookup and re-uploads.
        Also (re)sets the per-shard dirty/total column counters the
        scheduler's per-shard upload-fraction attribution reads."""
        if mesh is None:
            key, n_shards = None, 0
        else:
            key = (tuple(mesh.shape.items()),
                   tuple(int(d.id) for d in mesh.devices.flat))
            n_shards = int(mesh.size)
        if key != self._mesh_key:
            self._mesh = mesh
            self._mesh_key = key
            self.stats["shard_dirty_cols"] = [0] * n_shards
            self.stats["shard_cols_total"] = [0] * n_shards

    def _note_shard_dirty(self, js, n: int) -> None:
        """Attribute dirty columns to the shard that will receive the
        upload bytes (``js=None`` = full-plane rewrite)."""
        ns = len(self.stats["shard_dirty_cols"])
        if not ns or n % ns:
            return
        n_loc = n // ns
        if js is None:
            for s in range(ns):
                self.stats["shard_dirty_cols"][s] += n_loc
        else:
            counts = np.bincount(
                np.asarray(js, dtype=np.int64) // n_loc, minlength=ns)
            for s in range(ns):
                self.stats["shard_dirty_cols"][s] += int(counts[s])

    def _note_shard_total(self, n: int) -> None:
        ns = len(self.stats["shard_cols_total"])
        if not ns or n % ns:
            return
        n_loc = n // ns
        for s in range(ns):
            self.stats["shard_cols_total"][s] += n_loc

    def _shard_put(self, arr):
        """Host→device with the node axis partitioned over the bound
        mesh.  Widths that don't divide the shard count fall back to a
        plain transfer (the sharded dispatch path pads segment widths to
        the shard count, so this only triggers for cache users outside
        the sharded loop — correct either way, GSPMD follows whatever
        sharding the inputs carry)."""
        if self._mesh is None or int(arr.shape[0]) % max(int(self._mesh.size), 1):
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec
        axis = tuple(self._mesh.shape.keys())[0]
        spec = PartitionSpec(*([axis] + [None] * (arr.ndim - 1)))
        return jax.device_put(np.asarray(arr), NamedSharding(self._mesh, spec))

    @staticmethod
    def _host_val(static: BatchStatic, f: str):
        """The field as the DEVICE wants it: node_alloc is resource-axis
        sliced here (not after the cache) so the cached buffer IS the
        buffer the kernel consumes — repeated same-token calls return
        identical device arrays with no per-segment gather."""
        arr = getattr(static, f)
        r_sel = getattr(static, "r_sel", None)
        if f == "node_alloc" and r_sel is not None:
            arr = arr[:, r_sel]
        return arr

    def _token_for(self, static: BatchStatic):
        tok = static.node_token
        r_sel = getattr(static, "r_sel", None)
        if tok is not None and r_sel is not None:
            # a changed resource selection changes the cached node_alloc
            # SHAPE — it must never alias a same-(epoch, version) entry
            tok = tok + (tuple(int(r) for r in r_sel),)
        if tok is not None and self._mesh_key is not None:
            # sharded placements must never alias single-device entries
            tok = tok + (self._mesh_key,)
        return tok

    def _upload(self, static: BatchStatic) -> tuple:
        return tuple(self._shard_put(self._host_val(static, f))
                     for f in self.FIELDS)

    @staticmethod
    def _changed_cols(new: np.ndarray, old: np.ndarray):
        diff = new != old
        if diff.ndim > 1:
            diff = diff.any(axis=tuple(range(1, diff.ndim)))
        return np.nonzero(diff)[0]

    def node_arrays(self, static: BatchStatic) -> tuple:
        tok = self._token_for(static)
        n = len(static.node_exists)
        if tok is None:
            # cache bypassed (no persistent rows): a full upload every
            # call — counted as all-dirty so the upload-fraction metric
            # reads 1.0, not a spurious "fully resident"
            self.stats["uploads"] += 1
            self.stats["dirty_cols"] += n
            self.stats["cols_total"] += n
            self._note_shard_dirty(None, n)
            self._note_shard_total(n)
            return self._upload(static)
        self.stats["cols_total"] += n
        self._note_shard_total(n)
        if self._token == tok and self._arrays is not None:
            self.stats["reuses"] += 1
            return self._arrays
        host = tuple(np.array(self._host_val(static, f)) for f in self.FIELDS)
        incremental = (
            self._arrays is not None and self._host is not None
            and self._token is not None and self._token[0] == tok[0]
            and all(h.shape == o.shape for h, o in zip(host, self._host)))
        if incremental:
            arrays = []
            dirty_total = 0
            for new_h, old_h, arr in zip(host, self._host, self._arrays):
                js = self._changed_cols(new_h, old_h)
                dirty_total += len(js)
                self._note_shard_dirty(js, n)
                if len(js) == 0:
                    arrays.append(arr)
                elif len(js) <= max(1, n // 8):
                    # in-place column scatter: GSPMD keeps the result on
                    # the input's (possibly node-sharded) placement, so
                    # only the owning shards receive update bytes
                    jdev = jnp.asarray(js.astype(np.int32))
                    arrays.append(arr.at[jdev].set(jnp.asarray(new_h[js])))
                else:
                    arrays.append(self._shard_put(new_h))
            arrays = tuple(arrays)
            self.stats["col_updates"] += 1
            self.stats["dirty_cols"] += dirty_total
        else:
            arrays = self._upload(static)
            self.stats["uploads"] += 1
            self.stats["dirty_cols"] += n
            self._note_shard_dirty(None, n)
        self._token = tok
        self._arrays = arrays
        self._host = host
        return arrays


def to_device(static: BatchStatic,
              node_cache: "DeviceNodeCache | None" = None) -> StaticArrays:
    # resource-axis tightening: slots no signature in the segment requests
    # are inert in the step (`g_req > 0` masks them to True in fit, and
    # the commit adds zero), so the device arrays carry only the selected
    # slots.  r_sel always keeps CPU_MILLI/MEM_MIB at positions 0/1 — the
    # scoring formulas index them positionally.  Host arrays stay
    # full-width for the oracle/commit paths; the slice happens at upload
    # (DeviceNodeCache._host_val on the cached path, here otherwise).
    r_sel = getattr(static, "r_sel", None)
    if node_cache is not None:
        node_exists, node_alloc, node_alloc_pods, node_zone = (
            node_cache.node_arrays(static))
    else:
        node_exists = jnp.asarray(static.node_exists)
        node_alloc = jnp.asarray(
            static.node_alloc if r_sel is None else static.node_alloc[:, r_sel])
        node_alloc_pods = jnp.asarray(static.node_alloc_pods)
        node_zone = jnp.asarray(static.node_zone)
    g_request = static.g_request
    if r_sel is not None:
        g_request = g_request[:, r_sel]
    return StaticArrays(
        node_exists=node_exists,
        node_alloc=node_alloc,
        node_alloc_pods=node_alloc_pods,
        node_zone=node_zone,
        static_ok=jnp.asarray(static.static_ok),
        node_aff_raw=jnp.asarray(static.node_aff_raw),
        taint_intol_raw=jnp.asarray(static.taint_intol_raw),
        static_score=jnp.asarray(static.static_score),
        interpod_raw=jnp.asarray(static.interpod_raw),
        g_request=jnp.asarray(g_request),
        g_nonzero=jnp.asarray(static.g_nonzero),
        g_ports=jnp.asarray(static.g_ports),
        g_has_spread=jnp.asarray(static.g_has_spread),
        spread_inc=jnp.asarray(static.spread_inc),
        term_matches_sig=jnp.asarray(static.term_matches_sig),
        sym_w=jnp.asarray(static.sym_w),
        own_w=jnp.asarray(static.own_w),
        own_ra=jnp.asarray(static.own_ra),
        own_raa=jnp.asarray(static.own_raa),
        own_all=jnp.asarray(static.own_all),
        is_raa=jnp.asarray(static.is_raa),
        self_match=jnp.asarray(static.self_match),
        node_domain=jnp.asarray(static.node_domain),
        dom_valid=jnp.asarray(static.dom_valid),
        vol_limits=jnp.asarray(static.vol_limits),
    )


def batch_xs(static: BatchStatic, min_length: int = 512):
    """Per-pod scan inputs, padded to a power-of-two bucket length so the
    scan's trip count (and therefore the compiled executable) is stable
    across batches: with the backend's max_segment_pods also a power of
    two, every full segment and every tail lands in the same bucket.
    Padded entries carry valid=False and are inert in the step."""
    p_real = len(static.group_of_pod)
    p_pad = max(min_length, 1)
    while p_pad < p_real:
        p_pad *= 2
    w = static.pod_vol_ids.shape[1]
    gids = np.zeros(p_pad, dtype=np.int32)
    gids[:p_real] = static.group_of_pod
    pvalid = np.zeros(p_pad, dtype=bool)
    pvalid[:p_real] = True
    vids = np.full((p_pad, w), static.v_state - 1, dtype=np.int32)
    vids[:p_real] = static.pod_vol_ids
    vval = np.zeros((p_pad, w), dtype=bool)
    vval[:p_real] = static.pod_vol_valid
    vro = np.zeros((p_pad, w), dtype=bool)
    vro[:p_real] = static.pod_vol_ro_ok
    vkind = np.zeros((p_pad, w), dtype=np.int32)
    vkind[:p_real] = static.pod_vol_kind
    vco = np.zeros((p_pad, w), dtype=bool)
    if static.pod_vol_count_only is not None:
        vco[:p_real] = static.pod_vol_count_only
    return (
        jnp.asarray(gids),
        jnp.asarray(pvalid),
        jnp.asarray(vids),
        jnp.asarray(vval),
        jnp.asarray(vro),
        jnp.asarray(vkind),
        jnp.asarray(vco),
    )


def state_to_device(init: InitialState, r_sel=None,
                    use_frontier: bool = False) -> ScanState:
    requested = init.requested if r_sel is None else init.requested[:, r_sel]
    return ScanState(
        requested=jnp.asarray(requested),
        nonzero_requested=jnp.asarray(init.nonzero_requested),
        pod_count=jnp.asarray(init.pod_count),
        ports_used=jnp.asarray(init.ports_used),
        spread_counts=jnp.asarray(init.spread_counts),
        round_robin=jnp.asarray(init.round_robin, dtype=jnp.int32),
        dm=jnp.asarray(init.dm),
        downer=jnp.asarray(init.downer),
        total_match=jnp.asarray(init.total_match),
        vol_any=jnp.asarray(init.vol_any),
        vol_ns=jnp.asarray(init.vol_ns),
        nk=jnp.asarray(init.nk),
        still_ok=(jnp.asarray(init.still_ok)
                  if use_frontier and init.still_ok is not None else None),
    )


# -- fixed-point scoring pieces (must mirror scheduler/priorities.py) -------


def _idiv(a, b):
    """int32 floor division, bit-identical to ``a // b`` on every lane the
    scoring formulas SELECT, computed as an f32 division plus a one-step
    integer fixup — variable-divisor int32 division has no SIMD lowering
    on CPU and scalarized into the single most expensive scoring op.

    Exactness: every selected lane of every caller has divisor 1 <= b <=
    2^24 (node capacities, normalization maxima) and true quotient |q| <=
    MAX_PRIORITY * FIXED_POINT_ONE = 10 * 1024 = 10240 < 2^23 (any
    quotient below 2^23 keeps the argument; the current scale has 64x
    headroom), so the f32 estimate (one input rounding of a, one
    correctly-rounded divide; b exact) is within |q| * 2^-22 < 1 of q —
    its floor is off by at most one, and the remainder fixup lands
    exactly on floor(a / b).  Masked-out lanes (infeasible nodes, guard
    branches of jnp.where) may hold garbage either way; they are never
    selected."""
    q0 = jnp.floor(a.astype(jnp.float32) / b.astype(jnp.float32)).astype(jnp.int32)
    r = a - q0 * b
    return q0 - (r < 0).astype(jnp.int32) + (r >= b).astype(jnp.int32)


def _usage_score(requested, capacity, most: bool):
    """least/most-requested per-resource score with the reference's guards
    (capacity==0 -> 0, requested > capacity -> 0)."""
    safe_cap = jnp.maximum(capacity, 1)
    if most:
        raw = _idiv(requested * MAX_PRIORITY, safe_cap)
    else:
        raw = _idiv((capacity - requested) * MAX_PRIORITY, safe_cap)
    return jnp.where((capacity == 0) | (requested > capacity), 0, raw)


def _balanced_score(cpu_req, cpu_cap, mem_req, mem_cap):
    f_cpu = _idiv(cpu_req * FIXED_POINT_ONE, jnp.maximum(cpu_cap, 1))
    f_mem = _idiv(mem_req * FIXED_POINT_ONE, jnp.maximum(mem_cap, 1))
    diff = jnp.abs(f_cpu - f_mem)
    score = (MAX_PRIORITY * FIXED_POINT_ONE - diff * MAX_PRIORITY) // FIXED_POINT_ONE
    bad = (cpu_cap == 0) | (mem_cap == 0) | (cpu_req >= cpu_cap) | (mem_req >= mem_cap)
    return jnp.where(bad, 0, score)


# -- cross-shard collective seams -------------------------------------------
# Identity when ``axis_name`` is None (the single-device path): the same
# step serves both the plain jit and the shard_map-wrapped wave loop, and
# these helpers are the ONLY points where shards communicate — everything
# else in the step is elementwise on the local node columns.


def _ax_sum(x, axis_name):
    return x if axis_name is None else jax.lax.psum(x, axis_name)


def _ax_max(x, axis_name):
    return x if axis_name is None else jax.lax.pmax(x, axis_name)


def _ax_min(x, axis_name):
    return x if axis_name is None else jax.lax.pmin(x, axis_name)


def _ax_any(mask, axis_name):
    """``jnp.any`` over the (possibly sharded) trailing node axis."""
    if axis_name is None:
        return jnp.any(mask, axis=-1)
    return _ax_sum(jnp.sum(mask.astype(jnp.int32), axis=-1), axis_name) > 0


def _ax_first_true(mask, offset, axis_name):
    """Global node index of the FIRST true column in GLOBAL node order:
    ``argmax`` on one device, a deterministic min-over-global-index tree
    reduce across shards (each shard offers ``offset + local_argmax`` or
    INT32_MAX when it has no hit).  Ordering by global index — never by
    shard arrival — is what keeps round-robin tie rotation bit-exact
    against the CPU oracle.  All-false masks yield INT32_MAX (sharded) /
    0 (single device); every caller guards on feasibility counts before
    consuming the result."""
    local = jnp.argmax(mask).astype(jnp.int32)
    if axis_name is None:
        return local
    cand = jnp.where(jnp.any(mask), offset + local, INT32_MAX)
    return _ax_min(cand, axis_name)


def _normalized_max(raw, feasible, reverse: bool, axis_name=None):
    """NormalizeReduce: 10*raw//max over feasible (0 if max==0); reversed
    variant returns 10 when max==0."""
    max_c = _ax_max(jnp.max(jnp.where(feasible, raw, 0)), axis_name)
    if reverse:
        return jnp.where(
            max_c > 0, _idiv(MAX_PRIORITY * (max_c - raw), jnp.maximum(max_c, 1)), MAX_PRIORITY
        )
    return jnp.where(max_c > 0, _idiv(MAX_PRIORITY * raw, jnp.maximum(max_c, 1)), 0)


def make_step(
    dev: StaticArrays, num_zones: int, w: dict, use_terms: bool = True,
    use_vols: bool = True, use_ports: bool = True, use_frontier: bool = False,
    axis_name: "str | None" = None,
):
    """Builds the scan step: (state, xs) -> (state', chosen_node).

    ``use_terms`` / ``use_vols`` / ``use_ports`` are compile-time flags
    (part of the cached runner key): segments whose batch carries no
    (anti)affinity terms, no direct-disk volumes, or no host ports skip
    those blocks entirely instead of paying the gather/scatter cost on
    inert state every step.

    ``use_frontier`` additionally maintains the ``still_ok`` carry plane
    (see ScanState): the current signature's row is ANDed with the
    monotone filter components each step, so a chunked caller can read
    the G-union between chunks and compact the node axis (frontier
    scan).  Off, the plane stays None and the step is unchanged.

    ``axis_name`` names the node-axis mesh dimension when the step runs
    under ``shard_map``: ``dev``/``state`` node planes are then per-shard
    slices and every whole-axis reduce below goes through the ``_ax_*``
    collectives so scores, tie sets, and the chosen GLOBAL node index are
    identical to the single-device trace.  None (the default) keeps every
    reduce local and the step byte-for-byte equivalent to the unsharded
    kernel."""

    n_local = dev.node_exists.shape[0]  # per-shard width under shard_map
    if axis_name is None:
        offset = jnp.int32(0)
    else:
        # global index of this shard's first column: shards are laid out
        # in node order along the 1-D mesh, so offset + local index IS
        # the original node-axis position
        offset = jax.lax.axis_index(axis_name).astype(jnp.int32) * n_local
    col_ids = offset + jnp.arange(n_local, dtype=jnp.int32)  # [N] global ids

    # Zone membership as a [Z, N] one-hot contraction matrix, hoisted out
    # of the step (scan treats closed-over values as loop constants): the
    # per-step `.at[zone_idx].add` scatter plus `zsum[zone_idx]` gather
    # scalarize on CPU and were the single most expensive ops of the plain
    # step (~300us/pod at N=5120); the matvec form is SIMD-friendly and
    # bit-identical (int32 adds in a different association order — exact).
    has_zone = dev.node_zone >= 0
    zone_idx = jnp.where(has_zone, dev.node_zone, 0)
    zone_onehot = (
        (jnp.arange(num_zones, dtype=jnp.int32)[:, None] == zone_idx[None, :])
        & has_zone[None, :]
    ).astype(jnp.int32)  # [Z, N]

    def step(state: ScanState, xs):
        # per-pod inputs: signature id, validity (False = scan-length
        # padding), and the pod's volume slots
        gid, pvalid, vol_ids, vol_valid, vol_ro_ok, vol_kind, vol_count_only = xs
        g_req = dev.g_request[gid]  # [R]
        g_nz = dev.g_nonzero[gid]  # [2]
        g_ports = dev.g_ports[gid]  # [Pv]

        # -- feasibility (filters) ------------------------------------
        # kernel: implements GeneralPredicates
        # (resources/pod-count/ports live here; the host/selector parts and
        # the node-condition predicates ride static_ok — models/snapshot.py)
        fit = jnp.all(
            jnp.where(g_req > 0, state.requested + g_req <= dev.node_alloc, True), axis=1
        )
        pods_ok = state.pod_count + 1 <= dev.node_alloc_pods

        feasible = dev.static_ok[gid] & fit & pods_ok & dev.node_exists
        if use_ports:
            ports_ok = ~jnp.any(state.ports_used & g_ports, axis=1)
            feasible = feasible & ports_ok

        if use_terms:
            # kernel: implements MatchInterPodAffinity
            # inter-pod affinity vs ALREADY-PLACED batch pods (the static_ok
            # mask covers existing pods; these domain counters cover the scan
            # carry — the batch generalization of the oracle's work_map feedback)
            m_g = dev.term_matches_sig[:, gid]  # [T] bool: pod in term t's scope
            dm = state.dm  # [T, N] int32 (already key-masked; see InitialState)
            downer = state.downer  # [T, N]
            # symmetry: placed pods' required anti-affinity forbids their
            # domains for matching candidates (predicates.go:1146)
            sym_anti_bad = jnp.any((m_g & dev.is_raa)[:, None] & (downer > 0), axis=0)
            # the pod's own required affinity: some matching pod in-domain, or
            # the first-pod rule (no matching pod anywhere + self-match,
            # predicates.go:1196-1216)
            first_ok = (state.total_match == 0) & dev.self_match  # [T]
            ra_ok = (dm > 0) | first_ok[:, None]  # [T, N]
            own_ra_bad = jnp.any(dev.own_ra[gid][:, None] & ~ra_ok, axis=0)
            # the pod's own required anti-affinity: no matching pod in-domain
            own_raa_bad = jnp.any(dev.own_raa[gid][:, None] & (dm > 0), axis=0)
            feasible = feasible & ~sym_anti_bad & ~own_ra_bad & ~own_raa_bad

        if use_vols:
            # kernel: implements NoDiskConflict, MaxVolumeCount
            # volumes checked against placed state.
            # Only the pod's own <= W slots are touched: gather their [W, N]
            # occupancy rows instead of sweeping the whole [V, N] state.
            rows_any = state.vol_any[vol_ids]  # [W, N]
            rows_ns = state.vol_ns[vol_ids]  # [W, N]
            blocked = jnp.where(vol_ro_ok[:, None], rows_ns, rows_any)
            disk_bad = jnp.any(vol_valid[:, None] & blocked, axis=0)
            new_v = vol_valid[:, None] & ~rows_any  # [W, N] would-be-new instance
            k_range = jnp.arange(dev.vol_limits.shape[0], dtype=jnp.int32)
            k_onehot = (
                (k_range[:, None] == vol_kind[None, :]) & vol_valid[None, :]
            ).astype(jnp.int32)  # [K, W]
            count_new = k_onehot @ new_v.astype(jnp.int32)  # [K, N]
            has_kind = jnp.any(k_onehot > 0, axis=1)  # [K]
            over = has_kind[:, None] & (state.nk + count_new > dev.vol_limits[:, None])
            vol_bad = disk_bad | jnp.any(over, axis=0)
            feasible = feasible & ~vol_bad
        n_feasible = _ax_sum(jnp.sum(feasible.astype(jnp.int32)), axis_name)

        if use_frontier:
            # monotone components ONLY: fit/pods/ports can only get worse
            # as the carry grows, and the required-anti hits (downer / dm
            # only ever increase) likewise — a False here is False for
            # the rest of the segment.  Volume conflicts are per-POD
            # (disk ids are off the signature axis) and own required
            # affinity can RESURRECT (dm growth / first-pod rule), so
            # neither belongs in the plane.  Padded steps (pvalid False)
            # leave the plane untouched.
            mono = fit & pods_ok
            if use_ports:
                mono = mono & ports_ok
            if use_terms:
                mono = mono & ~sym_anti_bad & ~own_raa_bad
            row = state.still_ok[gid]
            still_ok_new = state.still_ok.at[gid].set(
                jnp.where(pvalid, row & mono, row))
        else:
            still_ok_new = state.still_ok

        # -- scores (priorities) --------------------------------------
        cpu_req = state.nonzero_requested[:, 0] + g_nz[0]
        mem_req = state.nonzero_requested[:, 1] + g_nz[1]
        cpu_cap = dev.node_alloc[:, 0]
        mem_cap = dev.node_alloc[:, 1]
        total = dev.static_score[gid]
        if w["least"]:
            s = (_usage_score(cpu_req, cpu_cap, False) + _usage_score(mem_req, mem_cap, False)) // 2
            total = total + w["least"] * s
        if w["most"]:
            s = (_usage_score(cpu_req, cpu_cap, True) + _usage_score(mem_req, mem_cap, True)) // 2
            total = total + w["most"] * s
        if w["balanced"]:
            total = total + w["balanced"] * _balanced_score(cpu_req, cpu_cap, mem_req, mem_cap)
        if w["spread"]:
            cnt = state.spread_counts[gid]  # [N]
            max_n = _ax_max(jnp.max(jnp.where(feasible, cnt, 0)), axis_name)
            node_fp = jnp.where(
                max_n > 0,
                _idiv((max_n - cnt) * (MAX_PRIORITY * FIXED_POINT_ONE), jnp.maximum(max_n, 1)),
                MAX_PRIORITY * FIXED_POINT_ONE,
            )
            # zone blend: counts aggregated over feasible nodes per zone
            # (one-hot matvec, not scatter/gather — see zone_onehot above)
            zsum = _ax_sum(
                zone_onehot @ jnp.where(feasible & has_zone, cnt, 0),
                axis_name)  # [Z], replicated across shards
            max_z = jnp.max(zsum)
            zcnt = zsum @ zone_onehot  # [N]: zsum[zone_idx] without the gather
            zone_fp = jnp.where(
                max_z > 0,
                _idiv((max_z - zcnt) * (MAX_PRIORITY * FIXED_POINT_ONE), jnp.maximum(max_z, 1)),
                MAX_PRIORITY * FIXED_POINT_ONE,
            )
            have_zones = dev.g_has_spread[gid] & _ax_any(
                feasible & has_zone, axis_name)
            total_fp = jnp.where(have_zones & has_zone, (node_fp + 2 * zone_fp) // 3, node_fp)
            total = total + w["spread"] * (total_fp // FIXED_POINT_ONE)
        if w["node_affinity"]:
            total = total + w["node_affinity"] * _normalized_max(
                dev.node_aff_raw[gid], feasible, reverse=False,
                axis_name=axis_name
            )
        if w["taint"]:
            total = total + w["taint"] * _normalized_max(
                dev.taint_intol_raw[gid], feasible, reverse=True,
                axis_name=axis_name
            )
        if w["interpod"]:
            # static (existing pods' symmetric terms) + dynamic: the pod's
            # own soft terms against all matching pods in-domain, and placed
            # batch owners' symmetric terms against this pod
            # (interpod_affinity.go:160-186)
            raw = dev.interpod_raw[gid]
            if use_terms:
                raw = raw + dev.own_w[gid] @ dm + (m_g.astype(jnp.int32) * dev.sym_w) @ downer
            max_c = jnp.maximum(0, _ax_max(
                jnp.max(jnp.where(feasible, raw, INT32_MIN)), axis_name))
            min_c = jnp.minimum(0, _ax_min(
                jnp.min(jnp.where(feasible, raw, INT32_MAX)), axis_name))
            rng = max_c - min_c
            s = jnp.where(rng > 0, _idiv(MAX_PRIORITY * (raw - min_c), jnp.maximum(rng, 1)), 0)
            total = total + w["interpod"] * s

        # -- selection (selectHost) -----------------------------------
        masked = jnp.where(feasible, total, INT32_MIN)
        max_score = _ax_max(jnp.max(masked), axis_name)
        ties = feasible & (total == max_score)
        t_count = _ax_sum(jnp.sum(ties.astype(jnp.int32)), axis_name)
        idx = state.round_robin % jnp.maximum(t_count, 1)
        cum = jnp.cumsum(ties.astype(jnp.int32))
        if axis_name is not None:
            # cross-shard exclusive prefix of tie counts: shifting shard
            # s's local cumsum by the ties on shards < s makes ``cum``
            # the GLOBAL running tie count in node-axis order, so the
            # round-robin pick rotates over the global tie set exactly
            # as the single-device kernel (and the oracle) rotate
            t_local = jnp.sum(ties.astype(jnp.int32))
            all_t = jax.lax.all_gather(t_local, axis_name)  # [S]
            me = jax.lax.axis_index(axis_name)
            shard_ids = jnp.arange(all_t.shape[0], dtype=jnp.int32)
            cum = cum + jnp.sum(jnp.where(shard_ids < me, all_t, 0))
        pick_among_ties = _ax_first_true(
            ties & (cum == idx + 1), offset, axis_name)
        only = _ax_first_true(feasible, offset, axis_name)
        chosen = jnp.where(
            (n_feasible == 0) | ~pvalid,
            jnp.int32(-1),
            jnp.where(n_feasible == 1, only, pick_among_ties).astype(jnp.int32),
        )
        # reference: selectHost (and its counter) runs only when >=2 feasible
        rr = state.round_robin + ((n_feasible >= 2) & pvalid).astype(jnp.int32)

        # -- commit (assume) ------------------------------------------
        landed = chosen >= 0
        safe = jnp.maximum(chosen, 0)
        # ``chosen``/``safe`` are GLOBAL node indices (replicated across
        # shards); comparing against ``col_ids`` lands the onehot on the
        # owning shard's local column and zeros everywhere else
        onehot = (col_ids == safe) & landed
        oh_i = onehot.astype(jnp.int32)
        # the chosen node's column, extracted by onehot CONTRACTION, never
        # by dynamic slice: a traced index into the SHARDED node axis makes
        # GSPMD all-gather the whole [T, N]/[W, N] plane every step (the
        # exact regression assert_collective_structure guards against); the
        # contraction is elementwise on the shard + an O(T) all-reduce
        safe_onehot = col_ids == safe
        if use_terms:
            # affinity domain counters, expanded over nodes: the landed pod
            # counts toward every node sharing the chosen node's topology
            # domain for each term it matches/owns — a scatter-free
            # elementwise same-domain mask (no-op when the chosen node lacks
            # the key, mirroring the old trash-slot semantics)
            d_at_safe = _ax_sum(
                (dev.node_domain
                 * safe_onehot[None, :].astype(jnp.int32)).sum(axis=1),
                axis_name)  # [T]
            valid_at_safe = _ax_any(
                dev.dom_valid & safe_onehot[None, :], axis_name)  # [T]
            same_dom = (
                (dev.node_domain == d_at_safe[:, None])
                & dev.dom_valid
                & valid_at_safe[:, None]
            )  # [T, N]
            m_i = (m_g & landed).astype(jnp.int32)
            own_i = (dev.own_all[gid] & landed).astype(jnp.int32)
            dm_new = state.dm + same_dom * m_i[:, None]
            downer_new = state.downer + same_dom * own_i[:, None]
            total_match = state.total_match + m_i
        else:
            dm_new, downer_new, total_match = state.dm, state.downer, state.total_match
        if use_vols:
            # volume occupancy on the chosen node: scatter the pod's slots
            # into the [V, N] maps (invalid AND count-only slots aim at the
            # sentinel row, which must stay empty — mask them to write False,
            # a no-op under max)
            vol_upd = (vol_valid & ~vol_count_only & landed)[:, None] & onehot[None, :]  # [W, N]
            newv_at_safe = _ax_any(new_v & safe_onehot[None, :], axis_name)  # [W]
            newv_chosen = (vol_valid & newv_at_safe & landed).astype(jnp.int32)  # [W]
            vol_any = state.vol_any.at[vol_ids].max(vol_upd)
            vol_ns = state.vol_ns.at[vol_ids].max(vol_upd & ~vol_ro_ok[:, None])
            nk = state.nk + (k_onehot @ newv_chosen)[:, None] * oh_i[None, :]
        else:
            vol_any, vol_ns, nk = state.vol_any, state.vol_ns, state.nk
        new_state = ScanState(
            requested=state.requested + oh_i[:, None] * g_req[None, :],
            nonzero_requested=state.nonzero_requested + oh_i[:, None] * g_nz[None, :],
            pod_count=state.pod_count + oh_i,
            ports_used=(state.ports_used | (onehot[:, None] & g_ports[None, :])
                        if use_ports else state.ports_used),
            spread_counts=state.spread_counts
            + dev.spread_inc[:, gid][:, None] * oh_i[None, :],
            round_robin=rr,
            dm=dm_new,
            downer=downer_new,
            total_match=total_match,
            vol_any=vol_any,
            vol_ns=vol_ns,
            nk=nk,
            still_ok=still_ok_new,
        )
        return new_state, chosen

    return step


def monotone_plane_device(dev: StaticArrays, state: ScanState,
                          use_terms: bool, use_ports: bool) -> jnp.ndarray:
    """Device twin of ``models.snapshot.monotone_plane``: the [G, N]
    monotone-component feasibility plane at the CURRENT carry state.
    ANDed into ``still_ok`` at chunk boundaries inside the device loop
    (the ROADMAP's periodic all-G refresh): the per-step update only
    tightens the current pod's signature row, so rows of signatures that
    stopped appearing would otherwise never learn that the carry grew
    past them.  Pure over-approximation tightening — every component
    here can only get WORSE as the carry grows, so a False is a
    permanent truth and compaction semantics are unchanged."""
    # kernel: implements GeneralPredicates
    # (same resource/pod-count/port masks as the step, vectorized [G, N])
    fit = jnp.all(
        (state.requested[None, :, :] + dev.g_request[:, None, :]
         <= dev.node_alloc[None, :, :]) | (dev.g_request[:, None, :] <= 0),
        axis=2)  # [G, N]
    pods_ok = state.pod_count + 1 <= dev.node_alloc_pods  # [N]
    mono = dev.static_ok & dev.node_exists[None, :] & fit & pods_ok[None, :]
    if use_ports:
        mono = mono & ~jnp.any(
            state.ports_used[None, :, :] & dev.g_ports[:, None, :], axis=2)
    if use_terms:
        raa_bad = (dev.own_raa.astype(jnp.int32)
                   @ (state.dm > 0).astype(jnp.int32)) > 0  # [G, N]
        sym = (dev.term_matches_sig & dev.is_raa[:, None]).astype(jnp.int32)
        sym_bad = (sym.T @ (state.downer > 0).astype(jnp.int32)) > 0  # [G, N]
        mono = mono & ~raa_bad & ~sym_bad
    return mono


@lru_cache(maxsize=64)
def _runner(num_zones: int, weights: tuple, use_terms: bool = True,
            use_vols: bool = True, use_ports: bool = True,
            use_frontier: bool = False):
    w = dict(zip(WEIGHT_KEYS, weights))

    @jax.jit
    def run(dev: StaticArrays, xs, state: ScanState):
        step = make_step(dev, num_zones, w, use_terms=use_terms,
                         use_vols=use_vols, use_ports=use_ports,
                         use_frontier=use_frontier)
        return jax.lax.scan(step, state, xs)

    return run


def _make_loop_run(num_zones: int, w: dict, use_terms: bool, use_vols: bool,
                   use_ports: bool, chunk_len: int,
                   axis_name: "str | None" = None):
    """The (unjitted) wave-loop body shared by the single-device and the
    shard_map runners.  ``axis_name`` threads through to ``make_step``:
    sharded, the in-loop still_ok/alive reduce and every score/tie reduce
    are per-shard collectives INSIDE the ``lax.while_loop`` — the shards
    advance in lockstep (cond consumes replicated scalars) with no host
    hop per chunk, and the per-shard ``alive`` slices concatenate back to
    the global mask at the loop exit."""

    def run(dev: StaticArrays, xs_full, state: ScanState, chosen_buf,
            start_chunk, n_chunks, compact_thresh):
        step = make_step(dev, num_zones, w, use_terms=use_terms,
                         use_vols=use_vols, use_ports=use_ports,
                         use_frontier=True, axis_name=axis_name)

        def alive_of(st):
            alive = jnp.any(st.still_ok, axis=0) & dev.node_exists
            return alive, _ax_sum(jnp.sum(alive.astype(jnp.int32)), axis_name)

        def cond(carry):
            _, _, c, want = carry
            return (c < n_chunks) & ~want

        def body(carry):
            st, buf, c, _ = carry
            start = c * jnp.int32(chunk_len)
            with jax.named_scope("ktpu.wave_chunk"):
                xs_c = tuple(
                    jax.lax.dynamic_slice_in_dim(a, start, chunk_len, axis=0)
                    for a in xs_full)
                st, chosen = jax.lax.scan(step, st, xs_c)
                buf = jax.lax.dynamic_update_slice(buf, chosen, (start,))
            with jax.named_scope("ktpu.still_ok_refresh"):
                st = st._replace(still_ok=st.still_ok & monotone_plane_device(
                    dev, st, use_terms, use_ports))
            _, n_alive = alive_of(st)
            return (st, buf, c + jnp.int32(1), n_alive <= compact_thresh)

        carry = (state, chosen_buf, start_chunk, jnp.bool_(False))
        state, chosen_buf, c, want = jax.lax.while_loop(cond, body, carry)
        alive, n_alive = alive_of(state)
        return state, chosen_buf, c, want, alive, n_alive

    return run


@lru_cache(maxsize=64)
def _loop_runner(num_zones: int, weights: tuple, use_terms: bool,
                 use_vols: bool, use_ports: bool, chunk_len: int):
    """The device-resident wave loop: a ``lax.while_loop`` that advances
    the frontier scan chunk by chunk entirely on device and exits only
    when the segment is done OR a compaction is worth taking — the host
    is re-entered O(compactions + 1) times per segment, independent of
    chunk count.

    Carry = (ScanState, chosen buffer [P_pad], chunk cursor, stop flag).
    ``state`` and ``chosen_buf`` are DONATED (the XLA executable reuses
    their buffers in place across iterations); callers must treat the
    passed-in arrays as consumed and must never fall back onto them —
    the backend's retry ladder re-derives everything from host arrays.
    The compaction decision is computed ON DEVICE: after each chunk the
    all-G ``still_ok`` refresh runs (see ``monotone_plane_device``) and
    the alive-union count is compared against ``compact_thresh`` (a
    host-precomputed int equivalent to the ``_pow2_width``/
    ``compact_frac`` rule; -1 = never fires).  ``n_chunks`` is a device
    operand, not a Python constant, so the pow-2 pod-axis bucket padding
    never adds loop trips."""
    w = dict(zip(WEIGHT_KEYS, weights))
    run = _make_loop_run(num_zones, w, use_terms, use_vols, use_ports,
                         chunk_len)
    return jax.jit(run, donate_argnums=(2, 3))


@lru_cache(maxsize=16)
def _sharded_loop_runner(num_zones: int, weights: tuple, use_terms: bool,
                         use_vols: bool, use_ports: bool, chunk_len: int,
                         mesh):
    """``_loop_runner``'s wave loop wrapped in ``shard_map`` over a 1-D
    node-axis mesh: every node-axis plane of StaticArrays/ScanState is
    partitioned (``parallel.mesh.loop_in_specs``), the pod-axis xs and
    the chosen buffer are replicated, and every whole-axis reduce inside
    the loop is a psum/pmax/pmin collective (see ``make_step``'s
    ``axis_name``) — the cross-host sync budget stays O(compactions + 1)
    per wave because the loop never leaves the device between chunks.

    Donation carries through shard_map unchanged (state and chosen
    buffer are reused in place across loop runs), which is what lets
    DC601's use-after-donate tracking extend through the sharded
    dispatch chain.  ``check_vma=False``: the replicated scalar outputs
    (cursor, stop flag, alive count) are provably identical on every
    shard — they are pure functions of psum/pmax results — but shard_map
    cannot prove it through ``lax.while_loop``."""
    from ..parallel.mesh import NODE_AXIS, loop_in_specs, loop_out_specs

    w = dict(zip(WEIGHT_KEYS, weights))
    run = _make_loop_run(num_zones, w, use_terms, use_vols, use_ports,
                         chunk_len, axis_name=NODE_AXIS)
    sharded = jax.shard_map(run, mesh=mesh, in_specs=loop_in_specs(),
                            out_specs=loop_out_specs(), check_vma=False)
    return jax.jit(sharded, donate_argnums=(2, 3))


def _sharded_loop_runner_for(static: BatchStatic, chunk_len: int, mesh):
    weights = tuple(int(static.weights.get(k, 0)) for k in WEIGHT_KEYS)
    return _sharded_loop_runner(  # device: static — mesh identity is a hashable per-device-set constant; one compile per (mesh, key)
        int(static.num_zones),
        weights,
        bool(static.terms),
        bool(static.use_vols),
        bool(getattr(static, "use_ports", True)),
        int(chunk_len),
        mesh,
    )


def _loop_runner_for(static: BatchStatic, chunk_len: int):
    weights = tuple(int(static.weights.get(k, 0)) for k in WEIGHT_KEYS)
    return _loop_runner(
        int(static.num_zones),
        weights,
        bool(static.terms),
        bool(static.use_vols),
        bool(getattr(static, "use_ports", True)),
        int(chunk_len),
    )


def _runner_for(static: BatchStatic, use_frontier: bool = False):
    weights = tuple(int(static.weights.get(k, 0)) for k in WEIGHT_KEYS)
    return _runner(
        int(static.num_zones),
        weights,
        use_terms=bool(static.terms),
        use_vols=bool(static.use_vols),
        use_ports=bool(getattr(static, "use_ports", True)),
        use_frontier=use_frontier,
    )


def dispatch_batch_arrays(static: BatchStatic, init: InitialState,
                          node_cache: "DeviceNodeCache | None" = None):
    """Async half: dispatch the scan and return the UNMATERIALIZED jax
    arrays (futures).  The caller may run host work while the device
    executes, then block via ``finalize_batch_arrays`` — the overlap seam
    the pipelined backend commits previous-segment bindings in."""
    dev = to_device(static, node_cache=node_cache)
    state = state_to_device(init, r_sel=getattr(static, "r_sel", None))
    xs = batch_xs(static)
    run = _runner_for(static)
    # XLA-profiler attribution: device time of this dispatch shows up
    # under this annotation (host-side trace spans stay as they are)
    with jax.profiler.TraceAnnotation("ktpu.wave_scan"):
        final_state, chosen = run(dev, xs, state)
    # enqueue the D2H transfer behind the scan (see dispatch_batch_pallas)
    chosen.copy_to_host_async()
    final_state.round_robin.copy_to_host_async()
    return chosen, final_state.round_robin


def finalize_batch_arrays(static: BatchStatic, chosen, rr) -> tuple[np.ndarray, int]:
    return np.asarray(chosen)[: len(static.group_of_pod)], int(rr)


def schedule_batch_arrays(static: BatchStatic, init: InitialState) -> tuple[np.ndarray, int]:
    """Run the kernel; returns (chosen node index per pod [-1 = unschedulable],
    final round-robin counter)."""
    chosen, rr = dispatch_batch_arrays(static, init)
    return finalize_batch_arrays(static, chosen, rr)


# -- frontier scan: chunked execution + mid-segment node-axis compaction ----

# StaticArrays fields carrying a node axis, with the axis position.
_STATIC_NODE_AXES = {
    "node_exists": 0, "node_alloc": 0, "node_alloc_pods": 0, "node_zone": 0,
    "static_ok": 1, "node_aff_raw": 1, "taint_intol_raw": 1,
    "static_score": 1, "interpod_raw": 1, "node_domain": 1, "dom_valid": 1,
}
# ScanState fields carrying a node axis (still_ok handled explicitly).
_STATE_NODE_AXES = {
    "requested": 0, "nonzero_requested": 0, "pod_count": 0, "ports_used": 0,
    "spread_counts": 1, "dm": 1, "downer": 1, "vol_any": 1, "vol_ns": 1,
    "nk": 1,
}


def _pow2_width(n: int, min_width: int) -> int:
    w = max(min_width, 1)
    while w < n:
        w *= 2
    return w


def gather_node_axis(dev: StaticArrays, state: ScanState, js: np.ndarray,
                     width: int) -> tuple[StaticArrays, ScanState]:
    """Device-side node-axis compaction: gather the kept columns ``js``
    (node-axis order preserved — the round-robin tie-break walks the axis
    in order, so relative order IS semantics) of every node-axis plane of
    the statics and the carry onto a ``width``-column buffer.  Positions
    past ``len(js)`` are padding: their ``node_exists`` / ``still_ok``
    are forced False, which makes every other plane's garbage there
    unreachable (feasible ≡ False).

    Parity: excluded columns are provably inert — every normalization,
    tie set, and n_feasible ranges over *feasible* columns only, and a
    column is dropped only when ``still_ok`` (the monotone
    over-approximation of every future pod's feasibility) has it False
    for ALL signatures.  The caller maps chosen indices back through its
    cumulative permutation."""
    # kernel: implements GeneralPredicates
    # (the compaction consumes the same monotone filter verdicts the step
    # computes; gathering them preserves each column's masks bit-for-bit)
    k = len(js)
    idx_host = np.zeros(width, dtype=np.int32)
    idx_host[:k] = js
    idx = jnp.asarray(idx_host)
    pad_mask = jnp.asarray(np.arange(width) < k)

    def take(arr, axis):
        return jnp.take(arr, idx, axis=axis)

    dev_new = dev._replace(**{
        f: take(getattr(dev, f), ax) for f, ax in _STATIC_NODE_AXES.items()
    })
    dev_new = dev_new._replace(node_exists=dev_new.node_exists & pad_mask)
    st_new = state._replace(**{
        f: take(getattr(state, f), ax) for f, ax in _STATE_NODE_AXES.items()
    })
    if state.still_ok is not None:
        st_new = st_new._replace(
            still_ok=take(state.still_ok, 1) & pad_mask[None, :])
    return dev_new, st_new


def _host_xs(static: BatchStatic):
    """The per-pod scan inputs as UNPADDED host numpy arrays — the
    frontier loop slices chunks out of these and pads each chunk to the
    chunk bucket (padding entries are pvalid=False, inert)."""
    p_real = len(static.group_of_pod)
    w = static.pod_vol_ids.shape[1]
    vco = np.zeros((p_real, w), dtype=bool)
    if static.pod_vol_count_only is not None:
        vco[:] = static.pod_vol_count_only
    return (
        np.asarray(static.group_of_pod, dtype=np.int32),
        np.ones(p_real, dtype=bool),
        np.asarray(static.pod_vol_ids, dtype=np.int32),
        np.asarray(static.pod_vol_valid, dtype=bool),
        np.asarray(static.pod_vol_ro_ok, dtype=bool),
        np.asarray(static.pod_vol_kind, dtype=np.int32),
        vco,
    )


def _chunk_xs(host_xs, start: int, chunk_len: int, v_sentinel: int):
    gids, pvalid, vids, vval, vro, vkind, vco = host_xs
    p_real = len(gids)
    end = min(start + chunk_len, p_real)
    n = end - start
    w = vids.shape[1]
    cg = np.zeros(chunk_len, dtype=np.int32)
    cg[:n] = gids[start:end]
    cp = np.zeros(chunk_len, dtype=bool)
    cp[:n] = True
    cv = np.full((chunk_len, w), v_sentinel, dtype=np.int32)
    cv[:n] = vids[start:end]
    cvv = np.zeros((chunk_len, w), dtype=bool)
    cvv[:n] = vval[start:end]
    cvr = np.zeros((chunk_len, w), dtype=bool)
    cvr[:n] = vro[start:end]
    cvk = np.zeros((chunk_len, w), dtype=np.int32)
    cvk[:n] = vkind[start:end]
    cvc = np.zeros((chunk_len, w), dtype=bool)
    cvc[:n] = vco[start:end]
    return tuple(jnp.asarray(a) for a in (cg, cp, cv, cvv, cvr, cvk, cvc))


class FrontierRun:
    """One segment's frontier execution.  Two drive modes share the same
    carry plane, compaction rule, and parity contract:

    - ``device_loop=True`` (the device-resident wave loop): ONE
      ``lax.while_loop`` dispatch advances every chunk on device with
      donated carries; the compaction decision is a device-computed
      flag checked inside the loop, so the host is re-entered only when
      a compaction is worth taking (it performs the dynamic-shape
      ``gather_node_axis`` and re-enters the loop at the new
      power-of-two width).  Host syncs per segment: one control read
      per loop run + the final result read = O(compactions + 1),
      independent of chunk count.
    - ``device_loop=False`` (the chunked host loop, also the fallback
      when the loop form fails): the host dispatches each chunk,
      reading the alive-union count back between chunks — O(chunks)
      syncs.

    ``__init__`` dispatches the first loop run / chunk and returns (the
    async seam the backend commits prior segments in — ``device_probe``
    polls it); ``finalize()`` drives the rest and returns chosen
    indices in the ORIGINAL node axis plus the final round-robin
    counter.  ``stats["host_syncs"]`` counts every blocking
    device→host round-trip this run performed — the seam the
    scheduler's per-wave ``host_syncs`` accounting deltas.

    Donation contract (loop mode): the ScanState and the chosen buffer
    are donated to each loop dispatch — after a dispatch the previous
    arrays are dead, and any failure path must rebuild from HOST data
    (the backend's full-width retry re-tensorizes from the original
    static/init, which donation never touches)."""

    def __init__(self, static: BatchStatic, init: InitialState,
                 node_cache: "DeviceNodeCache | None" = None,
                 chunk_len: int = 512, compact_frac: float = 0.5,
                 min_width: int = 128, on_compact=None,
                 device_loop: bool = False, on_loop=None, mesh=None):
        self.static = static
        self.chunk_len = chunk_len
        self.compact_frac = compact_frac
        self.min_width = min_width
        self.on_compact = on_compact
        self.on_loop = on_loop
        self.device_loop = bool(device_loop)
        self.mesh = mesh if device_loop else None
        self._p_real = len(static.group_of_pod)
        self._dev = to_device(static, node_cache=node_cache)
        self._state = state_to_device(
            init, r_sel=getattr(static, "r_sel", None), use_frontier=True)
        if self._state.still_ok is None:
            raise ValueError("frontier run requires init.still_ok (seed the "
                             "InitialState via models.snapshot.frontier_seed)")
        self._width = int(static.n_pad)
        # cumulative permutation: current column position -> original
        # full-axis index (chosen indices map back through the snapshot
        # of this array taken at each dispatch)
        self._map = np.arange(self._width, dtype=np.int64)
        self.stats = {"chunks": 0, "compactions": 0,
                      "alive_frac": [], "widths": [self._width],
                      "host_syncs": 0, "loop_runs": 0}
        if self.device_loop:
            if chunk_len <= 0 or chunk_len & (chunk_len - 1):
                raise ValueError(
                    "device_loop requires a power-of-two chunk_len (the "
                    "pod-axis bucket must be chunk-divisible)")
            # whole-segment xs uploaded ONCE: the pod axis is invariant
            # under node compaction, so every re-entry reuses this upload
            self._xs_full = batch_xs(static)
            p_pad = int(self._xs_full[0].shape[0])  # pow2, >= chunk bucket
            self._chunk_eff = min(chunk_len, p_pad)
            if self.mesh is not None:
                ns = int(self.mesh.size)
                if ns < 2 or ns & (ns - 1):
                    raise ValueError(
                        "mesh mode requires a power-of-two shard count >= 2")
                if self._width % ns:
                    raise ValueError(
                        f"segment width {self._width} not divisible by {ns} "
                        "shards (pad via snapshot.pad_segment_to_multiple)")
                from ..parallel import mesh as pmesh
                # compaction widths must stay shard-divisible: every
                # pow-2 width >= the pow-2 shard count divides evenly
                self.min_width = max(self.min_width, ns)
                self._dev = pmesh.place_static(self._dev, self.mesh)
                self._state = pmesh.place_state(self._state, self.mesh)
                self._loop = _sharded_loop_runner_for(
                    static, self._chunk_eff, self.mesh)
                self.stats["n_shards"] = ns
                self.stats["shard_alive_frac"] = []
            else:
                self._loop = _loop_runner_for(static, self._chunk_eff)
            self._n_chunks = -(-self._p_real // self._chunk_eff)
            self._buf = jnp.full((p_pad,), -1, dtype=jnp.int32)
            self._c = 0  # chunks completed (host mirror, updated at syncs)
            self._regions: list = []  # (start pod index, map snapshot)
            self._pending = None
            self._dispatch_loop()
        else:
            self._run = _runner_for(static, use_frontier=True)
            self._host_xs = _host_xs(static)
            self._chunks: list = []  # (chosen_dev, map_snapshot)
            self._next = 0
            self._dispatch_chunk()

    # -- device-resident loop drive ------------------------------------

    def _loop_thresh(self) -> int:
        """The device-side compaction trigger, as one int32: fire iff
        ``n_alive <= thresh``.  Exactly the host rule — ``_pow2_width``
        can shrink a pow-2 width iff n_alive <= width // 2 (and the
        floor allows it), and the frac gate is ``n_alive <=
        floor(compact_frac * width)`` for integer n_alive."""
        if self.min_width >= self._width:
            return -1  # width floor: no smaller pow-2 exists
        return min(self._width // 2, int(self.compact_frac * self._width))

    def _dispatch_loop(self) -> None:
        if self.on_loop is not None:
            # fault/trace seam BEFORE the dispatch: an injected loop
            # failure aborts the run and the segment falls back
            self.on_loop(self.stats["loop_runs"], self._width, self._c)
        tr = tracing.current()
        with (tr.span("frontier.loop", cat="frontier",
                      index=self.stats["loop_runs"], width=self._width,
                      start_chunk=self._c, n_chunks=self._n_chunks)
              if tr is not None else tracing.NULL_SPAN):
            with jax.profiler.TraceAnnotation("ktpu.frontier.loop"):
                out = self._loop(
                    self._dev, self._xs_full, self._state, self._buf,
                    jnp.int32(self._c), jnp.int32(self._n_chunks),
                    jnp.int32(self._loop_thresh()))
            # the donated state/buf are dead the moment the call returns;
            # rebind to the outputs before anything can raise
            self._state, self._buf = out[0], out[1]
            self._pending = out[2:]  # (c, want, alive, n_alive)
            self._regions.append((self._c * self._chunk_eff, self._map))
            self.stats["loop_runs"] += 1
            for a in self._pending[:2]:
                a.copy_to_host_async()

    def _sync_loop(self) -> tuple[bool, "jnp.ndarray", int]:
        """ONE blocking control read per loop run: the exit cursor, the
        compaction flag, and the alive count/mask arrive together (the
        loop already finished computing all of them — a single stall,
        then ready-buffer copies)."""
        c_dev, want_dev, alive, n_alive_dev = self._pending
        self._pending = None
        c_exit = int(c_dev)  # device: sync — blocks until the loop run completes; the one control stall per run
        self.stats["host_syncs"] += 1
        want = bool(want_dev)  # device: sync — compaction flag rides the same ready transfer as the cursor
        n_alive = int(n_alive_dev)  # device: sync — alive count, already host-side once the cursor read returned
        self.stats["chunks"] += c_exit - self._c
        self._c = c_exit
        frac = round(n_alive / max(self._width, 1), 4)
        self.stats["alive_frac"].append(frac)
        shard_fracs = None
        ns = self.stats.get("n_shards", 0)
        if ns and self._width % ns == 0:
            # per-shard alive split: the mask is shard-concatenated in
            # node order, so an even reshape recovers each shard's slice
            alive_h = np.asarray(alive)  # device: sync — rides the loop-exit transfer the cursor read above already stalled on
            n_loc = self._width // ns
            per = alive_h.reshape(ns, n_loc).sum(axis=1)
            shard_fracs = [round(int(c) / max(n_loc, 1), 4) for c in per]
            self.stats["shard_alive_frac"].append(shard_fracs)
        tr = tracing.current()
        if tr is not None:
            # one instant per loop EXIT (not per chunk): the pruning
            # trajectory at every host re-entry.  Per-shard fractions ride
            # the SAME instant as extra attrs — no second trace format.
            attrs = dict(frac=frac, width=self._width, chunk=self._c)
            if shard_fracs is not None:
                attrs["shards"] = shard_fracs
            tr.instant("frontier.alive", **attrs)
        return want, alive, n_alive

    def _finalize_loop(self) -> tuple[np.ndarray, int]:
        while True:
            want, alive, n_alive = self._sync_loop()
            if self._c >= self._n_chunks:
                break
            if want:
                width_new = _pow2_width(n_alive, self.min_width)  # device: static — pow2 buckets bound compiles to log2(N)
                if (width_new < self._width
                        and n_alive <= self.compact_frac * self._width):
                    if self.on_compact is not None:
                        self.on_compact(self._width, width_new, n_alive)
                    js = np.nonzero(np.asarray(alive))[0]
                    self._dev, self._state = gather_node_axis(
                        self._dev, self._state, js, width_new)
                    if self.mesh is not None:
                        # re-commit the compacted planes to the mesh: the
                        # gather ran under GSPMD and its output placement
                        # is whatever XLA chose, but the next loop run's
                        # in_specs demand clean node-axis partitions
                        from ..parallel import mesh as pmesh
                        self._dev = pmesh.place_static(self._dev, self.mesh)
                        self._state = pmesh.place_state(
                            self._state, self.mesh)
                    self._map = self._map[js]
                    self._width = width_new
                    self.stats["compactions"] += 1
                    self.stats["widths"].append(width_new)
            self._dispatch_loop()
        # final result read: the whole segment's chosen buffer at once
        buf_host = np.asarray(self._buf)  # device: sync — the whole segment's chosen buffer, once per wave
        rr = int(self._state.round_robin)  # device: sync — round-robin cursor rides the final result read
        self.stats["host_syncs"] += 1
        chosen_full = np.empty(self._p_real, dtype=np.int64)
        bounds = [start for start, _ in self._regions] + [self._p_real]
        for (start, map_snap), end in zip(self._regions, bounds[1:]):
            end = min(end, self._p_real)
            if end <= start:
                continue
            part = buf_host[start:end].astype(np.int64)
            safe = np.clip(part, 0, len(map_snap) - 1)
            chosen_full[start:end] = np.where(part >= 0, map_snap[safe], -1)
        return chosen_full, rr

    # -- chunked host-loop drive (and loop-failure fallback) -----------

    def _dispatch_chunk(self) -> None:
        tr = tracing.current()
        with (tr.span("frontier.chunk", cat="frontier",
                      index=self.stats["chunks"], width=self._width,
                      start=self._next)
              if tr is not None else tracing.NULL_SPAN):
            with jax.profiler.TraceAnnotation("ktpu.frontier.chunk"):
                xs = _chunk_xs(self._host_xs, self._next, self.chunk_len,
                               int(self.static.v_state) - 1)
                self._state, chosen = self._run(self._dev, xs, self._state)
                chosen.copy_to_host_async()
            self._chunks.append((chosen, self._map))
            self._next += self.chunk_len
            self.stats["chunks"] += 1

    @property
    def device_probe(self):
        cand = (self._pending[0] if self.device_loop and self._pending
                else self._chunks[0][0] if not self.device_loop
                else None)
        return cand if hasattr(cand, "is_ready") else None

    def _maybe_compact(self) -> None:
        alive = jnp.any(self._state.still_ok, axis=0) & self._dev.node_exists
        n_alive = int(jnp.sum(alive))  # device: sync — the one [N] reduce + sync per chunk
        self.stats["host_syncs"] += 1
        frac = round(n_alive / max(self._width, 1), 4)
        self.stats["alive_frac"].append(frac)
        tr = tracing.current()
        if tr is not None:
            # per-chunk alive fraction: the frontier's pruning trajectory
            # is readable straight off the wave trace
            tr.instant("frontier.alive", frac=frac, width=self._width,
                       chunk=self.stats["chunks"])
        width_new = _pow2_width(n_alive, self.min_width)  # device: static — pow2 buckets bound compiles to log2(N)
        if width_new >= self._width or n_alive > self.compact_frac * self._width:
            return
        if self.on_compact is not None:
            self.on_compact(self._width, width_new, n_alive)
        js = np.nonzero(np.asarray(alive))[0]  # device: sync — compaction gather indices (mask already reduced)
        self._dev, self._state = gather_node_axis(
            self._dev, self._state, js, width_new)
        self._map = self._map[js]
        self._width = width_new
        self.stats["compactions"] += 1
        self.stats["widths"].append(width_new)

    def finalize(self) -> tuple[np.ndarray, int]:
        if self.device_loop:
            return self._finalize_loop()
        while self._next < self._p_real:
            self._maybe_compact()
            self._dispatch_chunk()
        chosen_full = np.empty(self._p_real, dtype=np.int64)
        pos = 0
        for chosen_dev, map_snap in self._chunks:
            part = np.asarray(chosen_dev)  # device: sync — per-chunk result read; D2H copy was pre-staged async
            self.stats["host_syncs"] += 1
            n = min(len(part), self._p_real - pos)
            part = part[:n].astype(np.int64)
            safe = np.clip(part, 0, len(map_snap) - 1)
            chosen_full[pos:pos + n] = np.where(
                part >= 0, map_snap[safe], -1)
            pos += n
        return chosen_full, int(self._state.round_robin)  # device: sync — round-robin cursor, once per segment
