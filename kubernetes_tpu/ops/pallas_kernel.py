"""Fused Pallas TPU kernel for the batched scheduling scan.

Why this exists: the XLA `lax.scan` step (``batch_kernel.py``) is
semantically right but latency-bound — each pod's step is a chain of
dependent reduce→broadcast stages (feasibility → scores → normalize →
argmax-with-tie-break → commit), and under XLA every stage round-trips
HBM, costing ~25μs per serialized stage and ~160μs per pod.  This kernel
runs the WHOLE scan as one Pallas program: all dynamic state lives in
VMEM scratch for the duration of the batch, each pod's step is a handful
of VPU passes over [.., N] rows, and the only HBM traffic is the initial
state load and the chosen-index writeback.

Parity contract: every arithmetic op mirrors ``batch_kernel.make_step``
in int32 (fixed-point ``scheduler/units.py``) — same masks, same
normalizations, same round-robin tie-break — so bindings are
bit-identical to the sequential oracle.  Signature-table "gathers" use
f32 one-hot matmuls on the MXU at ``precision=HIGHEST``: the gathered
values are ints below 2^24 (exact in f32) and are cast straight back to
int32, so no float rounding can reach a score.  Mosaic's DEFAULT f32 dot
is a single bf16 pass — measured on a v5e (libtpu 0.0.34) it returns
256 for 257 and 1,104 for 1,100, which the interpreter never shows.

Layouts (host-prepped in ``_pack``): the node axis is the lane axis
everywhere; per-signature tables are stored [*, G] so a one-hot e_gid
[G, 1] matmul yields sublane-major columns; volume occupancy packs
(any, non-sharable) into two bits of an int8 [V, N] map whose rows are
dynamically sliced per volume slot.

Reference capability: the scheduling algorithm of
``plugin/pkg/scheduler/core/generic_scheduler.go:88`` (filter → score →
selectHost) batched over the pod queue.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ..models.snapshot import BatchStatic, InitialState
from ..scheduler.predicates import VOLUME_COUNT_LIMITS
from ..scheduler.units import FIXED_POINT_ONE, MAX_PRIORITY
from ..utils import tracing
from .batch_kernel import WEIGHT_KEYS

INT32_MIN = -(2**31)

_VOL_LIMITS = list(VOLUME_COUNT_LIMITS.values())  # static: baked into the kernel

# The kernel's VMEM limit, handed to the compiler (``vmem_limit_bytes``)
# and used by the admission guard below — one number, not two.  A v5e
# TensorCore has 128 MiB of VMEM (jax's ``pallas.tpu.get_tpu_info`` table);
# the rest is left to XLA for the operand copies it stages outside the
# kernel's own allocation.  Without an explicit limit the compiler's
# default (16 MiB scoped) refuses shapes from G ≈ 384 at N = 5120 while
# admitting the north shape only because XLA happens to stage its 32 MiB
# ``pod_vol`` operand outside the scope.
VMEM_LIMIT_BYTES = 100 * 2**20
# Mosaic's own temporaries and spills on top of the declared buffers:
# ~0.9 MiB measured at the north shape (compiled scoped size minus the
# declared scratch), 4 MiB allowed.
_VMEM_HEADROOM_BYTES = 4 * 2**20


def _f32(x):
    return np.ascontiguousarray(x, dtype=np.float32)


def _i32(x):
    return np.ascontiguousarray(x, dtype=np.int32)


def _tiled_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """Bytes a [rows, cols] array occupies in VMEM: the minor dimension
    pads to 128 lanes, the major one to the dtype's native sublane count
    (8 for 32-bit, 32 for int8)."""
    sub = 32 // itemsize
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def pallas_vmem_bytes(static: BatchStatic) -> int:
    """VMEM footprint of the kernel for this segment's shapes, in padded
    tiles as the compiled program holds them.  Static maps, tables and
    the per-pod volume slots are whole-array VMEM operands; the dynamic
    state lives ONCE in scratch (its initial values arrive via HBM + DMA,
    so they are not double-counted).  ``pod_vol`` dominates at a full
    segment: its [P, W] int32 rows pad W to 128 lanes — 32 MiB at
    P = 65,536 whatever W is."""
    n = static.n_pad
    g = static.static_ok.shape[0]
    t = static.term_matches_sig.shape[0]
    pv = static.g_ports.shape[1]
    v = static.v_state
    r = static.node_alloc.shape[1]
    k = len(_VOL_LIMITS)
    w = static.pod_vol_ids.shape[1]
    p_pad = _pod_pad(len(static.group_of_pod))
    tb = _tiled_bytes
    operands = (
        tb(r, n) + 3 * tb(1, n)  # alloc_t, alloc_pods, exists, zone
        + 5 * tb(g, n) + 2 * tb(t, n)  # [G, N] maps, node_domain, dom_valid
        # signature tables (f32) and the [T, 1] term columns
        + tb(r, g) + tb(2, g) + tb(pv, g) + tb(1, g) + tb(g, g)
        + 5 * tb(t, g) + 3 * tb(t, 1)
        + tb(p_pad, w)  # pod_vol
    )
    scratch = (
        tb(r, n) + tb(2, n) + tb(1, n) + tb(pv, n) + tb(g, n)
        + 2 * tb(t, n) + tb(t, 1) + tb(v, n, itemsize=1) + tb(k, n)
    )
    chosen_out = tb(p_pad // 128, 128)
    return operands + scratch + chosen_out


def supports_pallas(static: BatchStatic) -> bool:
    return (
        static.num_zones <= 8
        and pallas_vmem_bytes(static) + _VMEM_HEADROOM_BYTES <= VMEM_LIMIT_BYTES
    )


def _pod_pad(p_real: int) -> int:
    """Power-of-two pod-count buckets (same policy as batch_xs): tails of
    different runs land in the same bucket, so the warm-up compile covers
    them.  Shared by ``_pack`` and ``shape_key`` — the fallback blacklist
    must bucket exactly as the compile cache does."""
    p_pad = 128
    while p_pad < p_real:
        p_pad *= 2
    return p_pad


def _pack(static: BatchStatic, init: InitialState):
    """numpy host prep: transposes, one-hot-matmul layouts, bit packing."""
    n = static.n_pad
    g = static.static_ok.shape[0]
    t = static.term_matches_sig.shape[0]
    p_real = len(static.group_of_pod)
    p_pad = _pod_pad(p_real)
    w = static.pod_vol_ids.shape[1]

    gids = np.zeros(p_pad, dtype=np.int32)
    gids[:p_real] = static.group_of_pod
    # packed per-pod volume slots: vid*64 | kind*8 | ro*4 | count_only*2 | valid
    pod_vol = np.full((p_pad, w), (static.v_state - 1) * 64, dtype=np.int32)
    pod_vol[:p_real] = (
        static.pod_vol_ids * 64
        + static.pod_vol_kind * 8
        + static.pod_vol_ro_ok.astype(np.int32) * 4
        + static.pod_vol_count_only.astype(np.int32) * 2
        + static.pod_vol_valid.astype(np.int32)
    )

    vol_flags = (init.vol_any.astype(np.int8) | (init.vol_ns.astype(np.int8) << 1))

    ins = (
        # -- static node-axis maps (int32) --
        _i32(static.node_alloc.T),  # alloc_t [R, N]
        _i32(static.node_alloc_pods)[None, :],  # [1, N]
        _i32(static.node_exists)[None, :],  # [1, N]
        _i32(static.node_zone)[None, :],  # [1, N]
        _i32(static.static_ok),  # [G, N]
        _i32(static.node_aff_raw),
        _i32(static.taint_intol_raw),
        _i32(static.static_score),
        _i32(static.interpod_raw),
        _i32(static.node_domain),  # [T, N]
        _i32(static.dom_valid),  # [T, N]
        # -- signature tables, [*, G] f32 for one-hot matmul gathers --
        _f32(static.g_request.T),  # [R, G]
        _f32(static.g_nonzero.T),  # [2, G]
        _f32(static.g_ports.T),  # [Pv, G]
        _f32(static.g_has_spread)[None, :],  # [1, G]
        _f32(static.spread_inc),  # [G, G] (col gid = increments)
        _f32(static.term_matches_sig),  # [T, G]
        _f32(static.own_w.T),  # [T, G]
        _f32(static.own_ra.T),  # [T, G]
        _f32(static.own_raa.T),  # [T, G]
        _f32(static.own_all.T),  # [T, G]
        _i32(static.sym_w)[:, None],  # [T, 1]
        _i32(static.is_raa)[:, None],  # [T, 1]
        _i32(static.self_match)[:, None],  # [T, 1]
        # -- xs --
        _i32(pod_vol),  # [P, W]
        # -- initial state --
        _i32(init.requested.T),  # [R, N]
        _i32(init.nonzero_requested.T),  # [2, N]
        _i32(init.pod_count)[None, :],  # [1, N]
        _i32(init.ports_used.T),  # [Pv, N]
        _i32(init.spread_counts),  # [G, N]
        _i32(init.dm),  # [T, N]
        _i32(init.downer),  # [T, N]
        _i32(init.total_match)[:, None],  # [T, 1]
        vol_flags,  # [V, N] int8
        _i32(init.nk),  # [K, N]
    )
    scalars = (
        np.array([p_real], dtype=np.int32),
        np.array([init.round_robin], dtype=np.int32),
        gids,
    )
    return scalars, tuple(ins)


@lru_cache(maxsize=64)
def _pallas_runner(
    n: int,
    g: int,
    t: int,
    pv: int,
    v: int,
    r: int,
    w: int,
    p_pad: int,
    num_zones: int,
    weights: tuple,
    use_terms: bool,
    use_vols: bool,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    wd = dict(zip(WEIGHT_KEYS, weights))
    k = len(_VOL_LIMITS)
    pc = p_pad // 128

    def kernel(
        p_real_ref,
        rr0_ref,
        gids_ref,
        # static
        alloc_t,
        alloc_pods,
        exists,
        zone,
        static_ok,
        aff_raw,
        taint_raw,
        score_raw,
        interpod_raw,
        node_domain,
        dom_valid,
        g_request_f,
        g_nonzero_f,
        g_ports_f,
        g_has_spread_f,
        spread_inc_f,
        tm_f,
        own_w_f,
        own_ra_f,
        own_raa_f,
        own_all_f,
        sym_w_c,
        is_raa_c,
        self_match_c,
        pod_vol,
        # initial state
        req0,
        nz0,
        cnt0,
        ports0,
        spread0,
        dm0,
        downer0,
        total0,
        volf0,
        nk0,
        # outputs
        chosen_out,
        rr_out,
        # scratch (state)
        req_s,
        nz_s,
        cnt_s,
        ports_s,
        spread_s,
        dm_s,
        downer_s,
        total_s,
        volf_s,
        nk_s,
        state_sem,
    ):
        # ---- DMA initial state (HBM inputs) into VMEM scratch ----
        # State inputs stay in HBM so VMEM holds exactly ONE copy of the
        # mutable state; without this the v_state*N volume map alone would
        # blow the budget at 5k-node scale.
        copies = [(req0, req_s), (nz0, nz_s), (cnt0, cnt_s), (ports0, ports_s),
                  (spread0, spread_s)]
        if use_terms:
            copies += [(dm0, dm_s), (downer0, downer_s), (total0, total_s)]
        if use_vols:
            copies += [(volf0, volf_s), (nk0, nk_s)]
        for src, dst in copies:
            dma = pltpu.make_async_copy(src, dst, state_sem)
            dma.start()
            dma.wait()
        chosen_out[:] = jnp.full((pc, 128), -1, dtype=jnp.int32)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
        lane128 = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        giota = jax.lax.broadcasted_iota(jnp.int32, (g, 1), 0)
        exists_b = exists[:] > 0

        def cumsum_lanes(x):
            """Inclusive prefix sum along lanes (Mosaic has no cumsum):
            log2(N) rounds of roll-and-add, masking the wrapped lanes."""
            off = 1
            while off < n:
                shifted = pltpu.roll(x, off, axis=1)
                x = x + jnp.where(lane >= off, shifted, 0)
                off *= 2
            return x

        def body(i, rr):
            gid = gids_ref[i]
            e_gid = (giota == gid).astype(jnp.float32)  # [G, 1]

            def gather_col(tab_f):  # [X, G] f32 @ [G, 1] -> [X, 1] int32
                col = jax.lax.dot_general(
                    tab_f[:], e_gid,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                )
                return col.astype(jnp.int32)

            g_req_c = gather_col(g_request_f)  # [R, 1]
            g_nz_c = gather_col(g_nonzero_f)  # [2, 1]
            g_ports_c = gather_col(g_ports_f)  # [Pv, 1]

            # ---- feasibility ----
            # NOTE: sublane reductions run in int32 — Mosaic cannot lower
            # bool (i8->i1) reductions
            fit_rn = jnp.where(
                g_req_c > 0,
                (req_s[:] + g_req_c <= alloc_t[:]).astype(jnp.int32),
                1,
            )  # [R, N]
            fit = jnp.min(fit_rn, axis=0, keepdims=True) > 0  # [1, N]
            pods_ok = cnt_s[:] + 1 <= alloc_pods[:]
            ports_bad = (
                jnp.max(
                    ((g_ports_c > 0) & (ports_s[:] > 0)).astype(jnp.int32),
                    axis=0, keepdims=True,
                )
                > 0
            )
            ok_row = static_ok[pl.ds(gid, 1), :] > 0
            feasible = ok_row & fit & pods_ok & ~ports_bad & exists_b

            if use_terms:
                m_g_c = gather_col(tm_f)  # [T, 1]
                own_ra_c = gather_col(own_ra_f)
                own_raa_c = gather_col(own_raa_f)
                own_all_c = gather_col(own_all_f)
                own_w_c = gather_col(own_w_f)
                dm = dm_s[:]  # [T, N]
                downer = downer_s[:]
                sym_anti_bad = (
                    jnp.max(
                        (((m_g_c > 0) & (is_raa_c[:] > 0)) & (downer > 0)).astype(jnp.int32),
                        axis=0, keepdims=True,
                    )
                    > 0
                )
                first_ok = (total_s[:] == 0) & (self_match_c[:] > 0)  # [T, 1]
                ra_ok = (dm > 0) | first_ok
                own_ra_bad = (
                    jnp.max(((own_ra_c > 0) & ~ra_ok).astype(jnp.int32), axis=0, keepdims=True)
                    > 0
                )
                own_raa_bad = (
                    jnp.max(((own_raa_c > 0) & (dm > 0)).astype(jnp.int32), axis=0, keepdims=True)
                    > 0
                )
                feasible = feasible & ~sym_anti_bad & ~own_ra_bad & ~own_raa_bad

            if use_vols:
                sub8 = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)

                def vol_row(vid):
                    # int8 dynamic sublane slices must be 8-aligned: fetch
                    # the aligned 8-row block and mask-select the row
                    base = pl.multiple_of((vid // 8) * 8, 8)
                    blk = volf_s[pl.ds(base, 8), :].astype(jnp.int32)  # [8, N]
                    sel = sub8 == vid % 8
                    return jnp.max(jnp.where(sel, blk, 0), axis=0, keepdims=True)

                disk_bad = jnp.zeros((1, n), dtype=jnp.bool_)
                slot_rows = []  # (vid, valid, ro, kind, any_row, new_row)
                count_new = [jnp.zeros((1, n), dtype=jnp.int32) for _ in range(k)]
                has_kind = [jnp.int32(0) for _ in range(k)]
                for s in range(w):
                    packed = pod_vol[i, s]
                    vid = packed // 64
                    kind = (packed // 8) % 8
                    ro = (packed // 4) % 2
                    co = (packed // 2) % 2  # count-only: sentinel row, no write
                    valid = packed % 2
                    row = vol_row(vid)  # [1, N]
                    any_row = row % 2
                    ns_row = row // 2
                    blocked = jnp.where(ro > 0, ns_row, any_row)
                    disk_bad = disk_bad | ((valid > 0) & (blocked > 0))
                    new_row = jnp.where(valid > 0, 1 - any_row, 0)  # [1, N]
                    slot_rows.append((vid, valid, ro, co, kind, any_row, new_row))
                    for kk in range(k):
                        kin = (kind == kk) & (valid > 0)
                        count_new[kk] = count_new[kk] + jnp.where(kin, new_row, 0)
                        has_kind[kk] = has_kind[kk] | kin.astype(jnp.int32)
                vol_bad = disk_bad
                for kk in range(k):
                    over = (has_kind[kk] > 0) & (
                        nk_s[pl.ds(kk, 1), :] + count_new[kk] > _VOL_LIMITS[kk]
                    )
                    vol_bad = vol_bad | over
                feasible = feasible & ~vol_bad

            n_feasible = jnp.sum(feasible.astype(jnp.int32))

            # ---- scores (int32 fixed point; mirrors batch_kernel) ----
            cpu_req = nz_s[pl.ds(0, 1), :] + g_nz_c[0, 0]
            mem_req = nz_s[pl.ds(1, 1), :] + g_nz_c[1, 0]
            cpu_cap = alloc_t[pl.ds(0, 1), :]
            mem_cap = alloc_t[pl.ds(1, 1), :]
            total = score_raw[pl.ds(gid, 1), :]

            def usage(requested, capacity, most: bool):
                safe_cap = jnp.maximum(capacity, 1)
                if most:
                    raw = (requested * MAX_PRIORITY) // safe_cap
                else:
                    raw = ((capacity - requested) * MAX_PRIORITY) // safe_cap
                return jnp.where((capacity == 0) | (requested > capacity), 0, raw)

            if wd["least"]:
                s_ = (usage(cpu_req, cpu_cap, False) + usage(mem_req, mem_cap, False)) // 2
                total = total + wd["least"] * s_
            if wd["most"]:
                s_ = (usage(cpu_req, cpu_cap, True) + usage(mem_req, mem_cap, True)) // 2
                total = total + wd["most"] * s_
            if wd["balanced"]:
                f_cpu = (cpu_req * FIXED_POINT_ONE) // jnp.maximum(cpu_cap, 1)
                f_mem = (mem_req * FIXED_POINT_ONE) // jnp.maximum(mem_cap, 1)
                diff = jnp.abs(f_cpu - f_mem)
                sc = (MAX_PRIORITY * FIXED_POINT_ONE - diff * MAX_PRIORITY) // FIXED_POINT_ONE
                bad = (
                    (cpu_cap == 0) | (mem_cap == 0)
                    | (cpu_req >= cpu_cap) | (mem_req >= mem_cap)
                )
                total = total + wd["balanced"] * jnp.where(bad, 0, sc)
            if wd["spread"]:
                cnt = spread_s[pl.ds(gid, 1), :]  # [1, N]
                max_n = jnp.max(jnp.where(feasible, cnt, 0))
                node_fp = jnp.where(
                    max_n > 0,
                    ((max_n - cnt) * (MAX_PRIORITY * FIXED_POINT_ONE))
                    // jnp.maximum(max_n, 1),
                    MAX_PRIORITY * FIXED_POINT_ONE,
                )
                has_zone = zone[:] >= 0
                zcnt = jnp.zeros((1, n), dtype=jnp.int32)
                max_z = jnp.int32(0)
                for z in range(num_zones):
                    zs = jnp.sum(
                        jnp.where(feasible & (zone[:] == z), cnt, 0)
                    )
                    max_z = jnp.maximum(max_z, zs)
                    zcnt = jnp.where(zone[:] == z, zs, zcnt)
                zone_fp = jnp.where(
                    max_z > 0,
                    ((max_z - zcnt) * (MAX_PRIORITY * FIXED_POINT_ONE))
                    // jnp.maximum(max_z, 1),
                    MAX_PRIORITY * FIXED_POINT_ONE,
                )
                g_sp = gather_col(g_has_spread_f)  # [1, 1]
                have_zones = (g_sp[0, 0] > 0) & (
                    jnp.max((feasible & has_zone).astype(jnp.int32)) > 0
                )
                total_fp = jnp.where(
                    have_zones & has_zone, (node_fp + 2 * zone_fp) // 3, node_fp
                )
                total = total + wd["spread"] * (total_fp // FIXED_POINT_ONE)
            if wd["node_affinity"]:
                raw = aff_raw[pl.ds(gid, 1), :]
                max_c = jnp.max(jnp.where(feasible, raw, 0))
                total = total + wd["node_affinity"] * jnp.where(
                    max_c > 0, (MAX_PRIORITY * raw) // jnp.maximum(max_c, 1), 0
                )
            if wd["taint"]:
                raw = taint_raw[pl.ds(gid, 1), :]
                max_c = jnp.max(jnp.where(feasible, raw, 0))
                total = total + wd["taint"] * jnp.where(
                    max_c > 0,
                    (MAX_PRIORITY * (max_c - raw)) // jnp.maximum(max_c, 1),
                    MAX_PRIORITY,
                )
            if wd["interpod"]:
                raw = interpod_raw[pl.ds(gid, 1), :]
                if use_terms:
                    raw = raw + jnp.sum(own_w_c * dm, axis=0, keepdims=True)
                    raw = raw + jnp.sum(
                        (m_g_c * sym_w_c[:]) * downer, axis=0, keepdims=True
                    )
                max_c = jnp.maximum(0, jnp.max(jnp.where(feasible, raw, INT32_MIN)))
                min_c = jnp.minimum(0, jnp.min(jnp.where(feasible, raw, 2**31 - 1)))
                rng_ = max_c - min_c
                s_ = jnp.where(
                    rng_ > 0, (MAX_PRIORITY * (raw - min_c)) // jnp.maximum(rng_, 1), 0
                )
                total = total + wd["interpod"] * s_

            # ---- selection (selectHost + lastNodeIndex round-robin) ----
            masked = jnp.where(feasible, total, INT32_MIN)
            max_score = jnp.max(masked)
            ties = feasible & (total == max_score)
            t_count = jnp.sum(ties.astype(jnp.int32))
            idx = rr % jnp.maximum(t_count, 1)
            cum = cumsum_lanes(ties.astype(jnp.int32))
            pick_among = jnp.min(jnp.where(ties & (cum == idx + 1), lane, n))
            only = jnp.min(jnp.where(feasible, lane, n))
            chosen = jnp.where(
                n_feasible == 0,
                jnp.int32(-1),
                jnp.where(n_feasible == 1, only, pick_among).astype(jnp.int32),
            )
            rr_new = rr + (n_feasible >= 2).astype(jnp.int32)

            # ---- commit ----
            landed = chosen >= 0
            safe = jnp.maximum(chosen, 0)
            oh = ((lane == safe) & landed).astype(jnp.int32)  # [1, N]
            req_s[:] = req_s[:] + g_req_c * oh
            nz_s[:] = nz_s[:] + g_nz_c * oh
            cnt_s[:] = cnt_s[:] + oh
            ports_s[:] = ports_s[:] | ((g_ports_c > 0) & (oh > 0)).astype(jnp.int32)
            spread_col = gather_col(spread_inc_f)  # [G, 1]
            spread_s[:] = spread_s[:] + spread_col * oh

            if use_terms:
                d_at_safe = jnp.sum(node_domain[:] * oh, axis=1, keepdims=True)  # [T,1]
                valid_at_safe = jnp.sum(dom_valid[:] * oh, axis=1, keepdims=True)
                same_dom = (
                    (node_domain[:] == d_at_safe)
                    & (dom_valid[:] > 0)
                    & (valid_at_safe > 0)
                )
                m_i = ((m_g_c > 0) & landed).astype(jnp.int32)  # [T, 1]
                own_i = ((own_all_c > 0) & landed).astype(jnp.int32)
                dm_s[:] = dm_s[:] + same_dom * m_i
                downer_s[:] = downer_s[:] + same_dom * own_i
                total_s[:] = total_s[:] + m_i

            if use_vols:
                for (vid, valid, ro, co, kind, any_row, new_row) in slot_rows:
                    # count-only slots aim at the sentinel row, which must
                    # stay empty: they never write occupancy
                    upd = ((valid > 0) & (co == 0) & landed & (oh > 0)).astype(jnp.int32)  # [1,N]
                    bits = upd * (1 + 2 * (1 - ro))
                    base = pl.multiple_of((vid // 8) * 8, 8)
                    blk = volf_s[pl.ds(base, 8), :].astype(jnp.int32)  # [8, N]
                    sel = sub8 == vid % 8
                    volf_s[pl.ds(base, 8), :] = jnp.where(
                        sel, blk | bits, blk
                    ).astype(jnp.int8)
                    new_at = jnp.sum(new_row * oh)  # scalar 0/1
                    for kk in range(k):
                        inc = (
                            ((kind == kk) & (valid > 0)).astype(jnp.int32)
                            * new_at
                        )
                        nk_s[pl.ds(kk, 1), :] = nk_s[pl.ds(kk, 1), :] + inc * oh

            # ---- writeback chosen ----
            row_i = i // 128
            col_i = i % 128
            crow = chosen_out[pl.ds(row_i, 1), :]
            chosen_out[pl.ds(row_i, 1), :] = jnp.where(lane128 == col_i, chosen, crow)
            return rr_new

        rr_final = jax.lax.fori_loop(0, p_real_ref[0], body, rr0_ref[0])
        rr_out[0, 0] = rr_final

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        # 25 static/table/xs inputs in VMEM; the 10 initial-state inputs in
        # HBM (DMA'd into scratch — one VMEM copy of the mutable state)
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 25
        + [pl.BlockSpec(memory_space=pl.ANY)] * 10,
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((r, n), jnp.int32),
            pltpu.VMEM((2, n), jnp.int32),
            pltpu.VMEM((1, n), jnp.int32),
            pltpu.VMEM((pv, n), jnp.int32),
            pltpu.VMEM((g, n), jnp.int32),
            pltpu.VMEM((t, n), jnp.int32),
            pltpu.VMEM((t, n), jnp.int32),
            pltpu.VMEM((t, 1), jnp.int32),
            pltpu.VMEM((v, n), jnp.int8),
            pltpu.VMEM((k, n), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
    )

    fn = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((pc, 128), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )
    return jax.jit(fn)


def schedule_batch_pallas(static: BatchStatic, init: InitialState):
    """Drop-in replacement for ``schedule_batch_arrays`` on TPU."""
    chosen2d, rr = dispatch_batch_pallas(static, init)
    return finalize_batch_pallas(static, chosen2d, rr)


def shape_key(static: BatchStatic) -> tuple:
    """The compiled-program identity for ``static`` — the argument tuple
    ``_pallas_runner`` caches compiles on (dims + weights + structure
    flags), so a fallback-blacklist entry maps 1:1 to one compilation
    unit (backend.py's per-shape fallback: one bad shape must not take
    every other shape off the Pallas path)."""
    return (
        static.n_pad,
        static.static_ok.shape[0],
        static.term_matches_sig.shape[0],
        static.g_ports.shape[1],
        static.v_state,
        static.node_alloc.shape[1],
        static.pod_vol_ids.shape[1],
        _pod_pad(len(static.group_of_pod)),
        int(static.num_zones),
        tuple(int(static.weights.get(kk, 0)) for kk in WEIGHT_KEYS),
        bool(static.terms),
        bool(static.use_vols),
    )


def dispatch_batch_pallas(static: BatchStatic, init: InitialState):
    """Async half of ``schedule_batch_pallas``: dispatch and return the
    unmaterialized device arrays (see dispatch_batch_arrays)."""
    tr = tracing.current()
    with (tr.span("dispatch.pack", cat="phase")
          if tr is not None else tracing.NULL_SPAN):
        scalars, ins = _pack(static, init)
    with (tr.span("dispatch.launch", cat="phase")
          if tr is not None else tracing.NULL_SPAN) as sp:
        # device: static — grid/shape keys are BatchStatic fields, frozen per segment build
        run = _pallas_runner(*shape_key(static))
        if tr is not None:
            sp.set(upload_bytes=sum(a.nbytes for a in (*scalars, *ins)))
            compiled = getattr(run, "_cache_size", None)
            if compiled is not None:
                # a runner that has compiled nothing yet: this launch
                # traces, lowers and compiles (or loads from the
                # persistent cache)
                sp.set(new_shape=compiled() == 0)
        # XLA-profiler attribution, the twin of ``ktpu.wave_scan``: the
        # upload and the enqueue of this dispatch on the profiler's clock
        with jax.profiler.TraceAnnotation("ktpu.pallas_scan"):
            out = run(*scalars, *ins)
    # enqueue the D2H transfer behind the kernel NOW: by finalize time the
    # chosen indices are already host-side (the copy rides the device's
    # shadow with the commit work instead of serializing after it)
    for a in out:
        a.copy_to_host_async()
    return out


def finalize_batch_pallas(static: BatchStatic, chosen2d, rr):
    chosen = np.asarray(chosen2d).reshape(-1)[: len(static.group_of_pod)]
    return chosen, int(np.asarray(rr)[0, 0])
