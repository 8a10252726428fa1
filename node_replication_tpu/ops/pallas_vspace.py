"""Pallas TPU replay kernels for the vspace models (flat + 4-level radix).

This generalizes the hashmap replay template (`ops/pallas_replay.py`) to
the model class the r3 verdict called out: ops that touch a SPAN of state
per entry (page-table map/unmap over up to `max_span` contiguous pages,
plus the radix model's 512-page table teardown) — the NrOS workload the
reference replays through its hot loop (`nr/src/log.rs:473-524`,
`benches/vspace.rs:176-481`).

Layout (vs the hashmap kernel's `[K, R]` transpose):

- page-table state lives per replica as `[ROWS, 128]` int32 — pages on
  (sublane, lane) in row-major 128-page rows. A map/unmap span of
  `n <= max_span` contiguous pages covers a STATIC number of rows, read
  with one dynamic-sublane slice and updated as a lane-masked blend:
  `page_id = row_base*128 + iota`, `mask = (page >= v) & (page < v+n)`,
  value affine in the page id. No per-page loop — the span IS the vector.
  The radix teardown clears a 512-page region = 4 aligned rows riding
  the same unified read-blend-write (row base and masks select per op
  kind), so the whole entry is STRAIGHT-LINE code: no branches.
- the grid processes replicas in GROUPS of `G` (largest VMEM-fitting
  divisor of R): the per-entry scalar work (SMEM window reads, level
  walks, index math) — which dominates a sequential replay loop — is
  paid once per group instead of once per replica, while the state
  blend is a `[G, H, 128]` vector op that does the honest per-replica
  work on the vector units.
- PML4/PDPT/PD present tables are SMALL (`ceil(P/512)` entries and up).
  PD lives in SMEM, read/written as scalars by dynamic index (a span
  crosses at most 2 entries). PDPT/PML4 (at most a few entries under
  the VMEM page gate) are carried IN REGISTERS through the replay loop
  and written back once.

Lock-step invariant: the fused step replays the identical window into
every replica, so replica states are identical by induction from
identical init. The kernel therefore keeps ONE canonical copy of the
level tables and of the response vector (they are provably equal across
replicas), while the page-table state — where the replay work lives —
stays per replica. `make_pallas_vspace_step` documents and preserves
this invariant; it is the same lock-step precondition `core/step`'s
combined engine already requires.

The kernel applies entries strictly in order, so — unlike the combined
`window_apply` reduction — it needs no algebraic window form and is the
rescue path for order-dependent replay at hardware speed. Responses are
bit-identical to the sequential fold (tests/test_pallas_vspace.py pins
this in interpret mode; `NR_TPU_SMOKE=1` runs the hardware check).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from node_replication_tpu.core.log import LogSpec, log_append
from node_replication_tpu.ops.pallas_ring import FusedEngineHost
from node_replication_tpu.utils.compat import x64_disabled

_FRAME_MASK = (1 << 30) - 1
_DEV_BIT = 1 << 30
_VMEM_BUDGET = 12 << 20


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _page_grid(row0, height):
    """`page_id[height, 128]` for rows starting at `row0` (scalar)."""
    return (
        row0 * 128
        + jax.lax.broadcasted_iota(jnp.int32, (height, 128), 0) * 128
        + jax.lax.broadcasted_iota(jnp.int32, (height, 128), 1)
    )


def _sum32(x):
    """int32 full reduction of `[rows, 128]` by unrolled adds.

    Mosaic's reduce lowering consults the ambient x64 config when the
    kernel is re-traced at jit-COMPILE time (outside any caller-side
    `enable_x64(False)`), inserting an int64 accumulator convert it then
    rejects — so fold rows with static slices and halve the lane axis
    with shifted adds instead; no reduce primitive at all.
    """
    row = x[0:1, :]
    for r in range(1, x.shape[0]):
        row = row + x[r:r + 1, :]
    w = x.shape[1]
    while w > 1:
        w //= 2
        row = row[:, :w] + row[:, w:2 * w]
    return row[0, 0]


def _floored_mod(x, m: int):
    r = jax.lax.rem(x, jnp.int32(m))
    return jnp.where(r < 0, r + jnp.int32(m), r)


def _smem_copy(dst, src, width: int):
    """Element-wise SMEM copy (Mosaic only loads scalars from SMEM)."""

    def cp(j, c):
        dst[0, 0, j] = src[0, 0, j]
        return c

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(width), cp, jnp.int32(0))


# --------------------------------------------------------------- flat
def _flat_kernel(opc_ref, a0_ref, a1_ref, a2_ref, fr_in, fr_out, resp_ref,
                 *, n_pages: int, max_span: int, window: int, rows: int,
                 span_rows: int):
    # the kernel is (re-)traced at jit-COMPILE time, outside any caller's
    # enable_x64(False) context — guard here so an x64 session can't
    # leak int64 converts into the Mosaic lowering
    with x64_disabled():
        _flat_body(opc_ref, a0_ref, a1_ref, a2_ref, fr_in, fr_out,
                   resp_ref, n_pages, max_span, window, rows, span_rows,
                   copy_in=True)


def _flat_plan_kernel(opc_ref, a0_ref, a1_ref, a2_ref, fr_in, tch_in,
                      fr_out, tch_out, resp_ref,
                      *, n_pages: int, max_span: int, window: int,
                      rows: int, span_rows: int):
    # plan variant (r5): one canonical replica, plus a TOUCHED plane
    # marking every page written in-window — the dense delta the vmapped
    # model-side `window_merge` blends per replica (see
    # make_pallas_vspace_plan_step)
    del tch_in  # aliased to tch_out
    with x64_disabled():
        _flat_body(opc_ref, a0_ref, a1_ref, a2_ref, fr_in, fr_out,
                   resp_ref, n_pages, max_span, window, rows, span_rows,
                   tch_out=tch_out)


def _flat_body(opc_ref, a0_ref, a1_ref, a2_ref, fr_in, fr_out, resp_ref,
               n_pages, max_span, window, rows, span_rows, tch_out=None,
               copy_in=False):
    # copy_in=True: UN-aliased in/out — aliased blocked state races with
    # the pipeline's prefetch/writeback on hardware past ~32 grid steps
    # (see ops/pallas_oahashmap._oa_body); the grid=1 plan kernels keep
    # in-place aliasing (copy_in=False)
    if copy_in:
        fr_out[...] = fr_in[...]
    else:
        del fr_in
    P = jnp.int32(n_pages)

    def body(i, carry):
        op = opc_ref[i]
        vs = a0_ref[i]          # RAW vpage: the flat model mods per lane
        a1 = a1_ref[i]
        is_map = op == 1
        is_un = op == 2
        is_span = is_map | is_un
        n = jnp.clip(jnp.where(is_un, a1, a2_ref[i]), 0,
                     jnp.int32(max_span))
        # scalar gate instead of a branch: inactive entries get an empty
        # span (n_eff=0) and the blends write state back unchanged
        n_eff = jnp.where(is_span, n, 0)
        vm = _floored_mod(vs, n_pages)

        def run(blk, row0, base_page):
            page = _page_grid(row0, span_rows)
            lane = page - base_page  # int32 wrap matches the model
            raw = vs + lane
            mask = (
                (lane >= 0) & (lane < n_eff) & (raw < P) & (page < P)
                & (page >= base_page) & (page < base_page + n_eff)
            )
            # arithmetic select: Mosaic cannot legalize a scalar-cond
            # select over i1 vectors (maps count absent pages, unmaps
            # count present ones). Replica 0 speaks for the group under
            # the lock-step invariant.
            pres = (blk[0] != 0).astype(jnp.int32)
            im = is_map.astype(jnp.int32)
            bits = im * (1 - pres) + (1 - im) * pres
            cnt = _sum32(mask.astype(jnp.int32) * bits)
            newv = jnp.where(is_map, a1 + lane, 0)
            return cnt, mask, jnp.where(mask[None], newv[None], blk)

        # run B: lanes with vm+lane < P (pages [vm, vm+n) direct)
        row0 = jnp.minimum(vm >> 7, jnp.int32(rows - span_rows))
        c_b, m_b, out_b = run(fr_out[:, pl.ds(row0, span_rows), :],
                              row0, vm)
        fr_out[:, pl.ds(row0, span_rows), :] = out_b
        if tch_out is not None:
            tb = tch_out[:, pl.ds(row0, span_rows), :]
            tch_out[:, pl.ds(row0, span_rows), :] = jnp.where(
                m_b[None], jnp.int32(1), tb
            )
        # run A: wrapped lanes (pages [0, vm+n-P)) — reachable only when
        # the raw vpage was negative (mod wraps the span). Rows start at
        # STATIC 0 (a concrete-constant pl.ds start miscompiles in
        # Mosaic). Run-A rows never overlap run-B's for n_pages >=
        # span_rows*128 + max_span (checked in make_vspace_replay), so
        # the read-after-write is clean.
        c_a, m_a, out_a = run(fr_out[:, :span_rows, :], 0, vm - P)
        fr_out[:, :span_rows, :] = out_a
        if tch_out is not None:
            ta = tch_out[:, :span_rows, :]
            tch_out[:, :span_rows, :] = jnp.where(
                m_a[None], jnp.int32(1), ta
            )
        resp_ref[0, 0, i] = c_b + c_a
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(window), body, jnp.int32(0))


# -------------------------------------------------------------- radix
def _radix_kernel(opc_ref, a0_ref, a1_ref, a2_ref,
                  pt_in, pd_in, pdpt_in, pml4_in,
                  pt_out, pd_out, pdpt_out, pml4_out, resp_ref,
                  *, n_pages: int, max_span: int, window: int, rows: int,
                  height: int, l2: int, l3: int, l4: int):
    # see _flat_kernel: guard the compile-time re-trace against x64
    with x64_disabled():
        _radix_body(opc_ref, a0_ref, a1_ref, a2_ref, pt_in, pd_in,
                    pdpt_in, pml4_in, pt_out, pd_out, pdpt_out, pml4_out,
                    resp_ref, n_pages, max_span, window, rows, height,
                    l2, l3, l4, copy_in=True)


def _radix_plan_kernel(opc_ref, a0_ref, a1_ref, a2_ref,
                       pt_in, pd_in, pdpt_in, pml4_in,
                       wins_in, clr_in, pdt_in,
                       pt_out, pd_out, pdpt_out, pml4_out, resp_ref,
                       wins_out, clr_out, pdt_out,
                       *, n_pages: int, max_span: int, window: int,
                       rows: int, height: int, l2: int, l3: int,
                       l4: int):
    # plan variant (r5): one canonical replica, extended with the dense
    # delta planes the model-side `window_merge` consumes — WINS (page
    # written since the last region clear), CLEARED (page's region torn
    # down in-window), and the per-PD-entry TOUCHED flags. All three
    # ride the same lane masks as the state blends; the scalar stream is
    # unchanged except two SMEM flag stores per entry.
    del wins_in, clr_in, pdt_in  # aliased to their outs
    with x64_disabled():
        _radix_body(opc_ref, a0_ref, a1_ref, a2_ref, pt_in, pd_in,
                    pdpt_in, pml4_in, pt_out, pd_out, pdpt_out, pml4_out,
                    resp_ref, n_pages, max_span, window, rows, height,
                    l2, l3, l4,
                    plan_refs=(wins_out, clr_out, pdt_out))


def _radix_body(opc_ref, a0_ref, a1_ref, a2_ref, pt_in, pd_in, pdpt_in,
                pml4_in, pt_out, pd_out, pdpt_out, pml4_out, resp_ref,
                n_pages, max_span, window, rows, height, l2, l3, l4,
                plan_refs=None, copy_in=False):
    # copy_in=True: UN-aliased pt in/out (the aliased-block pipeline
    # race — see _flat_body); the grid=1 plan kernel keeps aliasing.
    # pd is the grid-invariant SHARED copy and must be reset from its
    # (unaliased) input at every grid step — later grid steps recompute
    # the identical level trajectory so their responses stay correct
    if copy_in:
        pt_out[...] = pt_in[...]
    else:
        del pt_in
    _smem_copy(pd_out, pd_in, l2)
    P = jnp.int32(n_pages)
    H = height

    def body(i, carry):
        # carry = (pdpt_0..pdpt_{l3-1}, pml4_0) — the upper levels ride
        # registers through the loop (monotone except for the final
        # write-back; they are only ever SET)
        pdpt_c = carry[:l3]
        pml4_c = carry[l3]
        op = opc_ref[i]
        vs = _floored_mod(a0_ref[i], n_pages)  # the model mods up front
        a1 = a1_ref[i]
        is_map = (op == 1) | (op == 2)
        is_dev = op == 2
        is_un = op == 3
        is_tbl = op == 4
        is_span = is_map | is_un
        n = jnp.clip(jnp.where(is_un, a1, a2_ref[i]), 0,
                     jnp.int32(max_span))
        # scalar gates instead of branches: inactive entries see an
        # empty span and an empty region, and every blend becomes an
        # identity write
        n_eff = jnp.where(is_span, n, 0)
        tbl_lim = jnp.where(is_tbl, P, jnp.int32(-1))
        r0 = vs >> 9
        r1 = jnp.minimum(r0 + 1, jnp.int32(l2 - 1))
        q_span = jnp.minimum(vs >> 7, jnp.int32(rows - H))
        q_tbl = jnp.minimum(r0 * 4, jnp.int32(rows - H))
        row0 = jnp.where(is_tbl, q_tbl, q_span)
        blk = pt_out[:, pl.ds(row0, H), :]            # [G, H, 128]
        page = _page_grid(row0, H)                    # [H, 128]
        mask_span = (page >= vs) & (page < vs + n_eff) & (page < P)
        mask_tbl = (page < tbl_lim) & ((page >> 9) == r0)
        # ---- full walk BEFORE the op (levels read pre-update) --------
        pd0 = pd_out[0, 0, r0]
        pd1 = pd_out[0, 0, r1]
        pd_l = jnp.where((page >> 9) == r0, pd0, pd1)
        pdpt_l = jnp.broadcast_to(pdpt_c[l3 - 1], page.shape)
        for k in range(l3 - 1):
            pdpt_l = jnp.where((page >> 18) == k, pdpt_c[k], pdpt_l)
        # P < 2^27 (VMEM gate) => every page's PML4 entry is 0
        walk = (
            (pd_l > 0) & (pdpt_l > 0) & (pml4_c > 0) & (blk[0] != 0)
        ).astype(jnp.int32)
        # responses: maps count not-fully-walked span pages, unmaps
        # count walked ones, teardown counts walked region pages —
        # arithmetic select (scalar-cond select over i1 vectors does not
        # legalize in Mosaic)
        im = is_map.astype(jnp.int32)
        span_bits = mask_span.astype(jnp.int32) * (
            im * (1 - walk) + (1 - im) * walk
        )
        tbl_bits = mask_tbl.astype(jnp.int32) * walk
        resp_ref[0, 0, i] = _sum32(span_bits + tbl_bits)
        # ---- unified state blend -------------------------------------
        entry = ((a1 + (page - vs) + 1) & jnp.int32(_FRAME_MASK)) | (
            jnp.where(is_dev, jnp.int32(_DEV_BIT), 0)
        )
        newv = jnp.where(is_map, entry, 0)            # unmap stores 0
        out = jnp.where(mask_span[None], newv[None], blk)
        out = jnp.where(mask_tbl[None], 0, out)
        pt_out[:, pl.ds(row0, H), :] = out
        if plan_refs is not None:
            wins_out, clr_out, _pdt = plan_refs
            # wins: written-since-last-clear — map/unmap lanes set, a
            # region teardown resets its pages
            wblk = wins_out[:, pl.ds(row0, H), :]
            wnew = jnp.where(mask_span[None], jnp.int32(1), wblk)
            wnew = jnp.where(mask_tbl[None], jnp.int32(0), wnew)
            wins_out[:, pl.ds(row0, H), :] = wnew
            cblk = clr_out[:, pl.ds(row0, H), :]
            clr_out[:, pl.ds(row0, H), :] = jnp.where(
                mask_tbl[None], jnp.int32(1), cblk
            )
        # ---- level updates (mirrors _mark_levels + teardown) ---------
        live = is_map & (n > 0)
        last = jnp.maximum(vs + n - 1, vs)
        ok0 = live & (r0 <= (last >> 9))
        ok1 = live & (r0 + 1 <= (last >> 9)) & (r0 + 1 < l2)
        value0 = jnp.where(is_tbl, 0, jnp.where(ok0, 1, pd0))
        value1 = jnp.where(ok1, 1, jnp.where(r1 == r0, value0, pd1))
        pd_out[0, 0, r0] = value0
        pd_out[0, 0, r1] = value1
        if plan_refs is not None:
            _pdt = plan_refs[2]
            # touched = a real update landed (mark under ok0/ok1, clear
            # under is_tbl); passthrough writes don't count
            _pdt[0, 0, r0] = jnp.where(ok0 | is_tbl, 1, _pdt[0, 0, r0])
            _pdt[0, 0, r1] = jnp.where(ok1, 1, _pdt[0, 0, r1])
        h0 = vs >> 18
        hl = last >> 18
        new_pdpt = tuple(
            jnp.where(live & ((h0 == k) | (hl == k)), 1, pdpt_c[k])
            for k in range(l3)
        )
        new_pml4 = jnp.where(live, 1, pml4_c)  # vs>>27 == 0 under gate
        return new_pdpt + (new_pml4,)

    init = tuple(pdpt_in[0, 0, k] for k in range(l3)) + (pml4_in[0, 0, 0],)
    final = jax.lax.fori_loop(jnp.int32(0), jnp.int32(window), body, init)
    for k in range(l3):
        pdpt_out[0, 0, k] = final[k]
    pml4_out[0, 0, 0] = final[l3]


def _levels(n_pages: int):
    l2 = max(1, -(-n_pages // 512))
    l3 = max(1, -(-n_pages // (512 ** 2)))
    l4 = max(1, -(-n_pages // (512 ** 3)))
    return l2, l3, l4


def _grid_layout(n_pages: int, n_replicas: int, interpret: bool,
                 what: str, aliased: bool = False):
    """ROWS (page rows per replica) and G (replicas per grid step).

    `aliased=True` (the grid=1 plan kernels): one in-place pt buffer.
    `aliased=False` (multi-grid-step classic kernels): separate in+out
    blocks (the pipeline race — see _flat_body), each double-buffered.
    """
    rows = max(4, _round_up(n_pages, 512) // 128)
    per = (2 if aliased else 4) * rows * 128 * 4
    if per > _VMEM_BUDGET and not interpret:
        raise ValueError(
            f"{what} pallas replay needs {per >> 20} MB of VMEM for "
            f"n_pages={n_pages}; use the combined/scan engines "
            f"(core/step.make_step) for this config"
        )
    group = 1
    for g in range(n_replicas, 0, -1):
        if n_replicas % g == 0 and g * per <= _VMEM_BUDGET:
            group = g
            break
    return rows, group


def make_vspace_replay(
    n_pages: int,
    n_replicas: int,
    window: int,
    max_span: int,
    radix: bool,
    interpret: bool = False,
):
    """Build the chunk replayer.

    flat:  `replay(opc[W], args[W,3], frames[R, ROWS, 128])
            -> (frames, resps[W])`
    radix: `replay(opc[W], args[W,3], pt[R, ROWS, 128], pd[l2],
            pdpt[l3], pml4[l4]) -> (pt, pd, pdpt, pml4, resps[W])`

    Levels and responses are single canonical copies under the lock-step
    identical-replicas invariant (see module docstring).
    """
    from jax.experimental.pallas import tpu as pltpu

    if max_span > 512:
        raise ValueError("max_span > 512 breaks the 2-entry/level "
                         "invariant of the radix walk kernel")
    what = "radix vspace" if radix else "flat vspace"
    rows, group = _grid_layout(n_pages, n_replicas, interpret, what)
    span_rows = min(-(-max_span // 128) + 1, rows)
    if not radix and n_pages < span_rows * 128 + max_span:
        raise ValueError(
            f"flat vspace pallas replay needs n_pages >= "
            f"{span_rows * 128 + max_span} so a mod-wrapped span's two "
            f"row blends never overlap; use the combined engine for "
            f"n_pages={n_pages}"
        )
    from node_replication_tpu.ops.pallas_chunk import (
        build_calls,
        chunk_size,
        run_chunks,
    )

    chunk_r = chunk_size(n_replicas, group)
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    # single canonical copies: every grid step recomputes the identical
    # values from the identical window (idempotent revisions)
    shared = lambda width: pl.BlockSpec(
        (1, 1, width), lambda i: (0, 0, 0), memory_space=pltpu.SMEM)

    if not radix:
        kernel = functools.partial(
            _flat_kernel, n_pages=n_pages, max_span=max_span,
            window=window, rows=rows, span_rows=span_rows,
        )

        def build_call(sub_r: int):
            state_spec = pl.BlockSpec((group, rows, 128),
                                      lambda i: (i, 0, 0))
            return pl.pallas_call(
                kernel,
                grid=(sub_r // group,),
                in_specs=[smem(), smem(), smem(), smem(), state_spec],
                out_specs=[state_spec, shared(window)],
                out_shape=[
                    jax.ShapeDtypeStruct((sub_r, rows, 128), jnp.int32),
                    jax.ShapeDtypeStruct((1, 1, window), jnp.int32),
                ],
                # NO aliasing: un-aliased in/out (pipeline race)
                interpret=interpret,
            )

        calls = build_calls(n_replicas, chunk_r, build_call)

        def replay(opc, args, frames):
            with x64_disabled():
                a0, a1, a2 = args[:, 0], args[:, 1], args[:, 2]
                (frames,), (resps,) = run_chunks(
                    n_replicas, chunk_r, calls,
                    lambda call, r0, sub: call(
                        opc, a0, a1, a2, frames[r0:r0 + sub]
                    ),
                    n_plane_outs=1,
                )
            return frames, resps.reshape(window)

        return replay

    l2, l3, l4 = _levels(n_pages)
    assert l4 == 1, "unreachable: the VMEM gate caps n_pages << 2^27"
    height = max(span_rows, 4)
    kernel = functools.partial(
        _radix_kernel, n_pages=n_pages, max_span=max_span, window=window,
        rows=rows, height=height, l2=l2, l3=l3, l4=l4,
    )

    def build_call(sub_r: int):
        state_spec = pl.BlockSpec((group, rows, 128),
                                  lambda i: (i, 0, 0))
        return pl.pallas_call(
            kernel,
            grid=(sub_r // group,),
            in_specs=[smem(), smem(), smem(), smem(), state_spec,
                      shared(l2), shared(l3), shared(l4)],
            out_specs=[state_spec, shared(l2), shared(l3), shared(l4),
                       shared(window)],
            out_shape=[
                jax.ShapeDtypeStruct((sub_r, rows, 128), jnp.int32),
                jax.ShapeDtypeStruct((1, 1, l2), jnp.int32),
                jax.ShapeDtypeStruct((1, 1, l3), jnp.int32),
                jax.ShapeDtypeStruct((1, 1, l4), jnp.int32),
                jax.ShapeDtypeStruct((1, 1, window), jnp.int32),
            ],
            # NO aliasing: un-aliased in/out (pipeline race)
            interpret=interpret,
        )

    calls = build_calls(n_replicas, chunk_r, build_call)

    def replay(opc, args, pt, pd, pdpt, pml4):
        with x64_disabled():
            a0, a1, a2 = args[:, 0], args[:, 1], args[:, 2]
            pd3 = pd.reshape(1, 1, l2)
            pdpt3 = pdpt.reshape(1, 1, l3)
            pml43 = pml4.reshape(1, 1, l4)
            # the level tables are canonical: each chunk recomputes the
            # identical trajectory, so the LAST chunk's outputs speak
            # for the fleet (run_chunks' `rest` contract)
            (pt,), (pd_o, pdpt_o, pml4_o, resps) = run_chunks(
                n_replicas, chunk_r, calls,
                lambda call, r0, sub: call(
                    opc, a0, a1, a2, pt[r0:r0 + sub], pd3, pdpt3, pml43
                ),
                n_plane_outs=1,
            )
        return (pt, pd_o.reshape(l2), pdpt_o.reshape(l3),
                pml4_o.reshape(l4), resps.reshape(window))

    return replay


def make_vspace_plan_replay(
    n_pages: int,
    window: int,
    max_span: int,
    radix: bool,
    interpret: bool = False,
):
    """Canonical-replica PLAN kernel: the span kernel run with R=1,
    extended to emit the dense in-window delta planes `window_merge`
    consumes (see `make_pallas_vspace_plan_step`).

    flat:  `plan_replay(opc[W], args[W,3], frames[1,ROWS,128],
            tch[1,ROWS,128]) -> (frames, tch, resps[W])`
    radix: `plan_replay(opc, args, pt[1,ROWS,128], pd[l2], pdpt[l3],
            pml4[l4], wins[1,ROWS,128], clr[1,ROWS,128], pdt[l2])
            -> (pt, pd, pdpt, pml4, wins, clr, pdt, resps[W])`

    All planes are carried across chunk calls, so a step's chunks
    compose: a later chunk's region clear resets earlier chunks' wins.
    """
    from jax.experimental.pallas import tpu as pltpu

    if max_span > 512:
        raise ValueError("max_span > 512 breaks the 2-entry/level "
                         "invariant of the radix walk kernel")
    what = "radix vspace plan" if radix else "flat vspace plan"
    rows, _ = _grid_layout(n_pages, 1, interpret, what, aliased=True)
    span_rows = min(-(-max_span // 128) + 1, rows)
    if not radix and n_pages < span_rows * 128 + max_span:
        raise ValueError(
            f"flat vspace plan replay needs n_pages >= "
            f"{span_rows * 128 + max_span}; use the combined engine for "
            f"n_pages={n_pages}"
        )
    grid = (1,)
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    plane = pl.BlockSpec((1, rows, 128), lambda i: (0, 0, 0))
    shared = lambda width: pl.BlockSpec(
        (1, 1, width), lambda i: (0, 0, 0), memory_space=pltpu.SMEM)
    pshape = jax.ShapeDtypeStruct((1, rows, 128), jnp.int32)

    if not radix:
        kernel = functools.partial(
            _flat_plan_kernel, n_pages=n_pages, max_span=max_span,
            window=window, rows=rows, span_rows=span_rows,
        )
        call = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[smem(), smem(), smem(), smem(), plane, plane],
            out_specs=[plane, plane, shared(window)],
            out_shape=[
                pshape, pshape,
                jax.ShapeDtypeStruct((1, 1, window), jnp.int32),
            ],
            input_output_aliases={4: 0, 5: 1},
            interpret=interpret,
        )

        def plan_replay(opc, args, frames, tch):
            with x64_disabled():
                frames, tch, resps = call(
                    opc, args[:, 0], args[:, 1], args[:, 2], frames, tch
                )
            return frames, tch, resps.reshape(window)

        return plan_replay

    l2, l3, l4 = _levels(n_pages)
    height = max(span_rows, 4)
    kernel = functools.partial(
        _radix_plan_kernel, n_pages=n_pages, max_span=max_span,
        window=window, rows=rows, height=height, l2=l2, l3=l3, l4=l4,
    )
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[smem(), smem(), smem(), smem(), plane,
                  shared(l2), shared(l3), shared(l4),
                  plane, plane, shared(l2)],
        out_specs=[plane, shared(l2), shared(l3), shared(l4),
                   shared(window), plane, plane, shared(l2)],
        out_shape=[
            pshape,
            jax.ShapeDtypeStruct((1, 1, l2), jnp.int32),
            jax.ShapeDtypeStruct((1, 1, l3), jnp.int32),
            jax.ShapeDtypeStruct((1, 1, l4), jnp.int32),
            jax.ShapeDtypeStruct((1, 1, window), jnp.int32),
            pshape, pshape,
            jax.ShapeDtypeStruct((1, 1, l2), jnp.int32),
        ],
        input_output_aliases={4: 0, 8: 5, 9: 6, 10: 7},
        interpret=interpret,
    )

    def plan_replay(opc, args, pt, pd, pdpt, pml4, wins, clr, pdt):
        with x64_disabled():
            pt, pd, pdpt, pml4, resps, wins, clr, pdt = call(
                opc, args[:, 0], args[:, 1], args[:, 2], pt,
                pd.reshape(1, 1, l2), pdpt.reshape(1, 1, l3),
                pml4.reshape(1, 1, l4), wins, clr,
                pdt.reshape(1, 1, l2),
            )
        return (pt, pd.reshape(l2), pdpt.reshape(l3), pml4.reshape(l4),
                wins, clr, pdt.reshape(l2), resps.reshape(window))

    return plan_replay


def make_pallas_vspace_plan_step(
    n_pages: int,
    spec: LogSpec,
    writes_per_replica: int,
    reads_per_replica: int,
    max_span: int,
    radix: bool,
    dispatch,
    interpret: bool = False,
    jit: bool = True,
    donate: bool = True,
):
    """Pallas-PLANNED combined step: the fleet-scale vspace engine (r5).

    The window's sequential semantics run ONCE, on a single canonical
    replica, inside the span kernel (bit-exact, fixed-size chunks so
    compile cost is window-independent); the kernel additionally emits
    the dense in-window delta planes, from which the model's own
    `window_merge` does the honest per-replica dense replay work —
    vmapped over the fleet in MODEL layout, pure HBM-bound blends.

    Why this is the scaling engine: step time ≈ span x ~1.2 µs (the
    kernel's Mosaic scalar stream, R-independent) + R x O(P/HBM-BW)
    merge, so fleet throughput grows ~linearly with R, where the classic
    grouped kernel is capped at G/450 ns by VMEM (G replicas per grid
    step) and the XLA plan pays ~19 µs/entry in sort/scatter passes
    whose COMPILE time also grows with the window. Same lock-step precondition as `core/step`'s
    plan/merge path; differential suite:
    tests/test_pallas_vspace.py::TestPlanStep.
    """
    from node_replication_tpu.ops.encoding import dispatch_reads

    R = spec.n_replicas
    Bw = int(writes_per_replica)
    span = R * Bw
    chunk = span
    while chunk > 4096 and chunk % 2 == 0:
        chunk //= 2
    replay = make_vspace_plan_replay(
        n_pages, chunk, max_span, radix, interpret=interpret
    )
    rows, _ = _grid_layout(n_pages, 1, interpret,
                           "vspace plan (layout)", aliased=True)
    P = n_pages

    def to_plane(flat, dtype=jnp.int32):
        padded = jnp.zeros((rows * 128,), dtype).at[:P].set(
            flat.astype(dtype)
        )
        return padded.reshape(1, rows, 128)

    def from_plane(plane):
        return plane.reshape(-1)[:P]

    def step(log, states, wr_opcodes, wr_args, rd_opcodes, rd_args):
        opc = wr_opcodes.reshape(span)
        args = wr_args.reshape(span, spec.arg_width)
        log = log_append(spec, log, opc, args, span)
        # distinct allocations: wins/clr are separately aliased kernel
        # in/outs and must not share one buffer
        zero_plane = lambda: jnp.zeros((1, rows, 128), jnp.int32)
        resp_chunks = []
        if radix:
            l2 = states["pd"].shape[-1]
            pt = to_plane(states["pt"][0])
            pd = states["pd"][0].astype(jnp.int32)
            pdpt0 = states["pdpt"][0]
            pml40 = states["pml4"][0]
            pdpt = pdpt0.astype(jnp.int32)
            pml4 = pml40.astype(jnp.int32)
            wins, clr = zero_plane(), zero_plane()
            pdt = jnp.zeros((l2,), jnp.int32)
            for c0 in range(0, span, chunk):
                pt, pd, pdpt, pml4, wins, clr, pdt, r = replay(
                    opc[c0:c0 + chunk], args[c0:c0 + chunk], pt, pd,
                    pdpt, pml4, wins, clr, pdt,
                )
                resp_chunks.append(r)
            plan = {
                "pt_wins": from_plane(wins) > 0,
                "pt_value": from_plane(pt),
                "pt_cleared": from_plane(clr) > 0,
                "pd_touched": pdt > 0,
                "pd_value": pd > 0,
                # monotone levels: in-window first-sets = final & ~init
                "pdpt_set": (pdpt > 0) & ~pdpt0,
                "pml4_set": (pml4 > 0) & ~pml40,
                "resps": (
                    jnp.concatenate(resp_chunks)
                    if len(resp_chunks) > 1 else resp_chunks[0]
                ),
            }
        else:
            frames = to_plane(states["frames"][0])
            tch = zero_plane()
            for c0 in range(0, span, chunk):
                frames, tch, r = replay(
                    opc[c0:c0 + chunk], args[c0:c0 + chunk], frames, tch
                )
                resp_chunks.append(r)
            plan = {
                "touched": from_plane(tch) > 0,
                "value": from_plane(frames),
                "resps": (
                    jnp.concatenate(resp_chunks)
                    if len(resp_chunks) > 1 else resp_chunks[0]
                ),
            }
        # honest per-replica dense replay: the model's own merge blends
        # the plan against every replica's own tables
        states, resps = jax.vmap(
            lambda s: dispatch.window_merge(s, plan)
        )(states)
        log = log._replace(
            ltails=jnp.broadcast_to(log.tail, (R,)), ctail=log.tail,
            head=log.tail,
        )
        own = jnp.arange(R, dtype=jnp.int32)[:, None] * Bw + jnp.arange(
            Bw, dtype=jnp.int32
        )[None, :]
        wr_resps = jnp.take_along_axis(resps, own, axis=1)
        rd_resps = dispatch_reads(dispatch, states, rd_opcodes, rd_args)
        return log, states, wr_resps, rd_resps

    if jit:
        step = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    return step


# ------------------------------------------------- state converters
def pallas_vspace_state(n_pages: int, n_replicas: int, radix: bool,
                        model_state=None):
    """Pallas-layout state, optionally seeded from one model-state pytree
    (`make_vspace`/`make_vspace_radix` `init_state()` shapes). Page
    tables are per replica; level tables are the single canonical copy
    of the lock-step invariant."""
    rows = max(4, _round_up(n_pages, 512) // 128)

    def grid3(flat):
        padded = jnp.zeros((rows * 128,), jnp.int32).at[:n_pages].set(flat)
        return jnp.broadcast_to(
            padded.reshape(rows, 128), (n_replicas, rows, 128)
        )

    if not radix:
        frames = (
            model_state["frames"] if model_state is not None
            else jnp.zeros((n_pages,), jnp.int32)
        )
        return {"frames": grid3(frames)}
    l2, l3, l4 = _levels(n_pages)

    def lvl(width, key):
        if model_state is None:
            return jnp.zeros((width,), jnp.int32)
        return model_state[key].astype(jnp.int32)

    pt = (
        model_state["pt"] if model_state is not None
        else jnp.zeros((n_pages,), jnp.int32)
    )
    return {
        "pt": grid3(pt), "pd": lvl(l2, "pd"), "pdpt": lvl(l3, "pdpt"),
        "pml4": lvl(l4, "pml4"),
    }


def model_view(state, n_pages: int, radix: bool):
    """Model-layout view of pallas state (per replica), for reads and
    differential tests: `{"pt": int32[R, P], "pd": bool[R, l2], ...}`."""
    if not radix:
        R = state["frames"].shape[0]
        return {"frames": state["frames"].reshape(R, -1)[:, :n_pages]}
    R = state["pt"].shape[0]
    bc = lambda v: jnp.broadcast_to(v > 0, (R,) + v.shape)
    return {
        "pt": state["pt"].reshape(R, -1)[:, :n_pages],
        "pd": bc(state["pd"]),
        "pdpt": bc(state["pdpt"]),
        "pml4": bc(state["pml4"]),
    }


def _vspace_reads(n_pages: int, max_span: int, radix: bool):
    """Per-replica read dispatch DIRECTLY on the pallas layout.

    Bit-identical to `dispatch_reads` over `model_view` (the step test
    pins this against the scan step) but without materializing the view:
    the `[R, ROWS, 128]` page grid answers reads through small gathers
    (`p -> [r, p>>7, p&127]`) instead of a whole-state relayout copy per
    step. Opcodes follow `models/vspace.py`: identify=1, resolved=2,
    (radix) tables=3; NOOP/unknown answer 0.
    """
    P = n_pages
    S = max_span

    def gather_pt(grid3, pages):
        # pages int32[R, B, L] (sentinel P -> 0-fill)
        safe = jnp.clip(pages, 0, P - 1)
        r_ix = jnp.arange(grid3.shape[0], dtype=jnp.int32).reshape(
            -1, *([1] * (pages.ndim - 1))
        )
        vals = grid3[r_ix, safe >> 7, safe & 127]
        return jnp.where(pages < P, vals, 0)

    def span_pages(vpage, npages):
        lanes = jnp.arange(S, dtype=jnp.int32)
        n = jnp.clip(npages, 0, S)[..., None]
        raw = vpage[..., None] + lanes
        return jnp.where((lanes < n) & (raw < P), raw % P, P)

    def reads(states, rd_opcodes, rd_args):
        a0, a1 = rd_args[..., 0], rd_args[..., 1]
        if not radix:
            grid3 = states["frames"]
            v = a0 % P
            f = gather_pt(grid3, v[..., None])[..., 0]
            ident = jnp.where(f == 0, jnp.int32(-1), f)
            pages = span_pages(a0, a1)
            resolved = jnp.sum(
                (pages < P) & (gather_pt(grid3, pages) != 0), axis=-1
            ).astype(jnp.int32)
            out = jnp.where(rd_opcodes == 1, ident, 0)
            return jnp.where(rd_opcodes == 2, resolved, out)
        grid3 = states["pt"]
        pd, pdpt, pml4 = states["pd"], states["pdpt"], states["pml4"]

        def walk(pages):
            safe = jnp.clip(pages, 0, P - 1)
            return (
                (pages < P)
                & (pml4[jnp.clip(safe >> 27, 0, pml4.shape[0] - 1)] > 0)
                & (pdpt[jnp.clip(safe >> 18, 0, pdpt.shape[0] - 1)] > 0)
                & (pd[safe >> 9] > 0)
                & (gather_pt(grid3, pages) != 0)
            )

        v = a0 % P
        pt_v = gather_pt(grid3, v[..., None])[..., 0]
        ident = jnp.where(walk(v[..., None])[..., 0], pt_v, jnp.int32(-1))
        pages = span_pages(a0 % P, a1)
        resolved = jnp.sum(walk(pages), axis=-1).astype(jnp.int32)
        tables = jnp.sum(pd > 0).astype(jnp.int32)
        out = jnp.where(rd_opcodes == 1, ident, 0)
        out = jnp.where(rd_opcodes == 2, resolved, out)
        return jnp.where(rd_opcodes == 3, tables, out)

    return reads


# ------------------------------------------------- fused combiner round
def _fused_flat_kernel(meta_ref, opc_ref, a0_ref, a1_ref, a2_ref,
                       *refs, n_planes: int, n_pages: int,
                       max_span: int, window: int, rows: int,
                       span_rows: int, win_rows: int):
    """Fused flat-vspace combiner round: the span-machinery replay body
    (`_flat_body` — unchanged, so the replay semantics cannot drift
    from the replay-only kernel) prefixed with the ring-window append
    DMA (`ops/pallas_ring.py`). One launch appends the batch to the
    ring AND replays it into every replica group."""
    from node_replication_tpu.ops.pallas_ring import (
        ring_append_dma,
        split_ring_refs,
    )

    # ring content flows via the aliasing; the ring inputs go unread
    lo, hi, _ring_in, rest = split_ring_refs(refs, n_planes)
    fr_in, *rest = rest
    ring_out = rest[:n_planes]
    fr_out, resp_ref, sem = rest[n_planes:]
    with x64_disabled():
        @pl.when(pl.program_id(0) == 0)
        def _append():
            ring_append_dma(sem, meta_ref[0], win_rows, lo, hi, ring_out)

        _flat_body(opc_ref, a0_ref, a1_ref, a2_ref, fr_in, fr_out,
                   resp_ref, n_pages, max_span, window, rows,
                   span_rows, copy_in=True)



class FusedVspaceEngine(FusedEngineHost):
    """Fused append+replay engine for the FLAT vspace model — the
    span-machinery twin of `ops/pallas_replay.FusedHashmapEngine` (same
    engine contract, same `core/replica.py` tier routing). Page-table
    state crosses the boundary in MODEL layout (`frames: int32[R, P]`);
    the `[R, ROWS, 128]` grid padding lives inside the round. Responses
    are the kernel's canonical copy broadcast per replica — sound under
    the same lock-step precondition the tier's eligibility check
    enforces. No fenced variant: the span kernel's group layout lets
    replica 0 speak for its group, which a frozen corrupt lane would
    poison — fenced fleets fall back to the chain
    (`supports_fenced=False`), meshed or not: the MESH-FUSED
    composition (`parallel/collectives.py:MeshFusedEngine`) builds
    this engine per replica shard through the same factory, and its
    canonical responses broadcast per shard exactly as they do
    fleet-wide. The radix model keeps the replay-only kernels (its
    level tables ride registers; a fused variant is a follow-up)."""

    supports_fenced = False

    def __init__(self, n_pages: int, max_span: int, spec,
                 interpret: bool | None = None):
        import jax as _jax

        from node_replication_tpu.ops.pallas_ring import fused_window_ok

        if interpret is None:
            interpret = _jax.default_backend() != "tpu"
        rows, group = _grid_layout(n_pages, spec.n_replicas, interpret,
                                   "fused flat vspace")
        span_rows = min(-(-max_span // 128) + 1, rows)
        if n_pages < span_rows * 128 + max_span:
            raise ValueError(
                f"fused flat vspace needs n_pages >= "
                f"{span_rows * 128 + max_span} (mod-wrapped span row "
                f"non-overlap); got {n_pages}"
            )
        if not fused_window_ok(spec.capacity, 1):
            raise ValueError(
                f"fused vspace engine: ring capacity {spec.capacity} "
                f"has no 128-slot row layout"
            )
        self.n_pages = int(n_pages)
        self.max_span = int(max_span)
        self.spec = spec
        self.interpret = bool(interpret)
        self._rows = rows
        self._group = group
        self._calls: dict = {}
        self._init_host()

    def supports(self, window: int) -> bool:
        from node_replication_tpu.ops.pallas_ring import fused_window_ok

        # 4096-entry SMEM window bound: the replay-only step chunks
        # past it; the fused round keeps one launch and falls back
        return (
            window <= 4096
            and fused_window_ok(self.spec.capacity, window)
            and window <= self.spec.capacity - self.spec.gc_slack
        )

    def launches(self, window: int) -> int:
        # derived from the BUILT chunk structure (the same chunk_r the
        # round loop iterates), like the hashmap engine — not a
        # recomputation that could drift from what actually dispatches
        _, chunk_r = self._built(window)
        return -(-self.spec.n_replicas // chunk_r)

    def _built(self, window: int):
        calls = self._calls.get(window)
        if calls is None:
            calls = self._build_calls(window)
            self._calls[window] = calls
        return calls

    def _build_calls(self, window: int):
        from jax.experimental.pallas import tpu as pltpu

        from node_replication_tpu.ops.pallas_chunk import (
            build_calls,
            chunk_size,
        )
        from node_replication_tpu.ops.pallas_ring import (
            ring_rows,
            window_rows,
        )

        spec = self.spec
        rows, group = self._rows, self._group
        span_rows = min(-(-self.max_span // 128) + 1, rows)
        win = window_rows(window)
        nrr = ring_rows(spec.capacity)
        A = spec.arg_width
        n_planes = 1 + A
        kernel = functools.partial(
            _fused_flat_kernel, n_planes=n_planes,
            n_pages=self.n_pages, max_span=self.max_span,
            window=window, rows=rows, span_rows=span_rows, win_rows=win,
        )
        smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
        anyspec = lambda: pl.BlockSpec(memory_space=pltpu.ANY)
        vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
        shared = pl.BlockSpec((1, 1, window), lambda i: (0, 0, 0),
                              memory_space=pltpu.SMEM)
        ring_shape = jax.ShapeDtypeStruct((nrr, 128), jnp.int32)
        ring0 = 5 + 2 * n_planes

        def build_call(sub_r: int):
            state_spec = pl.BlockSpec((group, rows, 128),
                                      lambda i: (i, 0, 0))
            return pl.pallas_call(
                kernel,
                grid=(sub_r // group,),
                in_specs=(
                    [smem()] * 5                  # meta, opc/a0/a1/a2
                    + [vmem()] * (2 * n_planes)   # lo + hi spans
                    + [anyspec()] * n_planes      # ring planes
                    + [state_spec]
                ),
                out_specs=[anyspec()] * n_planes + [state_spec, shared],
                out_shape=[ring_shape] * n_planes + [
                    jax.ShapeDtypeStruct((sub_r, rows, 128), jnp.int32),
                    jax.ShapeDtypeStruct((1, 1, window), jnp.int32),
                ],
                # UN-BLOCKED ring planes aliased in->out (outside the
                # grid pipeline — the r5-safe aliasing regime)
                input_output_aliases={
                    ring0 + i: i for i in range(n_planes)
                },
                scratch_shapes=[pltpu.SemaphoreType.DMA(())],
                interpret=self.interpret,
            )

        chunk_r = chunk_size(spec.n_replicas, group)
        return build_calls(spec.n_replicas, chunk_r, build_call), chunk_r

    def round_fn(self, window: int, fenced: bool = False):
        from node_replication_tpu.ops.pallas_ring import (
            append_window_planes,
            fused_cursor_lattice,
            ring_log,
            ring_planes,
        )

        if fenced:
            raise ValueError(
                "fused vspace round has no fenced variant "
                "(supports_fenced=False)"
            )
        calls, chunk_r = self._built(window)
        spec = self.spec
        R, P = spec.n_replicas, self.n_pages
        rows = self._rows

        def fn(log, states, opcodes, args, count, fenced_vec=None):
            ring = ring_planes(log)
            s_lo, lo, hi = append_window_planes(
                spec.mask, ring, opcodes, args, log.tail, count,
            )
            meta = jnp.stack([s_lo, jnp.asarray(count, jnp.int32)])
            fr = jnp.zeros((R, rows * 128), jnp.int32).at[:, :P].set(
                states["frames"]
            ).reshape(R, rows, 128)
            a0, a1, a2 = args[:, 0], args[:, 1], args[:, 2]
            n = len(ring)
            fr_chunks = []
            resp = None
            with x64_disabled():
                for r0 in range(0, R, chunk_r):
                    sub = min(chunk_r, R - r0)
                    out = calls[sub](
                        meta, opcodes, a0, a1, a2, *lo, *hi, *ring,
                        fr[r0:r0 + sub],
                    )
                    ring, (f, resp) = tuple(out[:n]), out[n:]
                    fr_chunks.append(f)
            fr = (
                fr_chunks[0] if len(fr_chunks) == 1
                else jnp.concatenate(fr_chunks, axis=0)
            )
            log = ring_log(log, ring)
            log = fused_cursor_lattice(log, count, None)
            states = {"frames": fr.reshape(R, -1)[:, :P]}
            # canonical responses, shared by the lock-step fleet
            resps = jnp.broadcast_to(
                resp.reshape(window)[None], (R, window)
            )
            return log, states, resps

        return fn

    # round() — the host entry with metrics + the kernel-launch event —
    # is inherited from FusedEngineHost (ops/pallas_ring.py); the
    # fenced-mask rejection falls out of supports_fenced=False there


def make_pallas_vspace_step(
    n_pages: int,
    spec: LogSpec,
    writes_per_replica: int,
    reads_per_replica: int,
    max_span: int,
    radix: bool,
    interpret: bool = False,
    jit: bool = True,
    donate: bool = True,
):
    """Pallas twin of `core/step.make_step` for the vspace models: append
    the fleet's batch to the ring, replay it in order into every replica
    via the kernel (chunked to bound SMEM), answer reads natively on the
    pallas layout (`_vspace_reads` — bit-identical to the model's read
    ops, pinned by the step test).

    Requires — and preserves — the lock-step identical-replicas
    invariant (every replica starts from the same init and replays the
    full window each step), which is already the precondition of the
    fused `core/step` contract.
    """
    R = spec.n_replicas
    Bw = int(writes_per_replica)
    span = R * Bw
    # chunk the window only past 4096 entries: the window rides SMEM
    # (5 int32 arrays -> 80 KB at 4096, within v5e scalar memory), and
    # each extra chunk re-pays the call's fixed dispatch+DMA cost
    chunk = span
    while chunk > 4096 and chunk % 2 == 0:
        chunk //= 2
    replay = make_vspace_replay(
        n_pages, R, chunk, max_span, radix, interpret=interpret
    )
    reads = _vspace_reads(n_pages, max_span, radix)

    def step(log, states, wr_opcodes, wr_args, rd_opcodes, rd_args):
        opc = wr_opcodes.reshape(span)
        args = wr_args.reshape(span, spec.arg_width)
        log = log_append(spec, log, opc, args, span)
        resp_chunks = []
        if radix:
            pt, pd, pdpt, pml4 = (states["pt"], states["pd"],
                                  states["pdpt"], states["pml4"])
            for c0 in range(0, span, chunk):
                pt, pd, pdpt, pml4, r = replay(
                    opc[c0:c0 + chunk], args[c0:c0 + chunk], pt, pd,
                    pdpt, pml4,
                )
                resp_chunks.append(r)
            states = {"pt": pt, "pd": pd, "pdpt": pdpt, "pml4": pml4}
        else:
            frames = states["frames"]
            for c0 in range(0, span, chunk):
                frames, r = replay(
                    opc[c0:c0 + chunk], args[c0:c0 + chunk], frames
                )
                resp_chunks.append(r)
            states = {"frames": frames}
        resps = (
            jnp.concatenate(resp_chunks, axis=0)
            if len(resp_chunks) > 1 else resp_chunks[0]
        )  # [span] — shared across replicas (lock-step invariant)
        log = log._replace(
            ltails=log.ltails + span, ctail=log.ctail + span,
            head=log.head + span,
        )
        own = jnp.arange(R, dtype=jnp.int32)[:, None] * Bw + jnp.arange(
            Bw, dtype=jnp.int32
        )[None, :]
        wr_resps = resps[own]
        rd_resps = reads(states, rd_opcodes, rd_args)
        return log, states, wr_resps, rd_resps

    if jit:
        step = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    return step
