"""Replicated virtual address space (page-table workload).

The reference replays a full x86-64 4-level page table (PML4→PDPT→PD→PT)
through NR with Map / MapDevice / Identify ops — the NrOS use-case
(`benches/vspace.rs:176-481`, ops at `483-526`).

TPU-first: pointer-chasing radix levels are hostile to fixed-shape compiled
replay, and the workload's semantics are a partial map vpage→pframe over a
bounded VA window. State is the flattened last-level table
`frames: int32[n_pages]` (0 = unmapped; the radix walk is an addressing
scheme, not semantics). Multi-page maps become one masked iota scatter —
the fixed-shape equivalent of the reference's per-page PT walk loop.

Write opcodes:
  VS_MAP=1       args (vpage, pframe, npages) → maps vpage+i ↦ pframe+i for
                 i < min(npages, max_span); resp = #pages newly mapped.
  VS_UNMAP=2     args (vpage, npages) → resp = #pages that were mapped.
Read opcodes:
  VS_IDENTIFY=1  args (vpage) → pframe, or -1 if unmapped
                 (`benches/vspace.rs` Identify).
  VS_RESOLVED=2  args (vpage, npages) → count of mapped pages in range.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from node_replication_tpu.ops.encoding import Dispatch

VS_MAP = 1
VS_UNMAP = 2
VS_IDENTIFY = 1
VS_RESOLVED = 2

UNMAPPED = 0


def make_vspace(n_pages: int, max_span: int = 16) -> Dispatch:
    """`max_span` bounds pages touched per op (fixed scatter width)."""

    def make_state():
        return {"frames": jnp.zeros((n_pages,), jnp.int32)}

    def _span_idx(vpage, npages):
        lanes = jnp.arange(max_span, dtype=jnp.int32)
        n = jnp.clip(npages, 0, max_span)
        # out-of-range lanes scatter to n_pages → dropped
        idx = jnp.where(
            (lanes < n) & (vpage + lanes < n_pages),
            (vpage + lanes) % n_pages,
            n_pages,
        )
        return idx, lanes, n

    def vmap_(state, args):
        vpage, pframe, npages = args[0], args[1], args[2]
        idx, lanes, n = _span_idx(vpage, npages)
        frames = state["frames"]
        newly = jnp.sum(
            jnp.where(idx < n_pages, frames.at[idx].get(mode="fill",
                                                        fill_value=1)
                      == UNMAPPED, False)
        )
        # pframe 0 is reserved (means unmapped); map to pframe+1 offset is
        # the caller's concern — we store pframe+lanes as given.
        frames = frames.at[idx].set(pframe + lanes, mode="drop")
        return {"frames": frames}, newly.astype(jnp.int32)

    def unmap(state, args):
        vpage, npages = args[0], args[1]
        idx, lanes, n = _span_idx(vpage, npages)
        frames = state["frames"]
        was = jnp.sum(
            jnp.where(idx < n_pages, frames.at[idx].get(mode="fill",
                                                        fill_value=UNMAPPED)
                      != UNMAPPED, False)
        )
        frames = frames.at[idx].set(UNMAPPED, mode="drop")
        return {"frames": frames}, was.astype(jnp.int32)

    def identify(state, args):
        vpage = args[0] % n_pages
        f = state["frames"][vpage]
        return jnp.where(f == UNMAPPED, jnp.int32(-1), f)

    def resolved(state, args):
        vpage, npages = args[0], args[1]
        idx, lanes, n = _span_idx(vpage, npages)
        return jnp.sum(
            jnp.where(idx < n_pages,
                      state["frames"].at[idx].get(
                          mode="fill", fill_value=UNMAPPED) != UNMAPPED,
                      False)
        ).astype(jnp.int32)

    def window_plan(state, opcodes, args):
        """Combined replay for the flat vspace (see `Dispatch.window_apply`).

        Map/Unmap are last-writer-wins *per page*; what makes vspace more
        than the hashmap is that one op touches a whole span. Each op is
        expanded into `max_span` page-EVENTS (lanes beyond the op's span
        park at a sentinel page), after which the window is exactly the
        hashmap algebra over W x max_span events:

        1. group events by page with one stable sort,
        2. presence-before(event) = same-page predecessor's stored value
           != UNMAPPED, else the replica's initial frame,
        3. per-op response = lane-sum of its events' presence bits
           (newly-mapped for MAP, was-mapped for UNMAP),
        4. final frames = per-page last event's stored value.

        Bit-identical to folding vmap_/unmap over the window in order
        (tests/test_window.py::TestVSpaceWindowApply). Replaces the
        sequential replay loop (`nr/src/log.rs:473-524`) with O(E log E)
        parallel work, E = W * max_span.

        Packaged as plan/merge (r5): the sorts and scans — the whole
        O(E log E) half — depend on the window plus the representative
        state, so under the fused step they run ONCE per window; the
        vmapped `window_merge` is the honest per-replica dense blend
        (one [P]-wide select against the replica's own frames). This is
        what makes long-log vspace throughput scale linearly with R
        instead of paying R sorts (the r4 bottleneck).
        """
        W = opcodes.shape[0]
        S = max_span
        vpage, pframe = args[:, 0], args[:, 1]
        is_map = opcodes == VS_MAP
        is_un = opcodes == VS_UNMAP
        active = is_map | is_un
        # MAP's span rides args[2]; UNMAP's rides args[1] (its arg tuple
        # is (vpage, npages) — matching the sequential ops)
        npages = jnp.where(is_un, args[:, 1], args[:, 2])
        lanes = jnp.arange(S, dtype=jnp.int32)[None, :]
        n = jnp.clip(npages, 0, S)[:, None]
        raw = vpage[:, None] + lanes
        lane_ok = (lanes < n) & (raw < n_pages) & active[:, None]
        # mirror _span_idx exactly: negative vpage wraps through the mod
        page = jnp.where(lane_ok, raw % n_pages, n_pages)
        # MAP stores pframe+lane (which CAN be UNMAPPED=0 — a map to
        # frame 0 reads back as unmapped, as in the sequential op);
        # UNMAP stores 0
        stored = jnp.where(is_map[:, None], pframe[:, None] + lanes,
                           jnp.int32(0))
        E = W * S
        pe = page.reshape(E).astype(jnp.int64)
        se = stored.reshape(E)
        # stable sort by page: equal pages keep flattened (= window)
        # order; no composite sort key (int32 overflow under the
        # NR_TPU_NO_X64=1 opt-out, ADVICE r3)
        order = jnp.argsort(pe, stable=True)
        sp = pe[order]
        same_prev = jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), sp[1:] == sp[:-1]]
        )
        prev = jnp.concatenate([order[:1], order[:-1]])
        init_pres = (
            state["frames"].at[
                jnp.minimum(sp, n_pages - 1).astype(jnp.int32)
            ].get(mode="clip")
            != UNMAPPED
        )
        pres_before_s = jnp.where(
            same_prev, se[prev] != UNMAPPED, init_pres
        )
        pres_before = (
            jnp.zeros((E,), jnp.bool_).at[order].set(pres_before_s)
            .reshape(W, S)
        )
        newly = jnp.sum(lane_ok & is_map[:, None] & ~pres_before, axis=1)
        was = jnp.sum(lane_ok & is_un[:, None] & pres_before, axis=1)
        resps = jnp.where(
            is_map, newly, jnp.where(is_un, was, 0)
        ).astype(jnp.int32)
        # last event per page wins (sentinel slot absorbs parked lanes)
        last = (
            jnp.full((n_pages + 1,), -1, jnp.int64)
            .at[pe].max(jnp.arange(E, dtype=jnp.int64))[:n_pages]
        )
        li = jnp.clip(last, 0).astype(jnp.int32)
        return {"touched": last >= 0, "value": se[li], "resps": resps}

    def window_merge(state, plan):
        return {
            "frames": jnp.where(plan["touched"], plan["value"],
                                state["frames"])
        }, plan["resps"]

    def window_apply(state, opcodes, args):
        # arbitrary-state form (catch-up, divergent fleets): the plan's
        # presence-before/response half reads THIS state, so the
        # composition is the full sequential-fold semantics per replica
        return window_merge(state, window_plan(state, opcodes, args))

    ok_combined = max_span <= n_pages

    # fused pallas combiner round (ops/pallas_vspace.py): the span
    # kernel with the ring-window append fused in — one launch per
    # serve batch. The factory rejects configs the span kernel's
    # row-overlap rule excludes; wrappers then fall back to the chain.
    def fused_factory(spec, interpret=None):
        from node_replication_tpu.ops.pallas_vspace import (
            FusedVspaceEngine,
        )

        return FusedVspaceEngine(n_pages, max_span, spec,
                                 interpret=interpret)

    return Dispatch(
        name=f"vspace{n_pages}",
        make_state=make_state,
        write_ops=(vmap_, unmap),
        read_ops=(identify, resolved),
        arg_width=3,
        # degenerate config guard: with max_span > n_pages one op's
        # mod-wrapped span can revisit a page, and the event expansion
        # (one predecessor per event) diverges from the sequential fold
        # -> fall back to the scan engine there
        window_apply=window_apply if ok_combined else None,
        window_plan=window_plan if ok_combined else None,
        window_merge=window_merge if ok_combined else None,
        window_canonical=ok_combined,
        fused_factory=fused_factory,
    )


# --------------------------------------------------------------- radix
# The 4-level variant (`benches/vspace.rs:176-481` models the full x86-64
# PML4→PDPT→PD→PT walk). Radix indices: 9 bits per level over the bounded
# window, so level l covers 512^l pages per entry.

VSR_MAP = 1
VSR_MAP_DEVICE = 2
VSR_UNMAP = 3
VSR_UNMAP_TABLE = 4

VSR_IDENTIFY = 1
VSR_RESOLVED = 2
VSR_TABLES = 3

# pt entry encoding: 0 = not present; else (pframe + 1) | device << 30
_DEV_BIT = jnp.int32(1 << 30)
_FRAME_MASK = jnp.int32((1 << 30) - 1)


def make_vspace_radix(n_pages: int, max_span: int = 16) -> Dispatch:
    """4-level page-table vspace with per-level present tables.

    Semantics note (the r2 question "is flat-last-level complete?"): over
    a BOUNDED VA window with on-demand intermediate tables, the pointer
    radix of the reference (`benches/vspace.rs:176-481`) is an addressing
    scheme for a 256 TiB sparse space — a fixed-shape device model does
    not need pointers to cover the same op semantics. What the radix adds
    *observably* is (a) table-granular operations and (b) table
    allocation accounting. This model keeps the flat PT as the last level
    and maintains real PML4/PDPT/PD present tables on every walk:

    Write opcodes:
      VSR_MAP=1          (vpage, pframe, npages) → maps vpage+i ↦
                         pframe+i, allocating the walk's tables;
                         resp = #pages newly mapped.
      VSR_MAP_DEVICE=2   same, but entries carry the device attribute
                         (uncacheable MMIO — the reference's MapDevice);
                         resp = #pages newly mapped.
      VSR_UNMAP=3        (vpage, npages) → clears PT entries (tables
                         stay allocated, as on a real unmap);
                         resp = #pages that were mapped.
      VSR_UNMAP_TABLE=4  (vpage) → tears down the PD-level table covering
                         vpage: its 512-page region unmaps at once and
                         the table deallocates (the radix-only O(table)
                         region operation); resp = #pages that were
                         mapped in the region.
    Read opcodes:
      VSR_IDENTIFY=1     (vpage) → (pframe+1) | device<<30 after a FULL
                         walk (every level present), or -1.
      VSR_RESOLVED=2     (vpage, npages) → #fully-walked mapped pages.
      VSR_TABLES=3       () → #allocated PD tables (the memory-accounting
                         observable the radix exists for).
    """
    l2 = max(1, -(-n_pages // 512))
    l3 = max(1, -(-n_pages // (512 ** 2)))
    l4 = max(1, -(-n_pages // (512 ** 3)))

    def make_state():
        return {
            "pt": jnp.zeros((n_pages,), jnp.int32),
            "pd": jnp.zeros((l2,), jnp.bool_),
            "pdpt": jnp.zeros((l3,), jnp.bool_),
            "pml4": jnp.zeros((l4,), jnp.bool_),
        }

    def _span_idx(vpage, npages):
        lanes = jnp.arange(max_span, dtype=jnp.int32)
        n = jnp.clip(npages, 0, max_span)
        idx = jnp.where(
            (lanes < n) & (vpage + lanes < n_pages),
            (vpage + lanes) % n_pages,
            n_pages,
        )
        return idx, lanes

    def _walk_present(state, pages):
        """Full 4-level walk for page indices (n_pages → False)."""
        safe = jnp.minimum(pages, n_pages - 1)
        ok = pages < n_pages
        return (
            ok
            & state["pml4"].at[safe >> 27].get(mode="clip")
            & state["pdpt"].at[safe >> 18].get(mode="clip")
            & state["pd"].at[safe >> 9].get(mode="clip")
            & (state["pt"].at[safe].get(mode="fill", fill_value=0) != 0)
        )

    # level-entry scatter width: a max_span run crosses at most this many
    # PD entries (and always at most 2 at the higher levels)
    _pd_w = -(-max_span // 512) + 1

    def _mark_levels(state, vpage, npages):
        n = jnp.clip(npages, 0, max_span)
        # an empty map (npages <= 0) must not allocate tables — the
        # VSR_TABLES accounting would report phantom allocations
        live = n > 0
        last = jnp.maximum(vpage + n - 1, vpage)
        pd_lanes = (vpage >> 9) + jnp.arange(_pd_w, dtype=jnp.int32)
        pd_idx = jnp.where(
            live & (pd_lanes <= (last >> 9)) & (pd_lanes < l2),
            pd_lanes, l2,
        )
        hi = jnp.stack([vpage >> 18, last >> 18])
        hi_idx = jnp.where(live & (hi < l3), hi, l3)
        top = jnp.stack([vpage >> 27, last >> 27])
        top_idx = jnp.where(live & (top < l4), top, l4)
        return {
            "pt": state["pt"],
            "pd": state["pd"].at[pd_idx].set(True, mode="drop"),
            "pdpt": state["pdpt"].at[hi_idx].set(True, mode="drop"),
            "pml4": state["pml4"].at[top_idx].set(True, mode="drop"),
        }

    def _map_common(state, args, device):
        vpage, pframe, npages = args[0], args[1], args[2]
        vpage = vpage % n_pages
        idx, lanes = _span_idx(vpage, npages)
        newly = jnp.sum(
            jnp.where(idx < n_pages, ~_walk_present(state, idx), False)
        )
        entry = ((pframe + lanes + 1) & _FRAME_MASK) | (
            _DEV_BIT if device else 0
        )
        state = _mark_levels(state, vpage, npages)
        state = dict(state, pt=state["pt"].at[idx].set(entry, mode="drop"))
        return state, newly.astype(jnp.int32)

    def map_(state, args):
        return _map_common(state, args, device=False)

    def map_device(state, args):
        return _map_common(state, args, device=True)

    def unmap(state, args):
        vpage, npages = args[0] % n_pages, args[1]
        idx, _ = _span_idx(vpage, npages)
        was = jnp.sum(
            jnp.where(idx < n_pages, _walk_present(state, idx), False)
        )
        return dict(
            state, pt=state["pt"].at[idx].set(0, mode="drop")
        ), was.astype(jnp.int32)

    def unmap_table(state, args):
        # tear down the PD table covering vpage: count mapped pages in
        # its 512-page region, zero the region's PT slice, clear the
        # PD entry (fixed-shape: one 512-lane masked scatter)
        vpage = args[0] % n_pages
        pd_i = vpage >> 9
        base = pd_i << 9
        lanes = base + jnp.arange(512, dtype=jnp.int32)
        idx = jnp.where(lanes < n_pages, lanes, n_pages)
        was = jnp.sum(
            jnp.where(idx < n_pages, _walk_present(state, idx), False)
        )
        return dict(
            state,
            pt=state["pt"].at[idx].set(0, mode="drop"),
            pd=state["pd"].at[pd_i].set(False),
        ), was.astype(jnp.int32)

    def identify(state, args):
        v = args[0] % n_pages
        ok = _walk_present(state, jnp.asarray(v))
        return jnp.where(ok, state["pt"][v], jnp.int32(-1))

    def resolved(state, args):
        vpage, npages = args[0] % n_pages, args[1]
        idx, _ = _span_idx(vpage, npages)
        return jnp.sum(
            jnp.where(idx < n_pages, _walk_present(state, idx), False)
        ).astype(jnp.int32)

    def tables(state, args):
        return jnp.sum(state["pd"]).astype(jnp.int32)

    def window_plan(state, opcodes, args):
        """Combined replay for the 4-level radix vspace.

        The hardest window algebra in the repo (alongside memfs): four
        COUPLED per-entry histories instead of one —

          pt[p]    written by map/unmap lanes, bulk-cleared by
                   UNMAP_TABLE over a 512-page region;
          pd[r]    set by maps' table walks, cleared by UNMAP_TABLE;
          pdpt/pml4  MONOTONE — only ever set (teardown stops at PD),
                   so presence-before(t) is just first-set-time < t.

        Decomposition into parallel passes, all bit-identical to the
        sequential fold (tests/test_window.py::TestVSpaceRadixWindowApply):

        1. *page stream* (W x max_span events): stable sort by page gives
           every lane its same-page predecessor/successor write.
        2. *region stream*: one stable sort by PD entry over interleaved
           per-op [lane queries | table query | pd-mark updates | clear
           update] columns (queries sort before their own op's updates, so
           every query sees strictly-pre-op state). Three segmented
           associative scans yield last-pd-update (pd presence-before),
           last-clear-before (pt epoch start), and first-clear-after
           (epoch assignment for teardown responses).
        3. pt-before(lane) joins 1+2: the predecessor write wins iff it
           postdates the last region clear, else cleared-0, else the
           replica's initial pt.
        4. UNMAP_TABLE's response — #fully-walked pages in its region,
           pre-op — uses epoch algebra: each clear t on region r counts
           (a) in-epoch pages whose LAST write before t is nonzero
           (epoch-last markers scatter-added into a bucket keyed by their
           first-clear-after = t) plus, when t is r's first clear, (b)
           initially-mapped pages not yet written (per-region initial
           census minus first-epoch touched pages), gated by the
           region-uniform pml4/pdpt/pd walk bits.
        5. final state: per-page last write vs last region clear; per-PD
           last update; pdpt/pml4 = init | ever-set.

        Packaged as plan/merge (r5): every sort/scan/scatter — the whole
        O(E log E) half above — runs ONCE per window on the
        representative replica; the vmapped `window_merge` does the
        honest per-replica dense work (pt/pd/pdpt/pml4 blends against
        the replica's own tables). r4 relied on XLA hoisting the sorts
        out of the replica vmap, which it does not do for
        gather/scatter-carrying pipelines — the split makes long-log
        throughput scale with R.
        """
        W = opcodes.shape[0]
        S = max_span
        t_op = jnp.arange(W, dtype=jnp.int32)
        vpage = args[:, 0] % n_pages
        pframe = args[:, 1]
        is_map = (opcodes == VSR_MAP) | (opcodes == VSR_MAP_DEVICE)
        is_dev = opcodes == VSR_MAP_DEVICE
        is_un = opcodes == VSR_UNMAP
        is_tbl = opcodes == VSR_UNMAP_TABLE
        # MAP spans ride args[2]; UNMAP's span rides args[1] (its arg
        # tuple is (vpage, npages) — matching the sequential ops)
        npages = jnp.where(is_un, args[:, 1], args[:, 2])
        lanes = jnp.arange(S, dtype=jnp.int32)[None, :]
        nn = jnp.clip(npages, 0, S)
        raw = vpage[:, None] + lanes
        lane_ok = (lanes < nn[:, None]) & (raw < n_pages) & (
            is_map | is_un
        )[:, None]
        page = jnp.where(lane_ok, raw, n_pages)  # vpage>=0: mod is a no-op
        stored = jnp.where(
            is_map[:, None],
            ((pframe[:, None] + lanes + 1) & _FRAME_MASK)
            | jnp.where(is_dev[:, None], _DEV_BIT, 0),
            jnp.int32(0),
        )
        safe = jnp.minimum(page, n_pages - 1)

        # ---- level marks (mirrors _mark_levels' exact conditions) ----
        live = is_map & (nn > 0)
        last_pg = jnp.maximum(vpage + nn - 1, vpage)
        pd_lanes = (vpage >> 9)[:, None] + jnp.arange(
            _pd_w, dtype=jnp.int32
        )[None, :]
        pd_mark = jnp.where(
            live[:, None]
            & (pd_lanes <= (last_pg >> 9)[:, None])
            & (pd_lanes < l2),
            pd_lanes, l2,
        )
        hi = jnp.stack([vpage >> 18, last_pg >> 18], axis=1)
        hi_mark = jnp.where(live[:, None] & (hi < l3), hi, l3)
        top = jnp.stack([vpage >> 27, last_pg >> 27], axis=1)
        top_mark = jnp.where(live[:, None] & (top < l4), top, l4)

        # ---- monotone levels: first-set time per entry ---------------
        tt2 = jnp.broadcast_to(t_op[:, None], (W, 2))
        fs_pdpt = jnp.full((l3 + 1,), W, jnp.int32).at[hi_mark].min(tt2)[:l3]
        fs_pml4 = jnp.full((l4 + 1,), W, jnp.int32).at[top_mark].min(
            tt2
        )[:l4]
        init_pdpt, init_pml4 = state["pdpt"], state["pml4"]
        init_pd, init_pt = state["pd"], state["pt"]

        def pdpt_before(entry, t):
            return init_pdpt[entry] | (fs_pdpt[entry] < t)

        def pml4_before(entry, t):
            return init_pml4[entry] | (fs_pml4[entry] < t)

        # ---- page stream: same-page predecessor / successor ----------
        E = W * S
        pe = page.reshape(E).astype(jnp.int64)
        se = stored.reshape(E)
        te = jnp.broadcast_to(t_op[:, None], (W, S)).reshape(E)
        ordp = jnp.argsort(pe, stable=True)
        spg = pe[ordp]
        samep = spg[1:] == spg[:-1]
        prevp = jnp.concatenate([ordp[:1], ordp[:-1]])
        nextp = jnp.concatenate([ordp[1:], ordp[-1:]])
        f_ = jnp.zeros((1,), jnp.bool_)
        unsort = lambda v, fill: jnp.full((E,), fill, v.dtype).at[ordp].set(v)
        has_pred = unsort(jnp.concatenate([f_, samep]), False).reshape(W, S)
        t_pred = unsort(te[prevp], 0).reshape(W, S)
        v_pred = unsort(se[prevp], 0).reshape(W, S)
        has_succ = unsort(jnp.concatenate([samep, f_]), False).reshape(W, S)
        t_succ = unsort(te[nextp], 0).reshape(W, S)

        # ---- region stream: [lane q | tbl q | pd marks | clear] ------
        reg_tbl = vpage >> 9
        tbl_q = jnp.where(is_tbl, reg_tbl, l2)[:, None]
        clear_u = tbl_q
        lane_q = jnp.where(lane_ok, page >> 9, l2)
        Wc = S + 1 + _pd_w + 1
        keys = jnp.concatenate([lane_q, tbl_q, pd_mark, clear_u], axis=1)
        one_r = lambda v: jnp.broadcast_to(
            jnp.asarray(v, jnp.bool_)[None, :], (W, Wc)
        )
        col_upd = one_r([False] * (S + 1) + [True] * (_pd_w + 1))
        col_val = one_r([False] * (S + 1) + [True] * _pd_w + [False])
        is_upd = col_upd & (keys < l2)
        is_clear = is_upd & ~col_val
        N = W * Wc
        kz = keys.reshape(N).astype(jnp.int64)
        tz = jnp.broadcast_to(t_op[:, None], (W, Wc)).reshape(N)
        uz = is_upd.reshape(N)
        vz = col_val.reshape(N)
        cz = is_clear.reshape(N)
        ordr = jnp.argsort(kz, stable=True)
        skr = kz[ordr]
        segf = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), skr[1:] != skr[:-1]]
        )

        def seg_last(a, b):
            ta, va, ha, fa = a
            tb, vb, hb, fb = b
            tk = jnp.where(fb, tb, jnp.where(hb, tb, ta))
            vk = jnp.where(fb, vb, jnp.where(hb, vb, va))
            hk = jnp.where(fb, hb, ha | hb)
            return tk, vk, hk, fa | fb

        # last pd update (presence value) before each position
        pt_, pv_, ph_, _ = jax.lax.associative_scan(
            seg_last, (tz[ordr], vz[ordr], uz[ordr], segf)
        )
        # last CLEAR before each position (pt epoch start)
        ct_, _, ch_, _ = jax.lax.associative_scan(
            seg_last, (tz[ordr], vz[ordr], cz[ordr], segf)
        )
        # first clear AFTER each position: same scan over the reversal
        segb = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), skr[::-1][1:] != skr[::-1][:-1]]
        )
        nt_, _, nh_, _ = jax.lax.associative_scan(
            seg_last, (tz[ordr][::-1], vz[ordr][::-1], cz[ordr][::-1], segb)
        )
        nt_, nh_ = nt_[::-1], nh_[::-1]
        unsR = lambda v, fill: jnp.full((N,), fill, v.dtype).at[ordr].set(v)
        pd_has = unsR(ph_, False).reshape(W, Wc)
        pd_val = unsR(pv_, False).reshape(W, Wc)
        lcb = unsR(jnp.where(ch_, ct_, -1), -1).reshape(W, Wc)
        nca = unsR(jnp.where(nh_, nt_, W), W).reshape(W, Wc)
        init_pd_q = init_pd.at[
            jnp.minimum(keys, l2 - 1).astype(jnp.int32)
        ].get(mode="clip")
        pd_b = jnp.where(pd_has, pd_val, init_pd_q)

        # ---- per-lane walk-present just before its op ----------------
        lane_pd_b = pd_b[:, :S]
        lane_lcb = lcb[:, :S]
        lane_nc = nca[:, :S]
        pt_b = jnp.where(
            has_pred & (t_pred > lane_lcb),
            v_pred,
            jnp.where(lane_lcb >= 0, 0, init_pt[safe]),
        )
        t_b = t_op[:, None]
        walk_b = (
            pml4_before(safe >> 27, t_b)
            & pdpt_before(safe >> 18, t_b)
            & lane_pd_b
            & (pt_b != 0)
        )
        resp_map = jnp.sum(lane_ok & ~walk_b, axis=1)
        resp_un = jnp.sum(lane_ok & walk_b, axis=1)

        # ---- UNMAP_TABLE responses: epoch algebra --------------------
        # a lane write is LAST-IN-ITS-EPOCH iff its next same-page write
        # falls beyond the epoch's terminating clear
        epoch_last = lane_ok & (~has_succ | (t_succ > lane_nc))
        feeds = epoch_last & (lane_nc < W)
        ncf = jnp.clip(lane_nc, 0, W).reshape(E)
        a_bucket = jnp.zeros((W + 1,), jnp.int32).at[ncf].add(
            (feeds & (stored != 0)).reshape(E)
        )
        init_nz_lane = init_pt[safe] != 0
        b_sub = jnp.zeros((W + 1,), jnp.int32).at[ncf].add(
            (feeds & (lane_lcb == -1) & init_nz_lane).reshape(E)
        )
        # per-region census of initially-mapped pages
        padded = jnp.zeros((l2 * 512,), jnp.bool_).at[: n_pages].set(
            init_pt != 0
        )
        init_nz_count = jnp.sum(
            padded.reshape(l2, 512), axis=1
        ).astype(jnp.int32)
        c0 = lcb[:, S]
        levels_tbl = (
            pml4_before(jnp.minimum(reg_tbl >> 18, l4 - 1), t_op)
            & pdpt_before(jnp.minimum(reg_tbl >> 9, l3 - 1), t_op)
            & pd_b[:, S]
        )
        count_pt = a_bucket[t_op] + jnp.where(
            c0 == -1, init_nz_count[reg_tbl] - b_sub[t_op], 0
        )
        resp_tbl = jnp.where(levels_tbl, count_pt, 0)

        resps = jnp.where(
            is_map, resp_map,
            jnp.where(is_un, resp_un, jnp.where(is_tbl, resp_tbl, 0)),
        ).astype(jnp.int32)

        # ---- final state ---------------------------------------------
        lastw = (
            jnp.full((n_pages + 1,), -1, jnp.int64)
            .at[pe].max(jnp.arange(E, dtype=jnp.int64))[:n_pages]
        )
        li = jnp.clip(lastw, 0).astype(jnp.int32)
        lw_t, lw_v = te[li], se[li]
        lc_reg = (
            jnp.full((l2 + 1,), -1, jnp.int32)
            .at[clear_u[:, 0]].max(jnp.where(is_tbl, t_op, -1))[:l2]
        )
        lc_pg = lc_reg[jnp.arange(n_pages) >> 9]
        upd_keys = jnp.concatenate([pd_mark, clear_u], axis=1)
        Uc = _pd_w + 1
        upd_vals = jnp.broadcast_to(
            jnp.asarray([True] * _pd_w + [False])[None, :], (W, Uc)
        )
        U = W * Uc
        lastu = (
            jnp.full((l2 + 1,), -1, jnp.int64)
            .at[upd_keys.reshape(U).astype(jnp.int64)]
            .max(jnp.arange(U, dtype=jnp.int64))[:l2]
        )
        return {
            # per-page: last in-window write (and whether it postdates
            # the last region clear), plus the clear mask itself
            "pt_wins": (lastw >= 0) & (lw_t > lc_pg),
            "pt_value": lw_v,
            "pt_cleared": lc_pg >= 0,
            # per-PD-entry: last update (mark=True / clear=False)
            "pd_touched": lastu >= 0,
            "pd_value": upd_vals.reshape(U)[
                jnp.clip(lastu, 0).astype(jnp.int32)
            ],
            # monotone levels: entries first set inside the window
            "pdpt_set": fs_pdpt < W,
            "pml4_set": fs_pml4 < W,
            "resps": resps,
        }

    def window_merge(state, plan):
        pt = jnp.where(
            plan["pt_wins"], plan["pt_value"],
            jnp.where(plan["pt_cleared"], 0, state["pt"]),
        )
        pd = jnp.where(plan["pd_touched"], plan["pd_value"], state["pd"])
        return {
            "pt": pt, "pd": pd,
            "pdpt": state["pdpt"] | plan["pdpt_set"],
            "pml4": state["pml4"] | plan["pml4_set"],
        }, plan["resps"]

    def window_apply(state, opcodes, args):
        # arbitrary-state form (catch-up, divergent fleets): the plan's
        # walk-before/epoch half reads THIS state, so the composition is
        # the full sequential-fold semantics per replica
        return window_merge(state, window_plan(state, opcodes, args))

    return Dispatch(
        name=f"vspace_radix{n_pages}",
        make_state=make_state,
        write_ops=(map_, map_device, unmap, unmap_table),
        read_ops=(identify, resolved, tables),
        arg_width=3,
        window_apply=window_apply,
        window_plan=window_plan,
        window_merge=window_merge,
        window_canonical=True,
    )
