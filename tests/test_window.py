"""Combined window replay (`Dispatch.window_apply`) vs the generic scan.

The combined path replaces the W-long sequential replay scan with one
parallel reduction (sort + predecessor lookup + dense merge). These tests
pin BIT-identical behavior against folding `apply_write` in order — state,
write responses, and read responses — across adversarial windows: duplicate
keys, PUT/REMOVE interleavings, NOOP padding, unknown opcodes, ring wrap,
and multi-step drives.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from node_replication_tpu import LogSpec, log_init, make_step
from node_replication_tpu.core.replica import replicate_state
from node_replication_tpu.models import (
    HM_GET,
    HM_PUT,
    HM_REMOVE,
    make_hashmap,
)
from node_replication_tpu.ops.encoding import apply_write


def fold_reference(d, state, opcodes, args):
    """Host-side ground truth: apply_write folded in window order."""
    resps = []
    for i in range(len(opcodes)):
        state, r = apply_write(d, state, opcodes[i], args[i])
        resps.append(int(r))
    return state, resps


class TestWindowApplySingle:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_matches_sequential_fold(self, seed):
        K, W = 13, 64
        d = make_hashmap(K)
        rng = np.random.default_rng(seed)
        # adversarial mix: heavy key collisions, NOOPs, unknown opcode 7
        opcodes = jnp.asarray(
            rng.choice([0, HM_PUT, HM_REMOVE, 7], size=W,
                       p=[0.15, 0.45, 0.3, 0.1]),
            jnp.int32,
        )
        args = jnp.asarray(
            np.stack(
                [rng.integers(0, K, W), rng.integers(1, 100, W),
                 np.zeros(W)], axis=1
            ),
            jnp.int32,
        )
        state0 = d.init_state()
        # start from a non-trivial state: some keys pre-present
        state0["present"] = state0["present"].at[::3].set(True)
        state0["values"] = state0["values"].at[::3].set(5)
        ref_state, ref_resps = fold_reference(d, state0, opcodes, args)
        got_state, got_resps = d.window_apply(state0, opcodes, args)
        np.testing.assert_array_equal(
            np.asarray(got_state["values"]), np.asarray(ref_state["values"])
        )
        np.testing.assert_array_equal(
            np.asarray(got_state["present"]),
            np.asarray(ref_state["present"]),
        )
        assert [int(x) for x in got_resps] == ref_resps

    def test_remove_answers_predecessor_not_initial(self):
        # REMOVE after an in-window PUT answers 1 even if the key started
        # absent; a second REMOVE answers 0
        K = 8
        d = make_hashmap(K)
        opcodes = jnp.asarray(
            [HM_PUT, HM_REMOVE, HM_REMOVE, HM_PUT], jnp.int32
        )
        args = jnp.asarray(
            [[3, 9, 0], [3, 0, 0], [3, 0, 0], [3, 11, 0]], jnp.int32
        )
        state, resps = d.window_apply(d.init_state(), opcodes, args)
        assert [int(x) for x in resps] == [0, 1, 0, 0]
        assert int(state["values"][3]) == 11
        assert bool(state["present"][3])

    def test_all_noop_window_is_identity(self):
        K = 4
        d = make_hashmap(K)
        state0 = d.init_state()
        state0["values"] = state0["values"].at[1].set(7)
        state0["present"] = state0["present"].at[1].set(True)
        state, resps = d.window_apply(
            state0, jnp.zeros((8,), jnp.int32), jnp.zeros((8, 3), jnp.int32)
        )
        np.testing.assert_array_equal(
            np.asarray(state["values"]), np.asarray(state0["values"])
        )
        assert not np.any(np.asarray(resps))


def gather_plan_reference(n_keys):
    """The hashmap plan as it was built by gathering for every slot: a
    scatter-max of window positions into an int64[K+1] table, then two
    K-index gathers. Kept as a reference for the scatter-built plan."""

    def plan(state, opcodes, args):
        W = opcodes.shape[0]
        k = args[:, 0] % n_keys
        v = args[:, 1]
        is_put = opcodes == HM_PUT
        is_rem = opcodes == HM_REMOVE
        key_eff = jnp.where(is_put | is_rem, k, n_keys).astype(jnp.int64)
        idx = jnp.arange(W, dtype=jnp.int64)
        order = jnp.argsort(key_eff, stable=True)
        sk = key_eff[order]
        same_prev = jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), sk[1:] == sk[:-1]]
        )
        prev = jnp.concatenate([order[:1], order[:-1]])
        init_present = state["present"].at[
            sk.astype(jnp.int32)
        ].get(mode="clip")
        pres_before = jnp.where(same_prev, is_put[prev], init_present)
        resp_sorted = jnp.where(
            is_rem[order], pres_before.astype(jnp.int32), jnp.int32(0)
        )
        resps = jnp.zeros((W,), jnp.int32).at[order].set(resp_sorted)
        last = (
            jnp.full((n_keys + 1,), -1, jnp.int64)
            .at[key_eff].max(idx)[:n_keys]
        )
        li = jnp.clip(last, 0).astype(jnp.int32)
        last_is_put = is_put[li]
        return {
            "touched": last >= 0,
            "value": jnp.where(last_is_put, v[li], 0),
            "present": last_is_put,
            "resps": resps,
        }

    return plan


def sparse_window(rng, K, W):
    """A window over a keyspace far larger than itself: keys drawn from a
    small pool (duplicates, REMOVEs as keys' last writers), some at
    or past K (they wrap by the modulus), NOOPs and unknown opcode 7."""
    pool = rng.integers(0, 3 * K, max(W // 4, 1))
    opcodes = rng.choice([0, HM_PUT, HM_REMOVE, 7], size=W,
                         p=[0.15, 0.45, 0.3, 0.1])
    keys = rng.choice(pool, size=W)
    vals = rng.integers(1, 1 << 30, W)
    # the last writer of key 3 (present at start) is a REMOVE
    opcodes[-2:] = [HM_PUT, HM_REMOVE]
    keys[-2:] = K + 3
    return (jnp.asarray(opcodes, jnp.int32),
            jnp.asarray(np.stack([keys, vals, np.zeros(W)], axis=1),
                        jnp.int32))


def assert_plans_agree(got, ref):
    touched = np.asarray(ref["touched"])
    np.testing.assert_array_equal(np.asarray(got["touched"]), touched)
    np.testing.assert_array_equal(np.asarray(got["resps"]),
                                  np.asarray(ref["resps"]))
    for name in ("value", "present"):
        np.testing.assert_array_equal(np.asarray(got[name])[touched],
                                      np.asarray(ref[name])[touched])


def assert_states_equal(got, ref):
    for name in ("values", "present"):
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(ref[name]))


class TestHashmapPlanSparse:
    """The plan at K >> W, where it must scatter the window's last
    writes rather than gather for every slot, against the sequential fold
    and the gather-built plan."""

    K = 1 << 16

    def _state0(self, d):
        state0 = d.init_state()
        state0["present"] = state0["present"].at[::3].set(True)
        state0["values"] = state0["values"].at[::3].set(5)
        return state0

    def _check(self, d, state0, opcodes, args):
        ref_state, ref_resps = fold_jit(d, state0, opcodes, args)
        old_plan = gather_plan_reference(self.K)(state0, opcodes, args)
        plan = d.window_plan(state0, opcodes, args)
        assert_plans_agree(plan, old_plan)
        for got_state, got_resps in (
            d.window_apply(state0, opcodes, args),
            d.window_merge(state0, plan),
            d.window_merge(state0, old_plan),
        ):
            assert_states_equal(got_state, ref_state)
            assert [int(x) for x in got_resps] == ref_resps

    @pytest.mark.parametrize("W", [64, 256])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_matches_fold_and_gather_plan(self, seed, W):
        d = make_hashmap(self.K)
        opcodes, args = sparse_window(np.random.default_rng(seed), self.K, W)
        self._check(d, self._state0(d), opcodes, args)

    @pytest.mark.parametrize("W", [64, 256])
    def test_all_noop_window(self, W):
        d = make_hashmap(self.K)
        opcodes = jnp.asarray(np.tile([0, 7], W // 2), jnp.int32)
        args = jnp.ones((W, 3), jnp.int32)
        self._check(d, self._state0(d), opcodes, args)
        assert not np.any(np.asarray(
            d.window_plan(self._state0(d), opcodes, args)["touched"]))

    def test_plan_has_no_slot_sized_gather(self):
        # structural guard: every gather yields fewer than K elements and
        # every scatter takes at most W updates; the gather-built plan
        # shows what the guard catches
        K, W = self.K, 256
        d = make_hashmap(K)
        opcodes, args = sparse_window(np.random.default_rng(0), K, W)
        state0 = d.init_state()

        def violations(plan_fn):
            found = []
            stack = [jax.make_jaxpr(plan_fn)(state0, opcodes, args).jaxpr]
            while stack:
                jaxpr = stack.pop()
                for eqn in jaxpr.eqns:
                    name = eqn.primitive.name
                    if (name == "gather"
                            and eqn.outvars[0].aval.size >= K):
                        found.append(name)
                    if (name.startswith("scatter")
                            and eqn.invars[2].aval.size > W):
                        found.append(name)
                    for p in eqn.params.values():
                        for sub in p if isinstance(p, tuple) else (p,):
                            sub = getattr(sub, "jaxpr", sub)
                            if hasattr(sub, "eqns"):
                                stack.append(sub)
            return found

        assert violations(d.window_plan) == []
        assert violations(gather_plan_reference(K)) == ["gather", "gather"]


class TestSortedSetWindowApply:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_matches_sequential_fold(self, seed):
        from node_replication_tpu.models import make_sortedset

        K, W = 11, 48
        d = make_sortedset(K)
        rng = np.random.default_rng(seed)
        opcodes = jnp.asarray(
            rng.choice([0, 1, 2, 9], size=W, p=[0.1, 0.45, 0.35, 0.1]),
            jnp.int32,
        )
        args = jnp.asarray(
            np.stack([rng.integers(0, K, W), np.zeros(W), np.zeros(W)],
                     axis=1),
            jnp.int32,
        )
        state0 = d.init_state()
        state0["present"] = state0["present"].at[::2].set(True)
        ref_state, ref_resps = fold_reference(d, state0, opcodes, args)
        got_state, got_resps = d.window_apply(state0, opcodes, args)
        np.testing.assert_array_equal(
            np.asarray(got_state["present"]),
            np.asarray(ref_state["present"]),
        )
        assert [int(x) for x in got_resps] == ref_resps


class TestMemfsWindowApply:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_matches_sequential_fold(self, seed):
        # the hardest combined model: coupled per-file truncate and
        # per-cell write histories plus running-size responses
        from node_replication_tpu.models import make_memfs

        F, B, W = 4, 6, 96
        d = make_memfs(F, B)
        rng = np.random.default_rng(seed)
        opcodes = jnp.asarray(
            rng.choice([0, 1, 2, 3, 9], size=W,
                       p=[0.08, 0.42, 0.18, 0.27, 0.05]),
            jnp.int32,
        )
        # include out-of-range fds/blocks to pin the clip/-1 semantics
        args = jnp.asarray(
            np.stack(
                [rng.integers(-1, F + 1, W), rng.integers(-1, B + 1, W),
                 rng.integers(1, 100, W)], axis=1
            ),
            jnp.int32,
        )
        state0 = d.init_state()
        state0["data"] = state0["data"].at[1, :3].set(
            jnp.asarray([11, 12, 13], jnp.int32)
        )
        state0["size"] = state0["size"].at[1].set(3)
        ref_state, ref_resps = fold_reference(d, state0, opcodes, args)
        got_state, got_resps = d.window_apply(state0, opcodes, args)
        np.testing.assert_array_equal(
            np.asarray(got_state["data"]), np.asarray(ref_state["data"])
        )
        np.testing.assert_array_equal(
            np.asarray(got_state["size"]), np.asarray(ref_state["size"])
        )
        assert [int(x) for x in got_resps] == ref_resps

    def test_truncate_then_write_then_logged_read(self):
        from node_replication_tpu.models import make_memfs

        d = make_memfs(2, 4)
        state0 = d.init_state()
        state0["data"] = state0["data"].at[0, 0].set(7)
        state0["size"] = state0["size"].at[0].set(1)
        ops = [
            (3, 0, 0, 0),   # read 7 (initial)
            (2, 0, 0, 0),   # truncate → old size 1
            (3, 0, 0, 0),   # read 0 (truncated)
            (1, 0, 2, 55),  # write block 2 → size 3
            (3, 0, 2, 0),   # read 55 (in-window write)
            (3, 0, 0, 0),   # read 0 (still truncated, no later write)
        ]
        opcodes = jnp.asarray([o[0] for o in ops], jnp.int32)
        args = jnp.asarray([list(o[1:]) for o in ops], jnp.int32)
        state, resps = d.window_apply(state0, opcodes, args)
        assert [int(x) for x in resps] == [7, 1, 0, 3, 55, 0]
        assert int(state["size"][0]) == 3
        assert int(state["data"][0, 0]) == 0
        assert int(state["data"][0, 2]) == 55


def fold_jit(d, state, opcodes, args):
    """fold_reference with a jitted per-op step (radix ops are slow
    eagerly: 512-lane scatters per unmap_table)."""
    step = jax.jit(lambda s, o, a: apply_write(d, s, o, a))
    resps = []
    for i in range(len(opcodes)):
        state, r = step(state, opcodes[i], args[i])
        resps.append(int(r))
    return state, resps


class TestVSpaceWindowApply:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_flat_matches_sequential_fold(self, seed):
        from node_replication_tpu.models import make_vspace

        K, S, W = 37, 5, 64
        d = make_vspace(K, max_span=S)
        rng = np.random.default_rng(seed)
        opcodes = jnp.asarray(
            rng.choice([0, 1, 2, 9], size=W, p=[0.1, 0.5, 0.3, 0.1]),
            jnp.int32,
        )
        # adversarial args: negative/overflowing vpages (the sequential
        # op wraps them through the mod), pframe=0 maps that read back
        # as unmapped, zero/negative/oversized spans
        args = jnp.asarray(
            np.stack(
                [rng.integers(-3, K + 3, W), rng.integers(0, 50, W),
                 rng.integers(-1, S + 3, W)], axis=1
            ),
            jnp.int32,
        )
        st0 = d.init_state()
        st0["frames"] = st0["frames"].at[::4].set(7)
        ref_state, ref_resps = fold_jit(d, st0, opcodes, args)
        got_state, got_resps = d.window_apply(st0, opcodes, args)
        np.testing.assert_array_equal(
            np.asarray(got_state["frames"]), np.asarray(ref_state["frames"])
        )
        assert [int(x) for x in got_resps] == ref_resps

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_radix_matches_sequential_fold(self, seed):
        # the deepest window algebra: coupled pt/pd/pdpt/pml4 histories,
        # region teardown epochs, span-crossing table marks
        from node_replication_tpu.models import make_vspace_radix

        P, S, W = 1500, 20, 96
        d = make_vspace_radix(P, max_span=S)
        rng = np.random.default_rng(seed)
        opcodes = jnp.asarray(
            rng.choice([0, 1, 2, 3, 4, 9], size=W,
                       p=[0.06, 0.3, 0.14, 0.25, 0.2, 0.05]),
            jnp.int32,
        )
        args = jnp.asarray(
            np.stack(
                [rng.integers(0, 2 * P, W), rng.integers(-2, 60, W),
                 rng.integers(-1, S + 3, W)], axis=1
            ),
            jnp.int32,
        )
        st0 = d.init_state()
        # torn init: full walk in region 0; pt WITHOUT pd in region 2
        # (walk fails); pd with no pt in region 1
        st0["pt"] = st0["pt"].at[10:40].set(5).at[1100:1130].set(9)
        st0["pd"] = st0["pd"].at[0].set(True).at[1].set(True)
        st0["pdpt"] = st0["pdpt"].at[0].set(True)
        st0["pml4"] = st0["pml4"].at[0].set(True)
        ref_state, ref_resps = fold_jit(d, st0, opcodes, args)
        got_state, got_resps = d.window_apply(st0, opcodes, args)
        for k in ("pt", "pd", "pdpt", "pml4"):
            np.testing.assert_array_equal(
                np.asarray(got_state[k]), np.asarray(ref_state[k]), k
            )
        assert [int(x) for x in got_resps] == ref_resps

    def test_radix_teardown_epochs(self):
        # directed epoch algebra: two teardowns of one region — the
        # first counts initially-mapped + in-window pages, the second
        # counts only pages re-mapped after the first
        from node_replication_tpu.models import make_vspace_radix

        P = 1100  # 3 pd regions (last one partial: 1100-1024=76 pages)
        d = make_vspace_radix(P, max_span=8)
        st0 = d.init_state()
        # 6 initially fully-walked pages in region 1 (512..1023)
        st0["pt"] = st0["pt"].at[600:606].set(3)
        st0["pd"] = st0["pd"].at[1].set(True)
        st0["pdpt"] = st0["pdpt"].at[0].set(True)
        st0["pml4"] = st0["pml4"].at[0].set(True)
        ops = [
            (1, 520, 9, 4),   # map 4 fresh pages in region 1 → newly 4
            (1, 602, 9, 4),   # overwrite 4 of the init pages → newly 0
            (4, 700, 0, 0),   # teardown region 1 → 6 init + 4 new = 10
            (3, 520, 4, 0),   # unmap after teardown → was 0
            (1, 640, 1, 2),   # re-map 2 pages (re-allocates the table)
            (4, 712, 0, 0),   # second teardown → only the 2 re-mapped
            (4, 712, 0, 0),   # third, empty epoch → 0
            (2, 76, 5, 3),    # MapDevice in region 0: pdpt/pml4 already
                              # set, pd fresh → newly 3
            (4, 100, 0, 0),   # teardown region 0 → 3
        ]
        opcodes = jnp.asarray([o[0] for o in ops], jnp.int32)
        args = jnp.asarray([list(o[1:]) for o in ops], jnp.int32)
        ref_state, ref_resps = fold_jit(d, st0, opcodes, args)
        got_state, got_resps = d.window_apply(st0, opcodes, args)
        assert ref_resps == [4, 0, 10, 0, 2, 2, 0, 3, 3]  # pin intent
        assert [int(x) for x in got_resps] == ref_resps
        for k in ("pt", "pd", "pdpt", "pml4"):
            np.testing.assert_array_equal(
                np.asarray(got_state[k]), np.asarray(ref_state[k]), k
            )

    def test_radix_step_combined_matches_scan(self):
        # whole-step integration: combined engine vs scan engine over a
        # multi-step drive with ring wrap
        from node_replication_tpu.models import make_vspace_radix

        R, Bw, Br, P, STEPS = 3, 4, 2, 1100, 5
        d = make_vspace_radix(P, max_span=8)
        spec = LogSpec(capacity=2 * R * Bw, n_replicas=R, arg_width=3,
                       gc_slack=R * Bw // 2)
        rng = np.random.default_rng(7)
        s_comb = make_step(d, spec, Bw, Br, jit=True, donate=False,
                           combined=True)
        s_scan = make_step(d, spec, Bw, Br, jit=True, donate=False,
                           combined=False)
        log_c, st_c = log_init(spec), replicate_state(d.init_state(), R)
        log_s, st_s = log_init(spec), replicate_state(d.init_state(), R)
        for _ in range(STEPS):
            wr_opc = jnp.asarray(
                rng.choice([0, 1, 2, 3, 4], size=(R, Bw)), jnp.int32
            )
            wr_args = jnp.asarray(
                np.stack([rng.integers(0, P, (R, Bw)),
                          rng.integers(0, 60, (R, Bw)),
                          rng.integers(0, 9, (R, Bw))], axis=-1),
                jnp.int32,
            )
            rd_opc = jnp.asarray(
                rng.choice([1, 2, 3], size=(R, Br)), jnp.int32
            )
            rd_args = jnp.asarray(
                np.stack([rng.integers(0, P, (R, Br)),
                          rng.integers(1, 9, (R, Br)),
                          np.zeros((R, Br))], axis=-1),
                jnp.int32,
            )
            log_c, st_c, wr_c, rd_c = s_comb(
                log_c, st_c, wr_opc, wr_args, rd_opc, rd_args
            )
            log_s, st_s, wr_s, rd_s = s_scan(
                log_s, st_s, wr_opc, wr_args, rd_opc, rd_args
            )
            np.testing.assert_array_equal(np.asarray(wr_c), np.asarray(wr_s))
            np.testing.assert_array_equal(np.asarray(rd_c), np.asarray(rd_s))
        for a, b in zip(jax.tree.leaves(st_c), jax.tree.leaves(st_s)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestStackWindowApply:
    """Order-dependent models via clamped-walk + slot-LWW algebra
    (ops/windowkit.py; VERDICT r3 #2 — parenthesis matching made LWW)."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_matches_sequential_fold(self, seed):
        from node_replication_tpu.models import make_stack

        C, W = 7, 64
        d = make_stack(C)
        rng = np.random.default_rng(seed)
        # heavy churn around both clamps: overfull pushes, empty pops
        opcodes = jnp.asarray(
            rng.choice([0, 1, 2, 9], size=W, p=[0.08, 0.44, 0.4, 0.08]),
            jnp.int32,
        )
        args = jnp.asarray(
            np.stack([rng.integers(1, 100, W), np.zeros(W),
                      np.zeros(W)], axis=1),
            jnp.int32,
        )
        st0 = d.init_state()
        st0["buf"] = st0["buf"].at[:3].set(
            jnp.asarray([11, 12, 13], jnp.int32)
        )
        st0["top"] = jnp.int32(3)
        ref_state, ref_resps = fold_jit(d, st0, opcodes, args)
        got_state, got_resps = d.window_apply(st0, opcodes, args)
        for k in ("buf", "top"):
            np.testing.assert_array_equal(
                np.asarray(got_state[k]), np.asarray(ref_state[k]), k
            )
        assert [int(x) for x in got_resps] == ref_resps

    def test_pop_sees_in_window_push_not_initial(self):
        from node_replication_tpu.models import make_stack

        d = make_stack(4)
        st0 = d.init_state()
        st0["buf"] = st0["buf"].at[0].set(99)
        st0["top"] = jnp.int32(1)
        ops = [
            (2, 0),    # pop initial 99
            (2, 0),    # pop empty -> -1
            (1, 7),    # push 7 (slot 0)
            (1, 8),    # push 8 (slot 1)
            (2, 0),    # pop 8
            (1, 9),    # push 9 (slot 1 again)
            (2, 0),    # pop 9 (not 8: slot 1 was overwritten)
            (2, 0),    # pop 7
        ]
        opcodes = jnp.asarray([o[0] for o in ops], jnp.int32)
        args = jnp.zeros((len(ops), 3), jnp.int32).at[:, 0].set(
            jnp.asarray([o[1] for o in ops], jnp.int32)
        )
        state, resps = d.window_apply(st0, opcodes, args)
        assert [int(x) for x in resps] == [99, -1, 1, 2, 8, 2, 9, 7]
        assert int(state["top"]) == 0

    def test_step_combined_matches_scan(self):
        from node_replication_tpu.models import make_stack

        R, Bw, Br, C, STEPS = 3, 4, 2, 9, 6
        d = make_stack(C)
        spec = LogSpec(capacity=2 * R * Bw, n_replicas=R, arg_width=3,
                       gc_slack=R * Bw // 2)
        rng = np.random.default_rng(2)
        s_comb = make_step(d, spec, Bw, Br, jit=True, donate=False,
                           combined=True)
        s_scan = make_step(d, spec, Bw, Br, jit=True, donate=False,
                           combined=False)
        log_c, st_c = log_init(spec), replicate_state(d.init_state(), R)
        log_s, st_s = log_init(spec), replicate_state(d.init_state(), R)
        for _ in range(STEPS):
            wr_opc = jnp.asarray(
                rng.choice([0, 1, 2], size=(R, Bw)), jnp.int32
            )
            wr_args = jnp.asarray(
                rng.integers(1, 50, size=(R, Bw, 3)), jnp.int32
            )
            rd_opc = jnp.asarray(
                rng.choice([1, 2], size=(R, Br)), jnp.int32
            )
            rd_args = jnp.zeros((R, Br, 3), jnp.int32)
            log_c, st_c, wr_c, rd_c = s_comb(
                log_c, st_c, wr_opc, wr_args, rd_opc, rd_args
            )
            log_s, st_s, wr_s, rd_s = s_scan(
                log_s, st_s, wr_opc, wr_args, rd_opc, rd_args
            )
            np.testing.assert_array_equal(np.asarray(wr_c), np.asarray(wr_s))
            np.testing.assert_array_equal(np.asarray(rd_c), np.asarray(rd_s))
        for a, b in zip(jax.tree.leaves(st_c), jax.tree.leaves(st_s)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestQueueWindowApply:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_matches_sequential_fold(self, seed):
        from node_replication_tpu.models import make_queue

        C, W = 7, 64
        d = make_queue(C)
        rng = np.random.default_rng(seed)
        opcodes = jnp.asarray(
            rng.choice([0, 1, 2, 9], size=W, p=[0.08, 0.44, 0.4, 0.08]),
            jnp.int32,
        )
        args = jnp.asarray(
            np.stack([rng.integers(1, 100, W), np.zeros(W),
                      np.zeros(W)], axis=1),
            jnp.int32,
        )
        st0 = d.init_state()
        st0["buf"] = st0["buf"].at[:3].set(
            jnp.asarray([11, 12, 13], jnp.int32)
        )
        st0["tail"] = jnp.int32(3)
        ref_state, ref_resps = fold_jit(d, st0, opcodes, args)
        got_state, got_resps = d.window_apply(st0, opcodes, args)
        for k in ("buf", "head", "tail"):
            np.testing.assert_array_equal(
                np.asarray(got_state[k]), np.asarray(ref_state[k]), k
            )
        assert [int(x) for x in got_resps] == ref_resps

    def test_ring_wrap_with_offset_cursors(self):
        # cursors far from zero, capacity-3 ring churned through many
        # generations: per-slot LWW must hand each dequeue its own
        # generation's value
        from node_replication_tpu.models import make_queue

        d = make_queue(3)
        st0 = d.init_state()
        st0["buf"] = jnp.asarray([5, 6, 7], jnp.int32)
        st0["head"] = jnp.int32(4)
        st0["tail"] = jnp.int32(6)
        rng = np.random.default_rng(9)
        W = 96
        opcodes = jnp.asarray(rng.choice([1, 2], size=W), jnp.int32)
        args = jnp.zeros((W, 3), jnp.int32).at[:, 0].set(
            jnp.asarray(rng.integers(1, 100, W), jnp.int32)
        )
        ref_state, ref_resps = fold_jit(d, st0, opcodes, args)
        got_state, got_resps = d.window_apply(st0, opcodes, args)
        for k in ("buf", "head", "tail"):
            np.testing.assert_array_equal(
                np.asarray(got_state[k]), np.asarray(ref_state[k]), k
            )
        assert [int(x) for x in got_resps] == ref_resps


class TestCombinedCatchup:
    """`log_catchup_all`: combined replay on DIVERGENT cursors — the
    catch-up-at-hot-loop-speed contract (`nr/src/log.rs:473-524`).
    Bit-identical to `log_exec_all` per round: states, resps, cursors."""

    def _drive(self, d, make_state, seed, model_args):
        from node_replication_tpu.core.log import (
            log_append,
            log_catchup_all,
            log_exec_all,
        )

        R, N, W = 4, 96, 32
        spec = LogSpec(capacity=256, n_replicas=R, arg_width=3,
                       gc_slack=16)
        rng = np.random.default_rng(seed)
        opcodes = jnp.asarray(
            rng.choice([0, 1, 2, 9], size=N, p=[0.1, 0.5, 0.3, 0.1]),
            jnp.int32,
        )
        args = jnp.asarray(
            np.stack([rng.integers(0, model_args, N),
                      rng.integers(1, 100, N),
                      np.zeros(N)], axis=1),
            jnp.int32,
        )
        outs = {}
        for eng in (log_exec_all, log_catchup_all):
            log = log_init(spec)
            log = log_append(spec, log, opcodes, args, N)
            states = replicate_state(d.init_state(), R)
            lim_rounds = []
            # limited rounds diverge the fleet (replica 2 fully dormant),
            # then unlimited rounds converge it — GC stalls in between.
            # Both engines follow the same lattice on the LIMITED rounds
            # (per-replica truncation admits no shared plan); on the
            # unlimited rounds the union-plan engine may advance lagging
            # replicas further per round, so there we compare the
            # position->response mapping and the converged state instead
            # of per-round cursors.
            limit_rounds = [jnp.asarray([10, 35, 0, N], jnp.int64),
                            jnp.asarray([60, 35, 0, N], jnp.int64)]
            for lim in limit_rounds:
                log, states, resps = eng(spec, d, log, states, W, lim)
                lim_rounds.append((np.asarray(resps),
                                   np.asarray(log.ltails),
                                   int(log.head), int(log.ctail)))
            # consumed-response map: replica r's answer for position p
            pos_resps = {r: {} for r in range(R)}
            rounds = 0
            while int(np.min(np.asarray(log.ltails))) < N:
                before = np.asarray(log.ltails).copy()
                log, states, resps = eng(spec, d, log, states, W)
                after = np.asarray(log.ltails)
                resps = np.asarray(resps)
                for r in range(R):
                    for i in range(int(after[r] - before[r])):
                        pos_resps[r][int(before[r]) + i] = int(
                            resps[r, i]
                        )
                rounds += 1
                assert rounds < 64, f"{eng.__name__} failed to converge"
            outs[eng.__name__] = (
                jax.tree.map(np.asarray, states),
                lim_rounds,
                pos_resps,
                np.asarray(log.ltails),
                int(log.head),
            )
        st_scan, lim_scan, pr_scan, lt_scan, h_scan = outs["log_exec_all"]
        st_comb, lim_comb, pr_comb, lt_comb, h_comb = outs[
            "log_catchup_all"
        ]
        for (ra, la, ha, ca), (rb, lb, hb, cb) in zip(lim_scan, lim_comb):
            np.testing.assert_array_equal(ra, rb)
            np.testing.assert_array_equal(la, lb)
            assert ha == hb and ca == cb
        # each replica must answer the SAME positions with the SAME
        # responses, regardless of how rounds chunked the catch-up
        assert pr_scan == pr_comb
        np.testing.assert_array_equal(lt_scan, lt_comb)
        assert h_scan == h_comb
        for a, b in zip(jax.tree.leaves(st_scan), jax.tree.leaves(st_comb)):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("seed", [0, 1])
    def test_hashmap_divergent_cursors(self, seed):
        self._drive(make_hashmap(13), None, seed, 13)

    @pytest.mark.parametrize("seed", [0, 1])
    def test_stack_divergent_cursors(self, seed):
        # order-dependent model on divergent state: exactly the case the
        # plan/merge fast path excludes and window_apply must cover
        from node_replication_tpu.models import make_stack

        self._drive(make_stack(9), None, seed, 50)

    @pytest.mark.parametrize("seed", [0])
    def test_queue_divergent_cursors(self, seed):
        from node_replication_tpu.models import make_queue

        self._drive(make_queue(9), None, seed, 50)

    @pytest.mark.parametrize("mk,nargs,N,snaps", [
        ("stack", 50, 64, (16, 25, 48)),
        ("queue", 50, 64, (16, 25, 48)),
        ("hashmap", 30, 64, (16, 25, 48)),
        ("sortedset", 30, 64, (16, 25, 48)),
        # fast tier-1 equivalents of the heavy models: the same
        # prefix-absorption contract over a SHORTER schedule (cost is
        # per-op apply + the plan compile, not model capacity — the
        # full-length runs below are ~15-50s each on this machine)
        ("vspace", 40, 20, (5, 9, 15)),
        ("vspace_radix", 40, 12, (3, 6, 9)),
        ("memfs", 5, 20, (5, 9, 15)),
        # full-length heavy schedules, slow-marked to fit the tier-1
        # verify budget; still green in the full suite
        pytest.param("vspace", 40, 64, (16, 25, 48),
                     marks=pytest.mark.slow),
        pytest.param("vspace_radix", 40, 64, (16, 25, 48),
                     marks=pytest.mark.slow),
        pytest.param("memfs", 5, 64, (16, 25, 48),
                     marks=pytest.mark.slow),
    ])
    def test_plan_is_prefix_absorbing(self, mk, nargs, N, snaps):
        # the union-window catch-up contract: merging plan(state(m),
        # [m, end)) into a replica ALREADY at p in [m, end] must land
        # exactly on state(end) — cursors in the plan must be absolute,
        # not deltas (the r5 queue bug: head/tail double-counted)
        from node_replication_tpu import models as M

        d = {
            "stack": lambda: M.make_stack(9),
            "queue": lambda: M.make_queue(9),
            "vspace": lambda: M.make_vspace(600, max_span=8),
            "vspace_radix": lambda: M.make_vspace_radix(1100, max_span=8),
            "hashmap": lambda: M.make_hashmap(30),
            "sortedset": lambda: M.make_sortedset(30),
            "memfs": lambda: M.make_memfs(5, 64),
        }[mk]()
        rng = np.random.default_rng(1)
        n_ops = {"stack": 2, "queue": 2, "vspace": 2, "vspace_radix": 4,
                 "hashmap": 2, "sortedset": 2, "memfs": 3}[mk]
        opcodes = jnp.asarray(
            rng.integers(0, n_ops + 1, N), jnp.int32
        )
        args = jnp.asarray(
            np.stack([rng.integers(0, nargs, N),
                      rng.integers(1, 60, N),
                      rng.integers(0, 9, N)], axis=1),
            jnp.int32,
        )
        lo, _mid, hi = snaps
        snap = {}
        st = d.init_state()
        for i in range(N):
            if i in snaps:
                snap[i] = st
            st, _ = apply_write(d, st, opcodes[i], args[i])
        snap[N] = st
        plan = d.window_plan(snap[lo], opcodes[lo:hi], args[lo:hi])
        for p in snaps:  # window start, mid-window, window end
            merged, _ = d.window_merge(snap[p], plan)
            for a, b in zip(jax.tree.leaves(merged),
                            jax.tree.leaves(snap[hi])):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    f"{mk}: merge from p={p} not canonical",
                )

    def test_off_trajectory_flag_uses_window_apply(self):
        # hand-built fleets whose states are NOT folds of the shared log
        # must opt out of the union-plan tier; on_trajectory=False takes
        # the per-replica window_apply tier, correct for arbitrary state
        from node_replication_tpu.core.log import (
            log_append,
            log_catchup_all,
        )

        K, R, N, W = 16, 2, 8, 8
        d = make_hashmap(K)
        spec = LogSpec(capacity=64, n_replicas=R, arg_width=3,
                       gc_slack=8)
        log = log_init(spec)
        opc = jnp.full((N,), HM_PUT, jnp.int32)
        ag = jnp.zeros((N, 3), jnp.int32).at[:, 0].set(
            jnp.arange(N, dtype=jnp.int32)
        ).at[:, 1].set(100)
        log = log_append(spec, log, opc, ag, N)
        # off-trajectory: replica 1 starts with a key the log never wrote
        states = replicate_state(d.init_state(), R)
        states = dict(states)
        states["values"] = states["values"].at[1, 15].set(999)
        states["present"] = states["present"].at[1, 15].set(True)
        log2, st2, _ = log_catchup_all(
            spec, d, log, states, W, on_trajectory=False
        )
        # replica 1 keeps its private key (untouched by the window) and
        # still applies the log's writes — the per-replica fold semantics
        assert int(st2["values"][1, 15]) == 999
        assert bool(st2["present"][1, 15])
        assert int(st2["values"][0, 15]) == 0
        for r in range(R):
            for k in range(N):
                assert int(st2["values"][r, k]) == 100
        assert (np.asarray(log2.ltails) == N).all()

    def test_union_tier_requires_canonical_opt_in(self):
        # ADVICE r5 / ISSUE 2: presence of window_plan/window_merge only
        # claims the lock-step contract; the union-window catch-up tier
        # needs the EXPLICIT `window_canonical=True` opt-in (all bundled
        # models set it) or an explicit union=True force from an
        # engine='combined' caller. Tier routing is observed through the
        # log.engine.* dispatch counters; results stay bit-equal either
        # way (hashmap satisfies both contracts).
        import dataclasses

        from node_replication_tpu.core.log import (
            log_append,
            log_catchup_all,
        )
        from node_replication_tpu.obs.metrics import get_registry

        K, R, N, W = 16, 2, 8, 8
        d = make_hashmap(K)
        assert d.window_canonical
        d_weak = dataclasses.replace(d, window_canonical=False)
        spec = LogSpec(capacity=64, n_replicas=R, arg_width=3,
                       gc_slack=8)
        opc = jnp.full((N,), HM_PUT, jnp.int32)
        ag = jnp.zeros((N, 3), jnp.int32).at[:, 0].set(
            jnp.arange(N, dtype=jnp.int32)
        ).at[:, 1].set(7)

        def fresh():
            log = log_append(spec, log_init(spec), opc, ag, N)
            return log, replicate_state(d.init_state(), R)

        reg = get_registry()
        was_enabled = reg.enabled
        reg.enable()
        c_union = reg.counter("log.engine.union_plan")
        c_window = reg.counter("log.engine.window_apply")
        try:
            # canonical model: auto routing takes the union tier
            log, states = fresh()
            u0, w0 = c_union.value, c_window.value
            _, st_canon, _ = log_catchup_all(spec, d, log, states, W)
            assert c_union.value == u0 + 1

            # weak model (lock-step-only contract): auto routing must
            # NOT take the stronger-contract engine
            log, states = fresh()
            u0, w0 = c_union.value, c_window.value
            _, st_weak, _ = log_catchup_all(spec, d_weak, log, states, W)
            assert c_union.value == u0
            assert c_window.value == w0 + 1

            # explicit force (the engine='combined' caller asserting
            # the contract) still routes the weak model through union
            log, states = fresh()
            u0 = c_union.value
            _, st_forced, _ = log_catchup_all(
                spec, d_weak, log, states, W, union=True
            )
            assert c_union.value == u0 + 1
        finally:
            if not was_enabled:
                reg.disable()
        for a, b, c in zip(jax.tree.leaves(st_canon),
                           jax.tree.leaves(st_weak),
                           jax.tree.leaves(st_forced)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    def test_auto_engine_honest_for_plan_only_weak_model(self):
        # a plan/merge-only model WITHOUT the canonical opt-in has no
        # combined tier that can actually run outside lock-step, so
        # engine='auto' must resolve (and report) 'scan', not a
        # 'combined' label whose every round falls through to the scan;
        # engine='combined' remains the explicit force
        import dataclasses

        from node_replication_tpu.core.replica import NodeReplicated

        d = make_hashmap(16)
        weak_plan_only = dataclasses.replace(
            d, window_apply=None, window_canonical=False
        )
        nr = NodeReplicated(weak_plan_only, n_replicas=2,
                            log_entries=64, gc_slack=8)
        assert nr.engine == "scan"
        forced = NodeReplicated(weak_plan_only, n_replicas=2,
                                log_entries=64, gc_slack=8,
                                engine="combined")
        assert forced.engine == "combined"
        for inst in (nr, forced):
            t = inst.register(0)
            for k in range(6):
                assert inst.execute_mut((HM_PUT, k, k + 50), t) == 0
            inst.sync()
            assert inst.replicas_equal()
            assert inst.execute((HM_GET, 3), t) == 53

    def test_node_replicated_engines_agree(self):
        # whole-wrapper drive: per-op API with interleaved sync on both
        # engines, responses and final states bit-equal
        from node_replication_tpu.core.replica import NodeReplicated
        from node_replication_tpu.models import HM_PUT, HM_REMOVE

        rng = np.random.default_rng(3)
        ops = [
            (int(rng.choice([HM_PUT, HM_REMOVE])),
             int(rng.integers(0, 16)), int(rng.integers(1, 50)))
            for _ in range(40)
        ]
        outs = {}
        for eng in ("scan", "combined"):
            nr = NodeReplicated(make_hashmap(16), n_replicas=2,
                                log_entries=512, gc_slack=16, engine=eng)
            assert nr.engine == eng
            t0, t1 = nr.register(0), nr.register(1)
            resps = []
            for i, op in enumerate(ops):
                resps.append(nr.execute_mut(op, t0 if i % 2 else t1))
            nr.sync()
            outs[eng] = (resps, jax.tree.map(np.asarray, nr.states))
        assert outs["scan"][0] == outs["combined"][0]
        for a, b in zip(jax.tree.leaves(outs["scan"][1]),
                        jax.tree.leaves(outs["combined"][1])):
            np.testing.assert_array_equal(a, b)


class TestMultilogCombined:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_partitioned_combined_matches_scan(self, seed):
        # per-log combined replay vs the per-log scan over the same
        # hash-routed stream: states, write resps, read resps, cursors
        from node_replication_tpu.harness.trait import MultiLogRunner
        from node_replication_tpu.models import (
            make_partitioned_sortedset,
            make_sortedset,
        )

        K, L, R, S, Bw = 32, 4, 3, 5, 6
        rng = np.random.default_rng(seed)
        wr_opc = rng.choice([0, 1, 2], size=(S, R, Bw)).astype(np.int32)
        wr_args = np.zeros((S, R, Bw, 3), np.int32)
        wr_args[..., 0] = rng.integers(0, K, (S, R, Bw))
        rd_opc = np.full((S, R, 2), 1, np.int32)
        rd_args = np.zeros((S, R, 2, 3), np.int32)
        rd_args[..., 0] = rng.integers(0, K, (S, R, 2))
        outs = {}
        for mode in (False, True):
            r = MultiLogRunner(
                make_sortedset(K), R, L, Bw, 2,
                partitioned=make_partitioned_sortedset(K, L),
                keyspace=K, combined=mode,
            )
            r.prepare(wr_opc, wr_args, rd_opc, rd_args)
            lasts = []
            for s in range(S):
                r.run_step(s)
                lasts.append(np.asarray(r._last))
            r.block()
            outs[mode] = (
                jax.tree.map(np.asarray, r.states),
                np.asarray(r.ml.ltails),
                lasts,
            )
        st_a, lt_a, rd_a = outs[False]
        st_b, lt_b, rd_b = outs[True]
        for x, y in zip(jax.tree.leaves(st_a), jax.tree.leaves(st_b)):
            np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(lt_a, lt_b)
        for x, y in zip(rd_a, rd_b):
            np.testing.assert_array_equal(x, y)


class TestCombinedStep:
    @pytest.mark.parametrize("seed", [0, 3])
    def test_step_bit_identical_to_scan_step(self, seed):
        R, Bw, Br, K, STEPS = 4, 3, 2, 11, 6
        d = make_hashmap(K)
        # capacity small enough that the ring wraps during the drive
        spec = LogSpec(capacity=2 * R * Bw, n_replicas=R, arg_width=3,
                       gc_slack=R * Bw // 2)
        rng = np.random.default_rng(seed)
        s_comb = make_step(d, spec, Bw, Br, jit=True, donate=False,
                           combined=True)
        s_scan = make_step(d, spec, Bw, Br, jit=True, donate=False,
                           combined=False)
        log_c, st_c = log_init(spec), replicate_state(d.init_state(), R)
        log_s, st_s = log_init(spec), replicate_state(d.init_state(), R)
        for _ in range(STEPS):
            wr_opc = jnp.asarray(
                rng.choice([0, HM_PUT, HM_REMOVE], size=(R, Bw)), jnp.int32
            )
            wr_args = jnp.asarray(
                rng.integers(0, K, size=(R, Bw, 3)), jnp.int32
            )
            rd_opc = jnp.full((R, Br), HM_GET, jnp.int32)
            rd_args = jnp.asarray(
                rng.integers(0, K, size=(R, Br, 3)), jnp.int32
            )
            log_c, st_c, wr_c, rd_c = s_comb(
                log_c, st_c, wr_opc, wr_args, rd_opc, rd_args
            )
            log_s, st_s, wr_s, rd_s = s_scan(
                log_s, st_s, wr_opc, wr_args, rd_opc, rd_args
            )
            np.testing.assert_array_equal(np.asarray(wr_c), np.asarray(wr_s))
            np.testing.assert_array_equal(np.asarray(rd_c), np.asarray(rd_s))
        for leaf_c, leaf_s in zip(jax.tree.leaves(st_c), jax.tree.leaves(st_s)):
            np.testing.assert_array_equal(np.asarray(leaf_c), np.asarray(leaf_s))
        for name in ("head", "tail", "ctail"):
            assert int(getattr(log_c, name)) == int(getattr(log_s, name))
        np.testing.assert_array_equal(
            np.asarray(log_c.ltails), np.asarray(log_s.ltails)
        )

    def test_auto_selects_combined_when_available(self):
        d = make_hashmap(8)
        assert d.window_apply is not None
        spec = LogSpec(capacity=64, n_replicas=2, arg_width=3, gc_slack=8)
        # default (None) → combined; explicit False → scan; both compile
        for combined in (None, False):
            step = make_step(d, spec, 1, 1, jit=True, donate=False,
                             combined=combined)
            log, st = log_init(spec), replicate_state(d.init_state(), 2)
            log, st, wr, rd = step(
                log, st,
                jnp.full((2, 1), HM_PUT, jnp.int32),
                jnp.zeros((2, 1, 3), jnp.int32).at[..., 0].set(3)
                .at[..., 1].set(9),
                jnp.full((2, 1), HM_GET, jnp.int32),
                jnp.zeros((2, 1, 3), jnp.int32).at[..., 0].set(3),
            )
            assert int(rd[0, 0]) == 9

    def test_combined_requires_window_apply(self):
        # synthetic is the remaining scan-only model (stack/queue gained
        # window_apply in r4)
        from node_replication_tpu.models import make_synthetic

        d = make_synthetic(16)
        assert d.window_apply is None
        spec = LogSpec(capacity=64, n_replicas=1, arg_width=3, gc_slack=8)
        with pytest.raises(ValueError):
            make_step(d, spec, 1, 0, combined=True)
