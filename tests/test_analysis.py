"""nrlint: one firing and one clean fixture per rule, plus the
suppression / severity / traced-closure machinery (ISSUE 2).

Fixtures are self-contained snippet files written to tmp_path; the
analyzer is purely syntactic, so the snippets never import anything at
test time — `import jax` lines exist only for the analyzer's name
resolution.
"""

import textwrap

from node_replication_tpu.analysis.lint import main, run_lint
from node_replication_tpu.analysis.rules import RULES


def lint_src(tmp_path, source, name="snippet.py", select=None):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    diags, errors = run_lint([str(p)], select=select)
    assert not errors, errors
    return diags


def firing(diags, rule_id):
    return [d for d in diags if d.rule_id == rule_id and not d.suppressed]


class TestHostSyncInJit:
    def test_np_asarray_in_jitted_fn_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                return np.asarray(x)
        """)
        assert len(firing(diags, "host-sync-in-jit")) == 1

    def test_item_via_call_graph_fires(self, tmp_path):
        # helper is traced only transitively (called from a jitted fn)
        diags = lint_src(tmp_path, """
            import jax

            def helper(x):
                return x.item()

            def g(x):
                return helper(x)

            f = jax.jit(g)
        """)
        assert len(firing(diags, "host-sync-in-jit")) == 1

    def test_host_code_and_jnp_clean(self, tmp_path):
        diags = lint_src(tmp_path, """
            import jax
            import jax.numpy as jnp
            import numpy as np

            @jax.jit
            def f(x):
                return jnp.asarray(x)

            def host_loop(x):
                return np.asarray(x).item()
        """)
        assert not firing(diags, "host-sync-in-jit")

    def test_tracer_isinstance_guard_is_exempt(self, tmp_path):
        # the project's explicit eager-only idiom (core/log.py)
        diags = lint_src(tmp_path, """
            import jax
            import numpy as np

            @jax.jit
            def f(log, x):
                if not isinstance(x, jax.core.Tracer):
                    return np.asarray(x)
                return x
        """)
        assert not firing(diags, "host-sync-in-jit")


class TestScalarCastInJit:
    def test_int_on_tracer_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                return int(x) + 1
        """)
        assert len(firing(diags, "scalar-cast-in-jit")) == 1

    def test_constant_cast_and_host_cast_clean(self, tmp_path):
        diags = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                return x + int(1)

            def host(x):
                return int(x)
        """)
        assert not firing(diags, "scalar-cast-in-jit")


class TestRawCheckifyCheck:
    def test_direct_checkify_check_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            from jax.experimental import checkify

            def f(x):
                checkify.check(x > 0, "bad")
                return x
        """)
        assert len(firing(diags, "raw-checkify-check")) == 1

    def test_project_wrapper_clean(self, tmp_path):
        diags = lint_src(tmp_path, """
            from node_replication_tpu.utils.checks import check

            def f(x):
                check(x > 0, "bad")
                return x
        """)
        assert not firing(diags, "raw-checkify-check")


class TestObsInTraced:
    def test_tracer_emit_in_jit_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import jax
            from node_replication_tpu.obs.recorder import get_tracer

            @jax.jit
            def f(x):
                get_tracer().emit("evt", n=1)
                return x
        """)
        assert len(firing(diags, "obs-in-traced")) >= 1

    def test_metric_handle_in_jit_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                _m_rounds.inc()
                return x
        """)
        assert len(firing(diags, "obs-in-traced")) == 1

    def test_host_loop_instrumentation_clean(self, tmp_path):
        diags = lint_src(tmp_path, """
            from node_replication_tpu.obs.recorder import get_tracer

            def exec_round(x):
                get_tracer().emit("exec-round")
                _m_rounds.inc()
                return x
        """)
        assert not firing(diags, "obs-in-traced")


class TestMutableCaptureInDispatch:
    def test_captured_global_mutation_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            from node_replication_tpu.ops.encoding import Dispatch

            CACHE = {}

            def bad_write(state, args):
                CACHE[0] = args
                return state, 0

            D = Dispatch(name="m", make_state=dict,
                         write_ops=(bad_write,), read_ops=())
        """)
        assert len(firing(diags, "mutable-capture-in-dispatch")) == 1

    def test_state_argument_mutation_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            from node_replication_tpu.ops.encoding import Dispatch

            def bad_write(state, args):
                state["x"] = 1
                return state, 0

            D = Dispatch(name="m", make_state=dict,
                         write_ops=(bad_write,), read_ops=())
        """)
        assert len(firing(diags, "mutable-capture-in-dispatch")) == 1

    def test_functional_updates_clean(self, tmp_path):
        # fresh local dict, a parameter REBOUND to a fresh copy, and
        # jnp .at[] functional updates are all pure idioms
        diags = lint_src(tmp_path, """
            from node_replication_tpu.ops.encoding import Dispatch

            def good_write(state, args):
                out = dict(state)
                out["x"] = 1
                return out, 0

            def good_rebind(state, args):
                state = dict(state)
                state["x"] = 1
                return state, 0

            def good_scatter(state, args):
                return state.at[0].add(1), 0

            D = Dispatch(name="m", make_state=dict,
                         write_ops=(good_write, good_rebind,
                                    good_scatter),
                         read_ops=())
        """)
        assert not firing(diags, "mutable-capture-in-dispatch")

    def test_unregistered_function_not_in_scope(self, tmp_path):
        # the rule only covers Dispatch-registered transitions
        diags = lint_src(tmp_path, """
            CACHE = {}

            def not_a_transition(state, args):
                CACHE[0] = args
                return state, 0
        """)
        assert not firing(diags, "mutable-capture-in-dispatch")


class TestWallClockTime:
    def test_time_time_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import time

            def stamp():
                return time.time()
        """)
        assert len(firing(diags, "wall-clock-time")) == 1

    def test_monotonic_clocks_clean(self, tmp_path):
        diags = lint_src(tmp_path, """
            import time

            def stamp():
                return time.monotonic(), time.perf_counter()
        """)
        assert not firing(diags, "wall-clock-time")


class TestRingIndexUnmasked:
    def test_unmasked_cursor_subscript_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            def gather(log, i):
                return log.opcodes[log.tail + i]
        """)
        assert len(firing(diags, "ring-index-unmasked")) == 1

    def test_masked_through_local_alias_clean(self, tmp_path):
        # one-level dataflow: the mask lives on the alias assignment
        diags = lint_src(tmp_path, """
            def gather(log, i, mask):
                idx = (log.tail + i) & mask
                return log.opcodes[idx]

            def gather_mod(log, i, capacity):
                return log.args[(log.head + i) % capacity]
        """)
        assert not firing(diags, "ring-index-unmasked")

    def test_non_ring_arrays_not_in_scope(self, tmp_path):
        diags = lint_src(tmp_path, """
            def model(buf, tail, i):
                return buf[tail + i]
        """)
        assert not firing(diags, "ring-index-unmasked")


class TestLockDiscipline:
    def test_write_outside_lock_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def inc(self):
                    with self._lock:
                        self.n += 1

                def clobber(self):
                    self.n = 5
        """)
        hits = firing(diags, "lock-discipline")
        assert len(hits) == 1 and "clobber" in hits[0].message

    def test_check_then_act_read_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def maybe_reset(self):
                    if self.n:
                        with self._lock:
                            self.n = 0
        """)
        hits = firing(diags, "lock-discipline")
        assert len(hits) == 1 and "read" in hits[0].message

    def test_locked_decorator_form_clean(self, tmp_path):
        # the core/replica.py `@_locked` whole-method region idiom
        diags = lint_src(tmp_path, """
            import threading

            def _locked(fn):
                def inner(self, *a, **kw):
                    with self._lock:
                        return fn(self, *a, **kw)
                return inner

            class C:
                def __init__(self):
                    self._lock = threading.RLock()
                    self.n = 0

                @_locked
                def inc(self):
                    self.n += 1

                @_locked
                def get(self):
                    return self.n
        """)
        assert not firing(diags, "lock-discipline")

    def test_lockless_class_not_in_scope(self, tmp_path):
        diags = lint_src(tmp_path, """
            class C:
                def __init__(self):
                    self.n = 0

                def inc(self):
                    self.n += 1
        """)
        assert not firing(diags, "lock-discipline")


class TestBlockingInHandler:
    def test_sleep_in_registered_callback_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import time

            def on_done(fut):
                time.sleep(0.1)

            def main(fut):
                fut.add_done_callback(on_done)
        """)
        hits = firing(diags, "blocking-in-handler")
        assert len(hits) == 1 and "time.sleep" in hits[0].message

    def test_future_wait_in_callback_kwarg_fires(self, tmp_path):
        # waiting on another future from the worker thread that must
        # resolve it is THE serve deadlock
        diags = lint_src(tmp_path, """
            def relay(fut):
                return other.result()

            def main(frontend, op):
                frontend.submit(op, callback=relay)
        """)
        hits = firing(diags, "blocking-in-handler")
        assert len(hits) == 1 and ".result()" in hits[0].message

    def test_inline_lambda_handler_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import time

            def main(fut):
                fut.add_done_callback(lambda f: time.sleep(1))
        """)
        assert len(firing(diags, "blocking-in-handler")) == 1

    def test_bound_method_handler_fires(self, tmp_path):
        # class-based consumers register bound methods; the method
        # body (and self.-helpers it calls) are handler scope too
        diags = lint_src(tmp_path, """
            import time

            class Consumer:
                def _backoff(self):
                    time.sleep(0.5)

                def _on_done(self, fut):
                    self._backoff()

                def main(self, fut):
                    fut.add_done_callback(self._on_done)
        """)
        assert len(firing(diags, "blocking-in-handler")) == 1

    def test_transitive_helper_fires(self, tmp_path):
        # the handler delegates its blocking to a same-module helper
        diags = lint_src(tmp_path, """
            import time

            def backoff():
                time.sleep(0.5)

            def on_done(fut):
                backoff()

            def main(fut):
                fut.add_done_callback(on_done)
        """)
        assert len(firing(diags, "blocking-in-handler")) == 1

    def test_own_future_result_is_sanctioned(self, tmp_path):
        # reading the handler's OWN (already-resolved) future is the
        # standard done-callback idiom — never a wait
        diags = lint_src(tmp_path, """
            OUT = []

            def on_done(fut):
                OUT.append(fut.result())

            def main(fut, frontend, op):
                fut.add_done_callback(on_done)
                fut.add_done_callback(lambda f: OUT.append(f.result()))
        """)
        assert not firing(diags, "blocking-in-handler")

    def test_non_serve_callback_api_out_of_scope(self, tmp_path):
        # callback= kwargs count only on serve-shaped calls
        # (submit/call): third-party APIs with a callback kwarg must
        # not trip an ERROR-severity serve rule
        diags = lint_src(tmp_path, """
            import time
            from scipy.optimize import minimize

            def progress(xk):
                time.sleep(0.1)

            def fit(f, x0):
                return minimize(f, x0, callback=progress)
        """)
        assert not firing(diags, "blocking-in-handler")

    def test_nonblocking_handler_and_free_sleep_clean(self, tmp_path):
        # hand-off handlers are the sanctioned shape; sleeps in
        # ordinary (non-handler) code — client backoff loops, benches
        # — are out of scope
        diags = lint_src(tmp_path, """
            import time

            RESULTS = []

            def on_done(fut):
                RESULTS.append(fut)

            def main(fut):
                fut.add_done_callback(on_done)

            def client_backoff():
                time.sleep(0.01)
        """)
        assert not firing(diags, "blocking-in-handler")


class TestTimeInTraced:
    def test_clock_read_in_jit_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import jax
            import time

            @jax.jit
            def f(x):
                t0 = time.perf_counter()
                return x
        """)
        assert len(firing(diags, "time-in-traced")) == 1

    def test_host_side_timing_clean(self, tmp_path):
        diags = lint_src(tmp_path, """
            import time

            def run_step(step, x):
                t0 = time.perf_counter()
                y = step(x)
                return y, time.perf_counter() - t0
        """)
        assert not firing(diags, "time-in-traced")


class TestSuppressionsAndSeverity:
    FIRING = """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x)
    """

    def test_inline_suppression(self, tmp_path):
        diags = lint_src(tmp_path, self.FIRING.replace(
            "np.asarray(x)",
            "np.asarray(x)  # nrlint: disable=host-sync-in-jit",
        ))
        assert not firing(diags, "host-sync-in-jit")
        assert any(
            d.rule_id == "host-sync-in-jit" and d.suppressed
            for d in diags
        )

    def test_line_above_suppression(self, tmp_path):
        diags = lint_src(tmp_path, self.FIRING.replace(
            "return np.asarray(x)",
            "# nrlint: disable=host-sync-in-jit — fixture\n"
            "            return np.asarray(x)",
        ))
        assert not firing(diags, "host-sync-in-jit")

    def test_suppression_is_rule_specific(self, tmp_path):
        # disabling an unrelated rule must not disarm the diagnostic
        diags = lint_src(tmp_path, self.FIRING.replace(
            "np.asarray(x)",
            "np.asarray(x)  # nrlint: disable=wall-clock-time",
        ))
        assert firing(diags, "host-sync-in-jit")

    def test_malformed_suppression_does_not_disarm(self, tmp_path):
        # a typo'd comment (missing '=') must neither suppress the
        # finding nor pass silently — both stay loud
        diags = lint_src(tmp_path, self.FIRING.replace(
            "np.asarray(x)",
            "np.asarray(x)  # nrlint: disable host-sync-in-jit",
        ))
        assert firing(diags, "host-sync-in-jit")
        assert firing(diags, "unknown-suppression")

    def test_unknown_rule_in_suppression_is_diagnosed(self, tmp_path):
        diags = lint_src(tmp_path, """
            x = 1  # nrlint: disable=not-a-rule
        """)
        assert len(firing(diags, "unknown-suppression")) == 1

    def test_min_severity_filtering(self, tmp_path, capsys):
        # wall-clock-time is a warning: fails the default gate, passes
        # --min-severity error
        p = tmp_path / "warn_only.py"
        p.write_text("import time\n\ndef f():\n    return time.time()\n")
        assert main([str(p)]) == 1
        assert main([str(p), "--min-severity", "error"]) == 0
        capsys.readouterr()

    def test_list_rules_covers_shipped_set(self, capsys):
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        assert len(RULES) >= 8
        for rid in RULES:
            assert rid in out


class TestSwallowedWorkerException:
    def test_thread_target_pass_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import threading

            def worker_loop(q):
                while True:
                    try:
                        q.work()
                    except Exception:
                        pass

            t = threading.Thread(target=worker_loop, args=(None,))
        """)
        assert len(firing(diags, "swallowed-worker-exception")) == 1

    def test_logging_only_still_fires(self, tmp_path):
        # a log line resolves no future and quarantines no replica
        diags = lint_src(tmp_path, """
            import logging
            import threading

            logger = logging.getLogger(__name__)

            def worker_loop(q):
                try:
                    q.work()
                except Exception:
                    logger.exception("batch failed")

            t = threading.Thread(target=worker_loop, args=(None,))
        """)
        assert len(firing(diags, "swallowed-worker-exception")) == 1

    def test_bound_method_target_and_helper_fire(self, tmp_path):
        # self._worker_loop target; the broad except hides in a
        # same-module helper the loop calls on the worker thread
        diags = lint_src(tmp_path, """
            import threading

            class Frontend:
                def start(self):
                    t = threading.Thread(target=self._worker_loop)
                    t.start()

                def _worker_loop(self):
                    while True:
                        self._run_batch()

                def _run_batch(self):
                    try:
                        self.nr.execute()
                    except Exception:
                        return None
        """)
        assert len(firing(diags, "swallowed-worker-exception")) == 1

    def test_reject_sink_reraise_and_health_clean(self, tmp_path):
        diags = lint_src(tmp_path, """
            import threading

            def rejects(batch, q):
                try:
                    q.work()
                except Exception as e:
                    for req in batch:
                        req.future._reject(e)

            def reraises(q):
                try:
                    q.work()
                except Exception:
                    raise

            def reports(q, health):
                try:
                    q.work()
                except Exception as e:
                    health.report_worker_exception(0, e)

            def typed_only(q):
                try:
                    q.work()
                except ValueError:
                    pass  # narrow except: not this rule's business

            for fn in (rejects, reraises, reports, typed_only):
                threading.Thread(target=fn).start()
        """)
        assert not firing(diags, "swallowed-worker-exception")

    def test_record_failure_helper_sanctioned(self, tmp_path):
        # the repl/ worker idiom (shipper ship loop, follower apply
        # loop): a broad except routing through `_record_failure` has
        # surfaced the failure — it stores the error for barrier/read
        # callers AND calls the health API
        diags = lint_src(tmp_path, """
            import threading

            class Shipper:
                def start(self):
                    threading.Thread(target=self._ship_loop).start()

                def _ship_loop(self):
                    try:
                        self._ship_once()
                    except Exception as e:
                        self._record_failure(e)

                def _record_failure(self, exc):
                    self._error = exc
                    self.health.report_worker_exception(0, exc)
        """)
        assert not firing(diags, "swallowed-worker-exception")

    def test_non_thread_function_is_exempt(self, tmp_path):
        # broad excepts outside worker threads are host-loop policy,
        # not this rule's concern
        diags = lint_src(tmp_path, """
            def best_effort_cleanup(path):
                try:
                    remove(path)
                except Exception:
                    pass
        """)
        assert not firing(diags, "swallowed-worker-exception")

    def test_suppression_works(self, tmp_path):
        diags = lint_src(tmp_path, """
            import threading

            def worker_loop(q):
                try:
                    q.work()
                # nrlint: disable=swallowed-worker-exception
                except Exception:
                    pass

            threading.Thread(target=worker_loop).start()
        """)
        hits = [d for d in diags
                if d.rule_id == "swallowed-worker-exception"]
        assert len(hits) == 1 and hits[0].suppressed


class TestNonDurablePublish:
    def test_rename_without_fsync_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import os

            def publish(path, data):
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
        """)
        assert len(firing(diags, "non-durable-publish")) == 1

    def test_bare_savez_to_path_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            import numpy as np

            def snapshot(path, arr):
                np.savez(path, arr=arr)
        """)
        assert len(firing(diags, "non-durable-publish")) == 1

    def test_fsync_before_rename_clean(self, tmp_path):
        # the core/checkpoint.py:save_snapshot discipline
        diags = lint_src(tmp_path, """
            import os

            import numpy as np

            def publish(path, payload):
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    np.savez(f, **payload)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
                dfd = os.open(os.path.dirname(path), os.O_RDONLY)
                os.fsync(dfd)
                os.close(dfd)
        """)
        assert not firing(diags, "non-durable-publish")

    def test_text_and_append_modes_not_in_scope(self, tmp_path):
        # CSV header rewrites and append-only journals are not
        # publish points (harness/mkbench.py:_append_csv,
        # durable/wal.py segment appends)
        diags = lint_src(tmp_path, """
            import os

            def rewrite_csv(path, rows):
                tmp = path + ".tmp"
                with open(tmp, "w") as g:
                    g.write(rows)
                os.replace(tmp, path)

            def journal(path, data):
                with open(path, "ab") as f:
                    f.write(data)
        """)
        assert not firing(diags, "non-durable-publish")

    def test_rename_with_no_prior_write_clean(self, tmp_path):
        # renaming something this scope never wrote (a compiler's
        # output, a download) is not the torn-publish pattern
        diags = lint_src(tmp_path, """
            import os

            def install(tmp, final):
                os.replace(tmp, final)
        """)
        assert not firing(diags, "non-durable-publish")


class TestRawClockInSubsystem:
    def _lint_in(self, tmp_path, subdir, source):
        import textwrap
        d = tmp_path / subdir
        d.mkdir(parents=True, exist_ok=True)
        p = d / "snippet.py"
        p.write_text(textwrap.dedent(source))
        diags, errors = run_lint([str(p)])
        assert not errors, errors
        return diags

    def test_monotonic_and_sleep_in_serve_fire(self, tmp_path):
        diags = self._lint_in(tmp_path, "serve", """
            import time

            def linger(cond, t):
                t_end = time.monotonic() + t
                time.sleep(t)
        """)
        assert len(firing(diags, "raw-clock-in-subsystem")) == 2

    def test_condition_wait_in_repl_fires(self, tmp_path):
        diags = self._lint_in(tmp_path, "repl", """
            class Shipper:
                def loop(self):
                    with self._cond:
                        self._cond.wait(0.002)
        """)
        assert len(firing(diags, "raw-clock-in-subsystem")) == 1

    def test_clock_routed_and_exempt_calls_clean(self, tmp_path):
        diags = self._lint_in(tmp_path, "fault", """
            from node_replication_tpu.utils.clock import get_clock

            def timed(cond, t):
                clock = get_clock()
                t0 = clock.now()
                clock.wait(cond, t)         # routed: receiver is the clock
                clock.sleep(0.01)
                evt_like.join(t)            # thread barrier: exempt
                return clock.now() - t0
        """)
        assert not firing(diags, "raw-clock-in-subsystem")

    def test_perf_counter_duration_probe_fires_in_subsystem(
            self, tmp_path):
        # ISSUE 14 satellite: the blanket perf_counter exemption is
        # narrowed to ops/bench paths — inside a clocked subsystem a
        # duration probe measured against the OS clock is the
        # wrong-clock bug (`_run_batch`'s old t0) this rule now flags
        diags = self._lint_in(tmp_path, "serve", """
            import time

            def run_batch():
                t0 = time.perf_counter()
                do_round()
                return time.perf_counter() - t0
        """)
        assert len(firing(diags, "raw-clock-in-subsystem")) == 2

    def test_perf_counter_in_ops_path_clean(self, tmp_path):
        # ops/ (and bench/harness paths) are outside the rule's path
        # scope: kernel calibration timing legitimately reads the OS
        # clock there
        diags = self._lint_in(tmp_path, "ops", """
            import time

            def calibrate():
                t0 = time.perf_counter()
                launch()
                return time.perf_counter() - t0
        """)
        assert not firing(diags, "raw-clock-in-subsystem")

    def test_outside_scoped_subsystems_clean(self, tmp_path):
        # obs/ and utils/ (the clock module itself) are outside the
        # rule's path scope — the raw clock legitimately lives there
        diags = self._lint_in(tmp_path, "obs", """
            import time

            def stamp():
                return time.monotonic()
        """)
        assert not firing(diags, "raw-clock-in-subsystem")


class TestUnboundedGrowthInSubsystem:
    def _lint_in(self, tmp_path, subdir, source):
        import textwrap
        d = tmp_path / subdir
        d.mkdir(parents=True, exist_ok=True)
        p = d / "snippet.py"
        p.write_text(textwrap.dedent(source))
        diags, errors = run_lint([str(p)])
        assert not errors, errors
        return diags

    def test_worker_append_without_bound_fires(self, tmp_path):
        # the accumulator pattern the rule exists for: a worker loop
        # appending to an __init__-unbounded deque with no depth check
        # and no drain path anywhere in the class
        diags = self._lint_in(tmp_path, "repl", """
            import threading
            from collections import deque

            class Shipper:
                def __init__(self):
                    self._backlog = deque()
                    self._t = threading.Thread(target=self._loop)

                def _loop(self):
                    while True:
                        self._backlog.append(self.next_record())
        """)
        assert len(firing(diags, "unbounded-growth-in-subsystem")) == 1

    def test_helper_on_worker_thread_fires(self, tmp_path):
        # transitive closure: the append lives in a helper the worker
        # loop calls (same closure swallowed-worker-exception uses)
        diags = self._lint_in(tmp_path, "serve", """
            import threading

            class Frontend:
                def __init__(self):
                    self._retries = []
                    self._t = threading.Thread(target=self._loop)

                def _loop(self):
                    while True:
                        self._stash(self.take())

                def _stash(self, req):
                    self._retries.append(req)
        """)
        assert len(firing(diags, "unbounded-growth-in-subsystem")) == 1

    def test_bound_check_and_drain_clean(self, tmp_path):
        # three sanctioned shapes: a len() bound compare in the
        # appending function, a deque(maxlen=), and a container the
        # class drains (a queue, not an accumulator)
        diags = self._lint_in(tmp_path, "serve", """
            import threading
            from collections import deque

            class Frontend:
                def __init__(self):
                    self._queue = deque()
                    self._recent = deque(maxlen=64)
                    self._ready = []
                    self._t = threading.Thread(target=self._loop)

                def _loop(self):
                    while True:
                        if len(self._queue) >= self.depth:
                            continue
                        self._queue.append(self.take())
                        self._recent.append(1)
                        self._drain()

                def _drain(self):
                    while self._ready:
                        self._ready.pop()
        """)
        assert not firing(diags, "unbounded-growth-in-subsystem")

    def test_watermark_named_bound_clean(self, tmp_path):
        # a watermark comparison counts as the bound check even
        # without len() (the lag-vs-high-watermark idiom)
        diags = self._lint_in(tmp_path, "repl", """
            import threading

            class Applier:
                def __init__(self):
                    self._pending = []
                    self._t = threading.Thread(target=self._loop)

                def _loop(self):
                    while True:
                        if self.lag() > self.high_watermark:
                            continue
                        self._pending.append(self.take())
        """)
        assert not firing(diags, "unbounded-growth-in-subsystem")

    def test_outside_subsystem_and_non_worker_clean(self, tmp_path):
        # same accumulator outside serve//repl/ is out of scope; and
        # inside scope, an append on a NON-worker path (no Thread
        # target reaches it) is the client's business, not the rule's
        diags = self._lint_in(tmp_path, "harness", """
            import threading

            class Collector:
                def __init__(self):
                    self._rows = []
                    self._t = threading.Thread(target=self._loop)

                def _loop(self):
                    while True:
                        self._rows.append(self.take())
        """)
        assert not firing(diags, "unbounded-growth-in-subsystem")
        diags = self._lint_in(tmp_path, "serve", """
            class Future:
                def __init__(self):
                    self._callbacks = []

                def add_done_callback(self, fn):
                    self._callbacks.append(fn)
        """)
        assert not firing(diags, "unbounded-growth-in-subsystem")


class TestRawSocketInWorker:
    def _lint_in(self, tmp_path, subdir, source):
        import textwrap
        d = tmp_path / subdir
        d.mkdir(parents=True, exist_ok=True)
        p = d / "snippet.py"
        p.write_text(textwrap.dedent(source))
        diags, errors = run_lint([str(p)])
        assert not errors, errors
        return diags

    def test_timeoutless_accept_and_recv_fire(self, tmp_path):
        # the wedge pattern: a repl/ worker loop blocking on a socket
        # with no timeout anywhere — a half-open peer parks the thread
        # forever, past every stop flag and join
        diags = self._lint_in(tmp_path, "repl", """
            import socket
            import threading

            class Server:
                def __init__(self):
                    self._sock = socket.socket()
                    self._t = threading.Thread(target=self._loop)

                def _loop(self):
                    while True:
                        conn, _ = self._sock.accept()
                        self._serve(conn)

                def _serve(self, conn):
                    return conn.recv(4096)
        """)
        assert len(firing(diags, "raw-socket-in-worker")) == 2

    def test_settimeout_discipline_clean(self, tmp_path):
        # construction-site settimeout sanctions the receiver (the
        # transport.py shape: configure once, block with a deadline)
        diags = self._lint_in(tmp_path, "repl", """
            import socket
            import threading

            class Server:
                def __init__(self):
                    self._sock = socket.socket()
                    self._sock.settimeout(0.2)
                    self._t = threading.Thread(target=self._loop)

                def _loop(self):
                    while True:
                        conn, _ = self._sock.accept()
                        conn.settimeout(5.0)
                        self._serve(conn)

                def _serve(self, conn):
                    return conn.recv(4096)
        """)
        assert not firing(diags, "raw-socket-in-worker")

    def test_non_worker_and_non_socket_clean(self, tmp_path):
        # a request helper on the CALLER's thread is the caller's
        # timeout problem, and a non-socket `.recv` (a pipe-like
        # object) is out of scope
        diags = self._lint_in(tmp_path, "repl", """
            import threading

            class Client:
                def request(self, sock, payload):
                    sock.send(payload)
                    return sock.recv(4096)

            class Pump:
                def __init__(self):
                    self._t = threading.Thread(target=self._loop)

                def _loop(self):
                    while True:
                        self._chan.recv(1)
        """)
        assert not firing(diags, "raw-socket-in-worker")

    def test_outside_repl_clean(self, tmp_path):
        diags = self._lint_in(tmp_path, "harness", """
            import socket
            import threading

            class Server:
                def __init__(self):
                    self._sock = socket.socket()
                    self._t = threading.Thread(target=self._loop)

                def _loop(self):
                    while True:
                        self._sock.accept()
        """)
        assert not firing(diags, "raw-socket-in-worker")


class TestRepoIsClean:
    def test_package_lints_clean(self):
        # the CI gate, as a test: every violation in the package is
        # either fixed or carries a justified suppression. Resolve the
        # package directory from the import (a cwd-relative path would
        # collect 0 files — and pass vacuously — when pytest runs from
        # outside the repo root) and require a real file count.
        import os

        import node_replication_tpu

        from node_replication_tpu.analysis.lint import collect_files

        pkg = os.path.dirname(node_replication_tpu.__file__)
        assert len(collect_files([pkg])) > 40
        diags, errors = run_lint([pkg])
        assert not errors
        bad = [d.format() for d in diags if not d.suppressed]
        assert not bad, "\n".join(bad)


class TestHostTransferInShardedPath:
    def _lint_in(self, tmp_path, subdir, source):
        import textwrap
        d = tmp_path / subdir
        d.mkdir(parents=True, exist_ok=True)
        p = d / "snippet.py"
        p.write_text(textwrap.dedent(source))
        diags, errors = run_lint([str(p)])
        assert not errors, errors
        return diags

    def test_state_gather_in_exec_path_fires(self, tmp_path):
        diags = self._lint_in(tmp_path, "core", """
            import numpy as np
            import jax

            def _exec_round(self):
                snap = np.asarray(self.states)      # whole-fleet gather
                tails = jax.device_get(self.log.opcodes)
                return snap, tails
        """)
        assert len(firing(diags, "host-transfer-in-sharded-path")) == 2

    def test_item_on_sharded_state_fires(self, tmp_path):
        diags = self._lint_in(tmp_path, "parallel", """
            def shmap_exec(log, states):
                return states[0].item()
        """)
        assert len(firing(diags, "host-transfer-in-sharded-path")) == 1

    def test_cursor_readbacks_and_out_of_scope_clean(self, tmp_path):
        # cursor readbacks are the exec loop's sanctioned host syncs;
        # functions outside the exec-path names (ring_slice-style host
        # bridges, checkpointing) are out of scope by design
        diags = self._lint_in(tmp_path, "core", """
            import numpy as np

            def _exec_round(self):
                cur = np.asarray(self.log.ltails)   # cursors: fine
                tail = int(self.log.tail)
                return cur, tail

            def ring_slice(spec, log, start, stop):
                return np.asarray(log.opcodes)      # host bridge: fine

            def save_snapshot(path, states):
                return np.asarray(states)           # checkpoint: fine
        """)
        assert not firing(diags, "host-transfer-in-sharded-path")

    def test_outside_core_parallel_clean(self, tmp_path):
        # the serve/obs layers read states through the wrapper's host
        # APIs — only core/ and parallel/ exec paths are in scope
        diags = self._lint_in(tmp_path, "serve", """
            import numpy as np

            def exec_probe(self):
                return np.asarray(self.states)
        """)
        assert not firing(diags, "host-transfer-in-sharded-path")


class TestAliasedPallasPlanes:
    def _lint_in_ops(self, tmp_path, source):
        import textwrap
        d = tmp_path / "ops"
        d.mkdir(parents=True, exist_ok=True)
        p = d / "snippet.py"
        p.write_text(textwrap.dedent(source))
        diags, errors = run_lint([str(p)])
        assert not errors, errors
        return diags

    def test_aliased_blocked_plane_on_deep_grid_fires(self, tmp_path):
        # the exact r5 corruption shape: a blocked state plane aliased
        # in->out while the grid pipelines across replica tiles
        diags = self._lint_in_ops(tmp_path, """
            import jax
            from jax.experimental import pallas as pl

            def build(kernel, kp, tile, R, shape):
                return pl.pallas_call(
                    kernel,
                    grid=(R // tile,),
                    in_specs=[pl.BlockSpec((kp, tile), lambda i: (0, i))],
                    out_specs=[pl.BlockSpec((kp, tile), lambda i: (0, i))],
                    out_shape=shape,
                    input_output_aliases={0: 0},
                )
        """)
        assert len(firing(diags, "aliased-pallas-planes")) == 1

    def test_grid_one_plan_kernel_aliasing_clean(self, tmp_path):
        # the plan kernels' sanctioned in-place form: one grid step,
        # no pipeline to race (ops/pallas_vspace.py)
        diags = self._lint_in_ops(tmp_path, """
            import jax
            from jax.experimental import pallas as pl

            def build(kernel, rows, shape):
                grid = (1,)
                plane = pl.BlockSpec((1, rows, 128), lambda i: (0, 0, 0))
                return pl.pallas_call(
                    kernel,
                    grid=grid,
                    in_specs=[plane, plane],
                    out_specs=[plane, plane],
                    out_shape=shape,
                    input_output_aliases={0: 0, 1: 1},
                )
        """)
        assert not firing(diags, "aliased-pallas-planes")

    def test_unblocked_any_ref_dma_aliasing_clean(self, tmp_path):
        # the fused round's ring planes: memory_space-only specs moved
        # by explicit in-kernel DMA sit outside the grid pipeline
        diags = self._lint_in_ops(tmp_path, """
            import jax
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def build(kernel, kp, tile, R, shape):
                return pl.pallas_call(
                    kernel,
                    grid=(R // tile,),
                    in_specs=[
                        pl.BlockSpec(memory_space=pltpu.ANY),
                        pl.BlockSpec((kp, tile), lambda i: (0, i)),
                    ],
                    out_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
                    out_shape=shape,
                    input_output_aliases={0: 0},
                )
        """)
        assert not firing(diags, "aliased-pallas-planes")

    def test_shard_map_wrapped_blocked_aliasing_fires(self, tmp_path):
        # the mesh-fused era variant of the race: the pallas_call
        # lives in a nested shard-local function (wrapped in
        # shard_map) while grid/in_specs/input_output_aliases are
        # bound in the enclosing builder — closure-level resolution
        # must still see the blocked aliased plane
        diags = self._lint_in_ops(tmp_path, """
            import jax
            from jax.experimental import pallas as pl
            from node_replication_tpu.utils.compat import shard_map

            def build(kernel, kp, tile, R, mesh, shape, P):
                grid = (R // tile,)
                specs = [pl.BlockSpec((kp, tile), lambda i: (0, i))]
                al = {0: 0}

                def local(states_l):
                    return pl.pallas_call(
                        kernel,
                        grid=grid,
                        in_specs=specs,
                        out_specs=specs,
                        out_shape=shape,
                        input_output_aliases=al,
                    )(states_l)

                return shard_map(local, mesh=mesh, in_specs=P,
                                 out_specs=P)
        """)
        assert len(firing(diags, "aliased-pallas-planes")) == 1

    def test_rebound_grid_resolves_to_last_assignment(self, tmp_path):
        # within a scope the LAST assignment wins (closure resolution
        # must not invert _local_aliases's order): a grid rebound from
        # (1,) to multi-step before the call is a real race and must
        # still fire
        diags = self._lint_in_ops(tmp_path, """
            import jax
            from jax.experimental import pallas as pl

            def build(kernel, kp, tile, R, shape):
                grid = (1,)
                grid = (R // tile,)
                return pl.pallas_call(
                    kernel,
                    grid=grid,
                    in_specs=[pl.BlockSpec((kp, tile), lambda i: (0, i))],
                    out_specs=[pl.BlockSpec((kp, tile), lambda i: (0, i))],
                    out_shape=shape,
                    input_output_aliases={0: 0},
                )
        """)
        assert len(firing(diags, "aliased-pallas-planes")) == 1

    def test_shard_map_wrapped_unblocked_dma_clean(self, tmp_path):
        # the sanctioned mesh-fused shape: the aliased refs are
        # UN-BLOCKED ANY planes moved by explicit DMA (the replicated
        # ring copies), outside the grid pipeline — clean even when
        # the call is built inside the shard-local closure
        diags = self._lint_in_ops(tmp_path, """
            import jax
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu
            from node_replication_tpu.utils.compat import shard_map

            def build(kernel, kp, tile, R, mesh, shape, P):
                grid = (R // tile,)
                specs = [
                    pl.BlockSpec(memory_space=pltpu.ANY),
                    pl.BlockSpec((kp, tile), lambda i: (0, i)),
                ]
                al = {0: 0}

                def local(ring, states_l):
                    return pl.pallas_call(
                        kernel,
                        grid=grid,
                        in_specs=specs,
                        out_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
                        out_shape=shape,
                        input_output_aliases=al,
                    )(ring, states_l)

                return shard_map(local, mesh=mesh, in_specs=P,
                                 out_specs=P)
        """)
        assert not firing(diags, "aliased-pallas-planes")

    def test_outside_ops_and_unaliased_clean(self, tmp_path):
        # path scope: kernels live in ops/; an aliased call elsewhere
        # (scratch experiments, tests) is out of scope — and a deep
        # grid WITHOUT aliasing is the sanctioned separate-plane shape
        diags = lint_src(tmp_path, """
            import jax
            from jax.experimental import pallas as pl

            def build(kernel, kp, tile, R, shape):
                return pl.pallas_call(
                    kernel,
                    grid=(R // tile,),
                    in_specs=[pl.BlockSpec((kp, tile), lambda i: (0, i))],
                    out_specs=[pl.BlockSpec((kp, tile), lambda i: (0, i))],
                    out_shape=shape,
                    input_output_aliases={0: 0},
                )
        """)
        assert not firing(diags, "aliased-pallas-planes")
        diags2 = self._lint_in_ops(tmp_path, """
            import jax
            from jax.experimental import pallas as pl

            def build(kernel, kp, tile, R, shape):
                return pl.pallas_call(
                    kernel,
                    grid=(R // tile,),
                    in_specs=[pl.BlockSpec((kp, tile), lambda i: (0, i))],
                    out_specs=[pl.BlockSpec((kp, tile), lambda i: (0, i))],
                    out_shape=shape,
                )
        """)
        assert not firing(diags2, "aliased-pallas-planes")


class TestUnboundedMetricCardinality:
    def _lint_in(self, tmp_path, subdir, source):
        import textwrap
        d = tmp_path / subdir
        d.mkdir(parents=True, exist_ok=True)
        p = d / "snippet.py"
        p.write_text(textwrap.dedent(source))
        diags, errors = run_lint([str(p)])
        assert not errors, errors
        return diags

    def test_pos_and_request_id_interpolation_fires(self, tmp_path):
        # the leak pattern: one instrument minted per record — the
        # registry (and every exporter scrape) grows without bound
        diags = self._lint_in(tmp_path, "serve", """
            from node_replication_tpu.obs.metrics import get_registry

            def note(reg, rec, request_id):
                reg.counter(f"repl.record.{rec.pos}").inc()
                reg.gauge("lat.req.{}".format(request_id)).set(1.0)
                get_registry().histogram("h.%d" % rec.seq).observe(0.1)
        """)
        assert len(firing(diags, "unbounded-metric-cardinality")) == 3

    def test_bounded_dimensions_clean(self, tmp_path):
        # rid (per-replica) and log_idx (per-log) are fleet-bounded
        # dimensions — the sanctioned serve.queue_depth.r<rid> shape —
        # and a constant name is the normal case
        diags = self._lint_in(tmp_path, "serve", """
            from node_replication_tpu.obs.metrics import get_registry

            def wire(reg, rid, log_idx):
                reg.gauge(f"serve.queue_depth.r{rid}").set(0)
                reg.counter(f"cnr.log{log_idx}.rounds").inc()
                get_registry().counter("serve.submitted").inc()
        """)
        assert not firing(diags, "unbounded-metric-cardinality")

    def test_non_registry_receiver_clean(self, tmp_path):
        # .counter() on something that is not the metrics registry
        # (a collections.Counter factory, a stats helper) is out of
        # scope — the rule keys on registry-shaped receivers
        diags = self._lint_in(tmp_path, "harness", """
            def tally(stats, pos):
                return stats.counter(f"bucket-{pos}")
        """)
        assert not firing(diags, "unbounded-metric-cardinality")

    def test_obs_package_out_of_scope(self, tmp_path):
        # the registry's own implementation/fixtures legitimately
        # build names from variables
        diags = self._lint_in(tmp_path, "obs", """
            def make(reg, pos):
                return reg.counter(f"fixture.{pos}")
        """)
        assert not firing(diags, "unbounded-metric-cardinality")

    def test_suppression_works(self, tmp_path):
        diags = self._lint_in(tmp_path, "repl", """
            def note(reg, pos):
                reg.counter(f"x.{pos}").inc()  # nrlint: disable=unbounded-metric-cardinality — fixture
        """)
        assert not firing(diags, "unbounded-metric-cardinality")


class TestDeviceSyncInAssembly:
    """Rule 19 (ISSUE 14): host syncs on the serve pipeline's assembly
    stage re-serialize exactly the overlap the pipeline exists to buy.
    Rooted at `_assemble`, closed over same-module helpers (the
    blocking-in-handler closure machinery)."""

    def test_item_in_assemble_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            class Frontend:
                def _assemble(self, rid, q, batch):
                    depth = self._nr.log.tail.item()
                    return depth
        """)
        assert len(firing(diags, "device-sync-in-assembly")) == 1

    def test_blocking_helper_via_closure_fires(self, tmp_path):
        # a helper reachable from _assemble is still assembly-stage
        # code: delegating the device_get does not launder it
        diags = lint_src(tmp_path, """
            import jax

            class Frontend:
                def _peek(self, arr):
                    return jax.device_get(arr)

                def _assemble(self, rid, q, batch):
                    return self._peek(batch)
        """)
        assert len(firing(diags, "device-sync-in-assembly")) == 1

    def test_future_result_in_assemble_fires(self, tmp_path):
        diags = lint_src(tmp_path, """
            class Frontend:
                def _assemble(self, rid, q, batch):
                    return batch[0].future.result()
        """)
        assert len(firing(diags, "device-sync-in-assembly")) == 1

    def test_clean_assembly_and_out_of_closure_sync_clean(
            self, tmp_path):
        # the real assembly shape (sweep + begin + handoff) is clean,
        # and a sync in the COMPLETION stage — not reachable from
        # _assemble — is exactly where the wait belongs
        diags = lint_src(tmp_path, """
            class Frontend:
                def _sweep(self, batch):
                    return [r for r in batch if r.live]

                def _assemble(self, rid, q, batch):
                    live = self._sweep(batch)
                    return self._nr.begin_mut_batch(
                        [r.op for r in live], rid
                    )

                def _complete(self, rid, q, staged):
                    resps = self._nr.finish_mut_batch(staged.pending)
                    return [int(r) for r in resps]

                def _deliver(self, arr):
                    return arr.item()  # completion-side: fine
        """)
        assert not firing(diags, "device-sync-in-assembly")

    def test_module_without_assemble_clean(self, tmp_path):
        diags = lint_src(tmp_path, """
            def worker(arr):
                return arr.item()
        """)
        assert not firing(diags, "device-sync-in-assembly")


class TestUnnamedWorkerThread:
    """Rule 20: anonymous threads inside the serve/repl/fault/
    durable/obs subsystems collapse into the sampling profiler's
    'other' role bucket (`obs/profile.role_of`), so subsystem spawns
    must carry `name=`. Tests/benches/examples are out of scope."""

    def _lint_in(self, tmp_path, subdir, source):
        import textwrap
        d = tmp_path / subdir
        d.mkdir(parents=True, exist_ok=True)
        p = d / "snippet.py"
        p.write_text(textwrap.dedent(source))
        diags, errors = run_lint([str(p)])
        assert not errors, errors
        return diags

    def test_unnamed_thread_in_serve_fires(self, tmp_path):
        diags = self._lint_in(tmp_path, "serve", """
            import threading

            def spawn(q):
                t = threading.Thread(target=q.drain, daemon=True)
                t.start()
                return t
        """)
        assert len(firing(diags, "unnamed-worker-thread")) == 1

    def test_unnamed_thread_in_obs_fires(self, tmp_path):
        diags = self._lint_in(tmp_path, "obs", """
            from threading import Thread

            def spawn(fn):
                return Thread(target=fn)
        """)
        assert len(firing(diags, "unnamed-worker-thread")) == 1

    def test_named_thread_clean(self, tmp_path):
        diags = self._lint_in(tmp_path, "repl", """
            import threading

            def spawn(rid, loop):
                return threading.Thread(
                    target=loop, name=f"repl-apply-{rid}", daemon=True,
                )
        """)
        assert not firing(diags, "unnamed-worker-thread")

    def test_out_of_scope_module_clean(self, tmp_path):
        # scratch threads in test/bench-style modules don't feed the
        # profiler's role table — out of the rule's scope
        diags = lint_src(tmp_path, """
            import threading

            def spawn(fn):
                return threading.Thread(target=fn)
        """)
        assert not firing(diags, "unnamed-worker-thread")


class TestUnroutedKeyInShardPath:
    def _lint_in(self, tmp_path, subdir, source):
        import textwrap
        d = tmp_path / subdir
        d.mkdir(parents=True, exist_ok=True)
        p = d / "snippet.py"
        p.write_text(textwrap.dedent(source))
        diags, errors = run_lint([str(p)])
        assert not errors, errors
        return diags

    def test_unrouted_submit_fires(self, tmp_path):
        # the mis-route pattern: a shard/ helper that hands ops to a
        # frontend with no ShardMap lookup anywhere in the function —
        # one stale-map refactor away from writing into the wrong
        # keyspace slice
        diags = self._lint_in(tmp_path, "shard", """
            class Proxy:
                def forward(self, fe, ops):
                    futs = [fe.submit(op) for op in ops]
                    return [f.result() for f in futs]

                def forward_round(self, nr, opcodes, args):
                    return nr.execute_mut_batch(opcodes, args)
        """)
        assert len(firing(diags, "unrouted-key-in-shard-path")) == 2

    def test_routed_submit_clean(self, tmp_path):
        # the sanctioned shape (shard/router.py LocalBackend): the
        # same function re-verifies each op's owner through the map
        # before staging anything
        diags = self._lint_in(tmp_path, "shard", """
            class Backend:
                def submit_batch(self, fe, ops):
                    for op in ops:
                        if self._map.shard_of_op(op) != self.shard:
                            raise ValueError("wrong shard")
                    return [fe.submit(op) for op in ops]

                def route(self, fe, ops):
                    groups = self._map.split_batch(ops)
                    for shard, entries in groups.items():
                        for _i, op in entries:
                            fe.submit(op)
        """)
        assert not firing(diags, "unrouted-key-in-shard-path")

    def test_outside_shard_clean(self, tmp_path):
        # the serve plane itself has no sharding contract to honor
        diags = self._lint_in(tmp_path, "serve", """
            class Caller:
                def call(self, fe, op):
                    return fe.submit(op).result()
        """)
        assert not firing(diags, "unrouted-key-in-shard-path")


class TestTxnAckBeforeDecision:
    def _lint_in(self, tmp_path, subdir, source):
        import textwrap
        d = tmp_path / subdir
        d.mkdir(parents=True, exist_ok=True)
        p = d / "snippet.py"
        p.write_text(textwrap.dedent(source))
        diags, errors = run_lint([str(p)])
        assert not errors, errors
        return diags

    def test_ack_without_decision_fires(self, tmp_path):
        # the lost-commit-point bug: the coordinator resolves the
        # caller's future after prepare with NO durable decision — a
        # crash right after the ack presumed-aborts a transaction the
        # caller was told committed
        diags = self._lint_in(tmp_path, "shard", """
            class Coordinator:
                def run(self, txn, groups, fut):
                    for shard, ops in groups.items():
                        self._backend(shard).prepare(txn, self.gen, ops)
                    results = self._commit_all(txn, groups)
                    fut.set_result(results)
        """)
        assert len(firing(diags, "txn-ack-before-decision")) == 1

    def test_verb_string_dispatch_fires(self, tmp_path):
        # the prepare step hidden behind a verb-string dispatch
        # helper is still the prepare step
        diags = self._lint_in(tmp_path, "shard", """
            class Coordinator:
                def run(self, txn, groups, fut):
                    for shard, ops in groups.items():
                        self._verb(shard, "prepare", txn, ops=ops)
                    fut.set_result(self._commit_all(txn, groups))
        """)
        assert len(firing(diags, "txn-ack-before-decision")) == 1

    def test_decision_before_ack_clean(self, tmp_path):
        # the sanctioned shape (shard/txn.py TxnCoordinator): the
        # decision document is durably published BEFORE any future
        # resolves
        diags = self._lint_in(tmp_path, "shard", """
            class Coordinator:
                def run(self, txn, groups, fut):
                    for shard, ops in groups.items():
                        self._backend(shard).prepare(txn, self.gen, ops)
                    self.decisions.publish(txn, "commit",
                                           shards=sorted(groups))
                    fut.set_result(self._commit_all(txn, groups))
        """)
        assert not firing(diags, "txn-ack-before-decision")

    def test_set_exception_exempt(self, tmp_path):
        # failing the caller never claims the transaction decided
        diags = self._lint_in(tmp_path, "shard", """
            class Coordinator:
                def run(self, txn, groups, fut):
                    try:
                        for shard, ops in groups.items():
                            self._backend(shard).prepare(txn, 0, ops)
                    except Exception as e:
                        fut.set_exception(e)
        """)
        assert not firing(diags, "txn-ack-before-decision")

    def test_outside_shard_clean(self, tmp_path):
        # only the shard/ txn plane carries the 2PC contract
        diags = self._lint_in(tmp_path, "serve", """
            class Pipeline:
                def run(self, stage, fut):
                    stage.prepare()
                    fut.set_result(stage.flush())
        """)
        assert not firing(diags, "txn-ack-before-decision")
