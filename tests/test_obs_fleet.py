"""Fleet observability plane: exporter, collector, merged-trace
report, dashboard, and per-record cross-process tracing (ISSUE 13).

The contract under test: a `MetricsExporter` serves the registry
snapshot + trace tail over CRC-framed JSON under a node_id/role
identity; a `FleetCollector` merges N exporters into time-series
rings and a `fleet.jsonl` whose events carry `node_id`/`role`/
`t_fleet` (per-pid dedup, component re-attribution); the report's
Fleet section joins per-record hop events on `pos` into causally
ordered timelines with per-edge percentiles; per-rid serve gauges
retire from the registry with their replica; and sampled per-record
tracing keeps whole chains or nothing.
"""

import json
import socket
import threading

import pytest

from node_replication_tpu.obs import report
from node_replication_tpu.obs.collect import FleetCollector
from node_replication_tpu.obs.export import (
    ExportError,
    MetricsExporter,
    recv_frame,
    scrape,
    send_frame,
    to_prometheus,
)
from node_replication_tpu.obs.metrics import MetricsRegistry, get_registry
from node_replication_tpu.obs.recorder import (
    Tracer,
    get_tracer,
    set_trace_sample,
)
from node_replication_tpu.obs.top import node_row, render_frame


@pytest.fixture
def reg():
    r = MetricsRegistry(enabled=True)
    return r


@pytest.fixture
def tracer():
    t = Tracer()
    t.enable(None, ring=512)
    yield t
    t.disable()


def make_exporter(reg, tracer, **kw):
    return MetricsExporter(registry=reg, tracer=tracer, port=0,
                           **kw)


class TestExporter:
    def test_scrape_roundtrip(self, reg, tracer):
        reg.counter("a.count").inc(3)
        reg.gauge("b.gauge").set(1.5)
        reg.histogram("c.hist").observe(0.01)
        tracer.emit("append", pos0=0, n=4)
        with make_exporter(reg, tracer, node_id="n1",
                           role="primary") as exp:
            exp.add_stats("serve", lambda: {"completed": 7})
            doc = scrape(*exp.address)
        assert doc["node_id"] == "n1" and doc["role"] == "primary"
        assert doc["metrics"]["a.count"] == 3
        assert doc["metrics"]["b.gauge"] == 1.5
        assert doc["metrics"]["c.hist"]["count"] == 1
        assert doc["stats"]["serve"]["completed"] == 7
        # the exporter's own announce event rides along with `append`
        assert [e["event"] for e in doc["events"]] == [
            "append", "obs-export-serve"]
        assert doc["seq"] == 2 and "now_ts" in doc

    def test_incremental_cursor(self, reg, tracer):
        with make_exporter(reg, tracer) as exp:
            tracer.emit("e1")
            d1 = scrape(*exp.address)
            assert [e["event"] for e in d1["events"]] == [
                "obs-export-serve", "e1"]
            tracer.emit("e2")
            d2 = scrape(*exp.address, since=d1["seq"])
            assert [e["event"] for e in d2["events"]] == ["e2"]
            # same cursor again: nothing new
            d3 = scrape(*exp.address, since=d2["seq"])
            assert d3["events"] == []

    def test_sick_stats_provider_isolated(self, reg, tracer):
        def boom():
            raise RuntimeError("sick subsystem")

        with make_exporter(reg, tracer) as exp:
            exp.add_stats("bad", boom)
            exp.add_stats("good", lambda: {"x": 1})
            doc = scrape(*exp.address)
        assert doc["stats"]["good"] == {"x": 1}
        assert "RuntimeError" in doc["stats"]["bad"]["error"]

    def test_prometheus_exposition(self, reg, tracer):
        reg.counter("serve.completed").inc(9)
        reg.gauge("repl.apply_lag_pos").set(2.0)
        reg.histogram("serve.batch.duration_s").observe(0.004)
        with make_exporter(reg, tracer, node_id="nX",
                           role="relay") as exp:
            text = to_prometheus(scrape(*exp.address))
        assert ('nr_tpu_serve_completed{node="nX",role="relay"} 9'
                in text)
        assert "# TYPE nr_tpu_repl_apply_lag_pos gauge" in text
        assert "nr_tpu_serve_batch_duration_s_count" in text
        assert 'quantile="0.95"' in text

    def test_bad_frame_is_transport_error_not_crash(self, reg,
                                                    tracer):
        with make_exporter(reg, tracer) as exp:
            sock = socket.create_connection(exp.address, timeout=2.0)
            sock.sendall(b"\xff" * 8 + b"garbage")
            sock.close()
            # the server survives a torn/garbage client: next scrape
            # still answers
            doc = scrape(*exp.address)
            assert "node_id" in doc

    def test_unknown_command_answers_typed_error(self, reg, tracer):
        with make_exporter(reg, tracer) as exp:
            sock = socket.create_connection(exp.address, timeout=2.0)
            try:
                send_frame(sock, json.dumps({"cmd": "nope"}).encode())
                rsp = json.loads(recv_frame(sock).decode())
            finally:
                sock.close()
            assert "error" in rsp
            with pytest.raises(RuntimeError):
                # the client helper surfaces it as a typed failure
                raise RuntimeError(rsp["error"])

    def test_closed_exporter_refuses(self, reg, tracer):
        exp = make_exporter(reg, tracer)
        addr = exp.address
        exp.close()
        with pytest.raises(ExportError):
            scrape(*addr, timeout_s=0.5)


class TestCollector:
    def test_socket_and_inprocess_targets(self, reg, tracer,
                                          tmp_path):
        reg.counter("x.ops").inc(4)
        out = tmp_path / "fleet.jsonl"
        with make_exporter(reg, tracer, node_id="socknode",
                           role="primary") as exp:
            reg2 = MetricsRegistry(enabled=True)
            t2 = Tracer()
            t2.enable(None, ring=64)
            exp2 = MetricsExporter(registry=reg2, tracer=t2, port=0,
                                   node_id="inproc", role="follower")
            coll = FleetCollector(
                [f"{exp.address[0]}:{exp.address[1]}", exp2],
                out_path=str(out),
            )
            try:
                assert coll.collect_once() == 2
                assert coll.nodes() == ["inproc", "socknode"]
                assert coll.series("socknode", "x.ops") == [
                    (coll.series("socknode", "x.ops")[0][0], 4)
                ]
                latest = coll.latest()
                assert latest["socknode"]["role"] == "primary"
            finally:
                coll.close()
                exp2.close()
                t2.disable()
        lines = [json.loads(ln) for ln in
                 out.read_text().splitlines()]
        assert sum(1 for ln in lines
                   if ln["event"] == "fleet-scrape") == 2

    def test_unreachable_target_counts_not_crashes(self, tmp_path):
        out = tmp_path / "fleet.jsonl"
        coll = FleetCollector(["127.0.0.1:1"], out_path=str(out),
                              timeout_s=0.2)
        try:
            assert coll.collect_once() == 0
            assert coll.stats()["errors"]
        finally:
            coll.close()
        lines = [json.loads(ln) for ln in
                 out.read_text().splitlines()]
        assert any(ln["event"] == "fleet-scrape-error"
                   for ln in lines)

    def test_pid_dedup_and_reattribution(self, reg, tracer,
                                         tmp_path):
        # two exporters in ONE process share the tracer: the merge
        # must keep each event once, and an event naming a known node
        # (a relay's relay-forward) re-attributes to that node
        out = tmp_path / "fleet.jsonl"
        a = make_exporter(reg, tracer, node_id="primary",
                          role="primary")
        b = make_exporter(reg, tracer, node_id="relay7",
                          role="relay")
        coll = FleetCollector([a, b], out_path=str(out))
        try:
            coll.collect_once()  # learn both identities
            tracer.emit("repl-ship", pos=8, n=1)
            tracer.emit("relay-forward", pos=8, n=1, name="relay7")
            coll.collect_once()
        finally:
            coll.close()
            a.close()
            b.close()
        lines = [json.loads(ln) for ln in
                 out.read_text().splitlines()]
        ships = [ln for ln in lines if ln["event"] == "repl-ship"]
        fwds = [ln for ln in lines
                if ln["event"] == "relay-forward"]
        assert len(ships) == 1 and len(fwds) == 1  # pid-deduped
        assert ships[0]["node_id"] == "primary"
        assert fwds[0]["node_id"] == "relay7"  # re-attributed
        assert fwds[0]["role"] == "relay"
        assert "t_fleet" in ships[0]

    def test_pre_scrape_events_reattribute_to_known_exporters(
            self, reg, tracer, tmp_path):
        # in-process exporters declare their identity at construction,
        # so even events emitted BEFORE the collector's first cycle
        # re-attribute to the right co-resident node
        out = tmp_path / "fleet.jsonl"
        a = make_exporter(reg, tracer, node_id="primary",
                          role="primary")
        b = make_exporter(reg, tracer, node_id="relay9",
                          role="relay")
        tracer.emit("relay-forward", pos=4, n=1, name="relay9")
        coll = FleetCollector([a, b], out_path=str(out))
        try:
            coll.collect_once()  # FIRST cycle already sees relay9
        finally:
            coll.close()
            a.close()
            b.close()
        fwds = [json.loads(ln) for ln in out.read_text().splitlines()
                if json.loads(ln)["event"] == "relay-forward"]
        assert len(fwds) == 1 and fwds[0]["node_id"] == "relay9"

    def test_owner_death_reelects_pid_owner(self, reg, tracer):
        # the pid's event-merge owner dies; a surviving co-resident
        # exporter must take over event merging on its next cycle
        a = make_exporter(reg, tracer, node_id="owner",
                          role="primary")
        b = make_exporter(reg, tracer, node_id="survivor",
                          role="relay")
        coll = FleetCollector(
            [f"{a.address[0]}:{a.address[1]}", b],
        )
        try:
            coll.collect_once()  # a owns the pid
            a.close()
            tracer.emit("repl-ship", pos=0, n=1)
            coll.collect_once()  # a errors -> ownership released
            n_before = coll.stats()["merged_events"]
            tracer.emit("repl-ship", pos=4, n=1)
            coll.collect_once()  # b merges now
            assert coll.stats()["merged_events"] > n_before
        finally:
            coll.close()
            b.close()

    def test_add_target_mid_run(self, reg, tracer):
        coll = FleetCollector([])
        try:
            assert coll.collect_once() == 0
            with make_exporter(reg, tracer, node_id="late") as exp:
                coll.add_target(exp)
                assert coll.collect_once() == 1
                assert coll.nodes() == ["late"]
        finally:
            coll.close()


def _merged(events):
    """Stamp a synthetic event list the way the collector would."""
    return [dict(e) for e in events]


class TestFleetReportJoin:
    def _chain_events(self):
        # the canonical 3-process chain for pos 64: primary submit/
        # append/sync/ship/ack, relay forward, leaf apply
        return [
            {"event": "fleet-scrape", "node_id": "primary",
             "role": "primary", "ts": 100.0, "t": 0.1,
             "metrics": {"repl.ship_lag_pos": 0.0},
             "stats": {"serve": {"completed": 10, "queued": 0,
                                 "shed": 0}}},
            {"event": "fleet-scrape", "node_id": "relay0",
             "role": "relay", "ts": 100.0, "t": 0.1, "metrics": {},
             "stats": {"relay": {"cursor": 65}}},
            {"event": "fleet-scrape", "node_id": "leaf0",
             "role": "follower", "ts": 100.0, "t": 0.1,
             "metrics": {"repl.apply_lag_pos": 1.0},
             "stats": {"follower": {"applied": 65}}},
            {"event": "serve-batch", "node_id": "primary", "pos": 64,
             "n": 1, "ts": 100.010, "t_fleet": 100.010,
             "duration_s": 0.004, "queue_delay_s": 0.001},
            {"event": "append", "node_id": "primary", "pos0": 64,
             "n": 1, "ts": 100.007, "t_fleet": 100.007,
             "duration_s": 0.001},
            {"event": "wal-sync", "node_id": "primary",
             "synced_to": 65, "ts": 100.008, "t_fleet": 100.008,
             "duration_s": 0.0005},
            {"event": "repl-ship", "node_id": "primary", "pos": 64,
             "n": 1, "ts": 100.009, "t_fleet": 100.009},
            {"event": "relay-forward", "node_id": "relay0",
             "name": "relay0", "pos": 64, "n": 1, "ts": 100.011,
             "t_fleet": 100.012},
            {"event": "repl-apply", "node_id": "leaf0",
             "name": "leaf0", "pos": 64, "n": 1, "ts": 100.013,
             "t_fleet": 100.015},
        ]

    def test_three_process_chain_joins(self):
        fleet = report.analyze(self._chain_events())["fleet"]
        assert {n["node_id"] for n in fleet["nodes"]} == {
            "primary", "relay0", "leaf0"}
        roles = {n["node_id"]: n["role"] for n in fleet["nodes"]}
        assert roles["relay0"] == "relay"
        assert fleet["records"] == 1
        assert fleet["complete_records"] == 1
        assert fleet["complete_multiprocess_records"] == 1
        tl = fleet["timelines"][0]
        assert tl["pos"] == 64 and tl["processes"] == 3
        hops = [(h["hop"], h["node"]) for h in tl["hops"]]
        assert hops == [
            ("submit", "primary"), ("append", "primary"),
            ("wal-sync", "primary"), ("ship", "primary"),
            ("relay-forward", "relay0"), ("apply", "leaf0"),
            ("ack", "primary"),
        ]
        # the submit stamp reconstructs from ack - delay - duration
        assert tl["hops"][0]["t"] == 0.0
        edges = fleet["edges"]
        assert "submit->ack" in edges
        assert edges["submit->ack"]["count"] == 1
        assert abs(edges["submit->ack"]["p50_s"] - 0.005) < 1e-9
        assert edges["relay-forward->apply"]["p50_s"] > 0

    def test_follower_reappend_filtered_to_origin(self):
        # followers replay through the same combiner protocol and
        # re-emit append/wal-sync — the chain keeps only the origin's
        events = self._chain_events() + [
            {"event": "append", "node_id": "leaf0", "pos0": 64,
             "n": 1, "ts": 100.014, "t_fleet": 100.016,
             "duration_s": 0.001},
            {"event": "wal-sync", "node_id": "leaf0",
             "synced_to": 70, "ts": 100.017, "t_fleet": 100.019,
             "duration_s": 0.0005},
        ]
        fleet = report.analyze(events)["fleet"]
        tl = fleet["timelines"][0]
        appends = [h for h in tl["hops"] if h["hop"] == "append"]
        syncs = [h for h in tl["hops"] if h["hop"] == "wal-sync"]
        assert [h["node"] for h in appends] == ["primary"]
        assert [h["node"] for h in syncs] == ["primary"]
        # no negative edges sneak in through the replayed append
        for label, e in fleet["edges"].items():
            assert e["p50_s"] >= 0, (label, e)

    def test_multi_node_same_hop_uses_first_occurrence(self):
        # two relays forward, two leaves apply: edges pair FIRST
        # occurrences, never across parallel nodes
        events = self._chain_events() + [
            {"event": "relay-forward", "node_id": "relay1",
             "name": "relay1", "pos": 64, "n": 1, "ts": 100.020,
             "t_fleet": 100.020},
            {"event": "repl-apply", "node_id": "leaf1",
             "name": "leaf1", "pos": 64, "n": 1, "ts": 100.025,
             "t_fleet": 100.025},
        ]
        fleet = report.analyze(events)["fleet"]
        tl = fleet["timelines"][0]
        assert tl["processes"] == 5
        for label, e in fleet["edges"].items():
            assert e["p50_s"] >= 0, (label, e)
        assert fleet["edges"]["relay-forward->apply"]["count"] == 1

    def test_earliest_occurrence_not_node_sort_order(self):
        # a relay whose name sorts BEFORE the fast relay but forwards
        # LATER must not become the edge anchor (earliest by time,
        # not by (rank, node) list order)
        events = self._chain_events() + [
            {"event": "relay-forward", "node_id": "a-relay",
             "name": "a-relay", "pos": 64, "n": 1, "ts": 100.030,
             "t_fleet": 100.030},
        ]
        fleet = report.analyze(events)["fleet"]
        for label, e in fleet["edges"].items():
            assert e["p50_s"] >= 0, (label, e)
        # relay-forward anchors at relay0's 100.012, so apply at
        # 100.015 gives +3ms, not 100.030 -> -15ms
        assert fleet["edges"]["relay-forward->apply"][
            "p50_s"] == pytest.approx(0.003, abs=1e-6)

    def test_no_node_tags_no_fleet_section(self):
        rep = report.analyze([
            {"event": "append", "pos0": 0, "n": 1, "ts": 1.0,
             "mono": 1.0, "duration_s": 0.001},
        ])
        assert rep["fleet"] is None

    def test_renders_and_json_roundtrips(self, capsys):
        import io

        rep = report.analyze(self._chain_events())
        buf = io.StringIO()
        report.render(rep, out=buf)
        out = buf.getvalue()
        assert "== fleet ==" in out
        assert "record @pos 64 (3 process(es), complete)" in out
        assert "per-edge latency:" in out
        json.dumps(rep)  # JSON-serializable end to end

    def test_partial_merge_scrapes_only(self):
        # a collector that merged summaries but no hop events still
        # renders (explicit no-joinable-hops note, no crash)
        import io

        events = [e for e in self._chain_events()
                  if e["event"] == "fleet-scrape"]
        rep = report.analyze(events)
        assert rep["fleet"]["records"] == 0
        buf = io.StringIO()
        report.render(rep, out=buf)
        assert "no joinable per-record hops" in buf.getvalue()


class TestReportRobustness:
    """Every section renders cleanly — no crash, explicit no-data —
    on traces missing (or only partially holding) its events."""

    def _render(self, events):
        import io

        rep = report.analyze(events)
        buf = io.StringIO()
        report.render(rep, out=buf)
        json.dumps(rep)
        return rep, buf.getvalue()

    def test_empty_trace(self):
        rep, out = self._render([])
        assert "trace: 0 events" in out
        assert "[no data:" in out and "fleet" in out

    def test_sections_line_lists_absences(self):
        _rep, out = self._render(
            [{"event": "serve-batch", "rid": 0, "n": 1, "ts": 1.0,
              "mono": 1.0, "queue_depth": 0, "duration_s": 0.001}]
        )
        assert "sections: serve" in out
        assert "[no data:" in out

    def test_serve_shed_without_batches(self):
        rep, out = self._render(
            [{"event": "serve-shed", "rid": 0, "depth": 4,
              "prio": "NORMAL", "ts": 1.0, "mono": 1.0}]
        )
        assert rep["serve"]["shed"] == 1
        assert rep["serve"]["max_batch"] == 0
        assert "== serve ==" in out

    def test_promotion_without_rto(self):
        rep, _ = self._render(
            [{"event": "repl-promote", "name": "f1", "epoch": 2,
              "applied": 10, "duration_s": 0.1, "ts": 1.0,
              "mono": 1.0}]
        )
        p = rep["replication"]["promotions"][0]
        assert p["rto_s"] == pytest.approx(0.1)
        assert p["detect_s"] == 0.0

    def test_fault_rehome_only(self):
        rep, out = self._render(
            [{"event": "serve-rehome", "rid": 1, "n": 3, "ts": 1.0,
              "mono": 1.0}]
        )
        assert rep["fault"]["rehomed"] == 3
        assert rep["fault"]["repair_p50_s"] == 0.0
        assert "== fault ==" in out

    def test_kernel_launch_only(self):
        rep, out = self._render(
            [{"event": "kernel-launch", "tier": "pallas_fused",
              "window": 64, "count": 40, "launches": 1,
              "duration_s": 0.002, "ts": 1.0, "mono": 1.0}]
        )
        k = rep["kernels"]
        assert k["rounds"] == 1 and k["fused_ops"] == 40
        assert k["launches_by_tier"] == {"pallas_fused": 1}
        assert k["window_hist"] == {64: 1}
        assert k["serve_batches_by_engine"] == {}
        assert "== kernels ==" in out
        assert "launches by tier: pallas_fused=1" in out

    def test_durability_open_only(self):
        rep, out = self._render(
            [{"event": "wal-open", "tail": 0, "ts": 1.0,
              "mono": 1.0}]
        )
        assert rep["durability"]["fsyncs"] == 0
        assert "== durability ==" in out


class TestDashboard:
    def test_node_row_and_frame(self):
        latest = {
            "primary": {
                "node_id": "primary", "role": "primary", "t": 1.0,
                "metrics": {
                    "repl.ship_lag_pos": 5.0,
                    "serve.request.latency_s": {"count": 9,
                                                "p99": 0.0021},
                },
                "stats": {"serve": {
                    "completed": 100, "accepted": 110, "shed": 10,
                    "deadline_missed": 0, "queued": 2,
                    "overload": {"limits": {"0": 32, "1": 64},
                                 "brownout": True,
                                 "backpressure": 0},
                }},
            },
            "leaf0": {
                "node_id": "leaf0", "role": "follower", "t": 0.0,
                "metrics": {"repl.apply_lag_pos": 7.0},
                "stats": {"follower": {"applied": 93}},
            },
        }
        row = node_row(latest["primary"])
        assert row["limit"] == "32"
        assert row["ship-lag"] == "5"
        assert row["burn"] == "9.1%"
        assert row["p99"] == "2.1ms"
        assert "BROWNOUT" in row["state"]
        frame = render_frame(latest, now_s=10.0, stale_after_s=5.0)
        lines = frame.splitlines()
        assert lines[0] == "fleet: 2 node(s)"
        # tree order + indent: primary row above the follower's
        p_line = next(ln for ln in lines if "primary" in ln)
        f_line = next(ln for ln in lines if "leaf0" in ln)
        assert lines.index(p_line) < lines.index(f_line)
        assert f_line.startswith("    ")
        assert "STALE" in f_line  # last scrape 10s ago > 5s
        assert "93" in f_line

    def test_empty_frame(self):
        frame = render_frame({})
        assert "no nodes answered" in frame
