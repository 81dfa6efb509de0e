"""Tests for metrics/events/state dump (modeled on the
reference's tests/test_metrics_agent.py, test_tracing.py scenarios)."""

import json
import urllib.request

import pytest

import ray_tpu
from ray_tpu import gcs
from ray_tpu.observability import (
    Counter,
    Gauge,
    Histogram,
    Severity,
    emit,
    global_event_log,
    prometheus_text,
    start_metrics_server,
    timeline,
)


def test_counter_gauge_histogram():
    c = Counter("t_requests", "reqs", tag_keys=("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2, tags={"route": "/a"})
    c.inc(tags={"route": "/b"})
    assert c.series()[("/a",)] == 3
    g = Gauge("t_temp", "temp")
    g.set(42.5)
    assert g.series()[()] == 42.5
    h = Histogram("t_lat", "latency", boundaries=(0.1, 1, 10))
    for v in (0.05, 0.5, 5, 50):
        h.observe(v)
    assert h.percentile(50) in (1, 10)


def test_prometheus_text_format():
    c = Counter("t_fmt_total", "desc", tag_keys=("k",))
    c.inc(tags={"k": "v"})
    text = prometheus_text()
    assert "# TYPE t_fmt_total counter" in text
    assert 't_fmt_total{k="v"} 1.0' in text


def test_metrics_server():
    Counter("t_served", "d").inc()
    server, port = start_metrics_server()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as resp:
            body = resp.read().decode()
        assert "t_served" in body
    finally:
        server.shutdown()


def test_core_metrics_instrumented(ray_init):
    from ray_tpu.observability.metrics import (
        scheduling_latency,
        tasks_finished,
        tasks_submitted,
    )

    before = tasks_submitted.series().get((), 0)

    @ray_tpu.remote
    def f():
        return 1

    ray_tpu.get([f.remote() for _ in range(5)])
    assert tasks_submitted.series().get((), 0) >= before + 5
    assert tasks_finished.series().get((), 0) >= 5
    assert scheduling_latency.percentile(99) is not None


def test_events():
    global_event_log.clear()
    emit("node", "node added", Severity.INFO, node_id="abc")
    emit("node", "node died", Severity.ERROR, node_id="abc")
    assert len(global_event_log.list(label="node")) == 2
    errors = global_event_log.list(min_severity=Severity.ERROR)
    assert len(errors) == 1 and errors[0]["message"] == "node died"


def test_global_state_tables(ray_init):
    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    a = A.options(name="state_actor").remote()
    ray_tpu.get([a.ping.remote()])
    actors = gcs.state.actor_table()
    assert any(rec["Name"] == "state_actor" and rec["State"] == "ALIVE"
               for rec in actors.values())
    nodes = gcs.state.node_table()
    assert len(nodes) == 1 and nodes[0]["Alive"]
    ref = ray_tpu.put(list(range(100)))
    table = gcs.state.object_table()
    assert ref.id().hex() in table
    summary = gcs.memory_summary()
    assert "objects tracked" in summary
    from ray_tpu.util.placement_group import placement_group

    pg = placement_group([{"CPU": 1}])
    pg.wait(5)
    pgs = gcs.state.placement_group_table()
    assert any(rec["State"] == "CREATED" for rec in pgs.values())


def test_dashboard_endpoints(ray_init):
    from ray_tpu.observability.dashboard import start_dashboard

    @ray_tpu.remote
    class D:
        def ping(self):
            return 1

    d = D.options(name="dash_actor").remote()
    ray_tpu.get([d.ping.remote()])
    dash = start_dashboard()
    try:
        for route in ("/api/cluster_status", "/api/nodes", "/api/actors",
                      "/api/placement_groups", "/api/objects",
                      "/api/events"):
            with urllib.request.urlopen(dash.url + route,
                                        timeout=5) as resp:
                payload = json.loads(resp.read())
            assert payload is not None, route
        with urllib.request.urlopen(dash.url + "/metrics",
                                    timeout=5) as resp:
            assert b"ray_tpu" in resp.read()
        with urllib.request.urlopen(dash.url + "/api/actors",
                                    timeout=5) as resp:
            actors = json.loads(resp.read())
        assert any(a["Name"] == "dash_actor" for a in actors.values())
    finally:
        dash.stop()


def test_user_metrics_api():
    """reference: python/ray/util/metrics.py — user-defined metrics join
    the system registry and the Prometheus exposition."""
    from ray_tpu.observability import prometheus_text
    from ray_tpu.util.metrics import Counter, Gauge, Histogram

    c = Counter("app_reqs_test", description="requests",
                tag_keys=("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2.0, tags={"route": "/a"})
    g = Gauge("app_gauge_test")
    g.set(7.5)
    h = Histogram("app_hist_test", boundaries=(1, 10))
    h.observe(3.0)
    text = prometheus_text()
    assert 'app_reqs_test{route="/a"} 3.0' in text
    assert "app_gauge_test 7.5" in text
    assert "app_hist_test" in text


# -------------------------------------------------- observability plane
@pytest.mark.observability
def test_flight_recorder_ring_and_dump(tmp_path):
    from ray_tpu.observability.flight_recorder import FlightRecorder

    rec = FlightRecorder(capacity=3)
    for i in range(5):
        rec.record_span({"name": f"s{i}", "trace_id": "t",
                         "span_id": f"{i}", "start_time": float(i),
                         "end_time": float(i) + 0.5})
    rec.record_event({"name": "boom", "timestamp": 1.0})
    snap = rec.snapshot()
    assert [s["name"] for s in snap["spans"]] == ["s2", "s3", "s4"]
    assert snap["dropped"] == 2  # honest about evicted history
    path = rec.dump(str(tmp_path / "dump.jsonl"), reason="test")
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[0]["kind"] == "flight_recorder_dump"
    assert lines[0]["reason"] == "test"
    assert lines[0]["dropped"] == 2
    kinds = [ln["kind"] for ln in lines[1:]]
    assert kinds.count("span") == 3 and kinds.count("event") == 1


@pytest.mark.observability
def test_flight_recorder_sigusr2_dump(tmp_path, monkeypatch):
    """kill -USR2 <pid> makes the process drop its black box to disk
    without dying — the live-debugging workflow from README."""
    import os as _os
    import signal
    import time as _time

    from ray_tpu.observability.flight_recorder import FlightRecorder

    monkeypatch.setenv("TMPDIR", str(tmp_path))
    rec = FlightRecorder(capacity=8)
    rec.record_span({"name": "before_signal", "start_time": 1.0,
                     "end_time": 2.0})
    rec.install()
    try:
        _os.kill(_os.getpid(), signal.SIGUSR2)
        deadline = _time.monotonic() + 5
        dumps = []
        while _time.monotonic() < deadline and not dumps:
            dumps = list(tmp_path.glob("ray_tpu_flight_*.jsonl"))
            _time.sleep(0.01)
        assert dumps, "SIGUSR2 produced no flight-recorder dump"
        lines = [json.loads(ln) for ln in open(dumps[0])]
        assert lines[0]["reason"] == "SIGUSR2"
        assert any(ln.get("name") == "before_signal" for ln in lines)
    finally:
        signal.signal(signal.SIGUSR2, signal.SIG_DFL)


@pytest.mark.observability
def test_fatal_event_dumps_black_box(tmp_path, monkeypatch):
    """A FATAL-severity event triggers an automatic crash dump while
    the process can still write (events.emit → record_fatal)."""
    from ray_tpu.observability.flight_recorder import global_recorder

    monkeypatch.setenv("TMPDIR", str(tmp_path))
    global_recorder.record_span({"name": "led_up_to_it",
                                 "start_time": 1.0, "end_time": 2.0})
    emit("crash", "irrecoverable store corruption", Severity.FATAL,
         node_id="n1")
    dumps = list(tmp_path.glob("ray_tpu_flight_*.jsonl"))
    assert dumps, "FATAL event produced no dump"
    lines = [json.loads(ln) for ln in open(dumps[0])]
    assert lines[0]["reason"] == "fatal_event"
    assert any(ln.get("kind") == "event"
               and ln.get("message") == "irrecoverable store corruption"
               for ln in lines)
    assert any(ln.get("name") == "led_up_to_it" for ln in lines)


@pytest.mark.observability
def test_merge_chrome_trace_corrects_clock_offset():
    """Two nodes observed the same instant under skewed wall clocks;
    the per-dump heartbeat-measured offset puts both spans on the GCS
    reference axis."""
    from ray_tpu.observability.flight_recorder import merge_chrome_trace

    span = {"name": "x", "trace_id": "t", "span_id": "a",
            "parent_id": None}
    dumps = [
        {"node_id": "gcs", "role": "gcs", "clock_offset_s": 0.0,
         "spans": [dict(span, start_time=100.0, end_time=100.5)],
         "events": []},
        # node clock runs 2s behind the GCS: offset = gcs - local = +2
        {"node_id": "n1", "role": "raylet", "clock_offset_s": 2.0,
         "spans": [dict(span, span_id="b", start_time=98.0,
                        end_time=98.5)],
         "events": [{"name": "mark", "timestamp": 98.0}]},
        {"node_id": "n2", "role": "raylet",
         "error": "node unreachable"},
    ]
    trace = merge_chrome_trace(dumps)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 2
    # offset-corrected: both spans land on the same reference instant
    assert abs(xs[0]["ts"] - xs[1]["ts"]) < 1e-6
    marks = [e for e in trace["traceEvents"] if e["ph"] == "i"]
    assert marks and abs(marks[0]["ts"] - 100.0 * 1e6) < 1e-6
    labels = [e["args"]["name"] for e in trace["traceEvents"]
              if e["ph"] == "M"]
    assert len(labels) == 3 and any("UNREACHABLE" in n for n in labels)


def _parse_prometheus(text):
    """Tiny exposition-format parser: unescapes label values, so the
    test asserts a true ROUND TRIP (format → parse → original values),
    pinning the escaping rules rather than string fragments."""
    import re

    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (.+)$",
                     line)
        assert m, f"unparseable exposition line: {line!r}"
        name, labelstr, value = m.groups()
        labels = {}
        if labelstr:
            for lm in re.finditer(
                    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:\\.|[^"\\])*)"',
                    labelstr):
                k, v = lm.group(1), lm.group(2)
                labels[k] = (v.replace("\\n", "\n")
                             .replace('\\"', '"').replace("\\\\", "\\"))
        out[(name, tuple(sorted(labels.items())))] = float(value)
    return out


@pytest.mark.observability
def test_prometheus_exposition_round_trip():
    """Tag values containing quotes/backslashes/newlines survive the
    exposition format, and histogram ``le`` bounds render per spec
    ("1.0", "+Inf" — never Python's repr of an int)."""
    nasty = 'he said "hi"\\once\nthen left'
    c = Counter("t_rt_total", "d", tag_keys=("msg",))
    c.inc(3, tags={"msg": nasty})
    h = Histogram("t_rt_lat", "d", boundaries=(1, 2.5))
    for v in (0.5, 2.0, 99.0):
        h.observe(v)
    parsed = _parse_prometheus(prometheus_text())
    assert parsed[("t_rt_total", (("msg", nasty),))] == 3.0
    # le is a spec-format float literal, buckets are cumulative
    assert parsed[("t_rt_lat_bucket", (("le", "1.0"),))] == 1.0
    assert parsed[("t_rt_lat_bucket", (("le", "2.5"),))] == 2.0
    assert parsed[("t_rt_lat_bucket", (("le", "+Inf"),))] == 3.0
    assert parsed[("t_rt_lat_sum", ())] == pytest.approx(101.5)
    assert parsed[("t_rt_lat_count", ())] == 3.0


@pytest.mark.observability
def test_histogram_percentile_edge_semantics():
    """percentile() returns bucket UPPER BOUNDS (docstring contract):
    empty → None, single sample → its bucket bound for every q,
    beyond-last-boundary → inf."""
    h = Histogram("t_pct_edge", "d", boundaries=(1, 10, 100))
    assert h.percentile(50) is None  # empty series
    h.observe(5.0)
    for q in (1, 50, 99):  # one sample: its bucket bound, even > sample
        assert h.percentile(q) == 10
    h2 = Histogram("t_pct_over", "d", boundaries=(1, 10))
    h2.observe(1e6)  # overflow bucket has no finite upper bound
    assert h2.percentile(99) == float("inf")


@pytest.mark.observability
def test_rpc_server_metrics_tagged_by_method_and_role():
    """The plane's per-method histograms exist and carry the
    (method, dst_kind) tag scheme."""
    from ray_tpu.observability.metrics import (
        rpc_request_bytes,
        rpc_server_latency_ms,
        scheduler_phase_ms,
    )

    assert rpc_server_latency_ms.tag_keys == ("method", "dst_kind")
    assert rpc_request_bytes.tag_keys == ("method", "dst_kind")
    assert scheduler_phase_ms.tag_keys == ("phase",)


def test_dashboard_serves_web_ui():
    """The head serves a human-facing page at / (reference:
    dashboard/client SPA over the same REST endpoints)."""
    import urllib.request

    from ray_tpu.cluster.process_cluster import ProcessCluster
    from ray_tpu.observability.dashboard_head import DashboardHead

    cluster = ProcessCluster(heartbeat_period_ms=200,
                             num_heartbeats_timeout=30)
    try:
        cluster.add_node(num_cpus=1)
        cluster.wait_for_nodes(1)
        head = DashboardHead(cluster.gcs_address)
        try:
            with urllib.request.urlopen(f"{head.url}/", timeout=10) as r:
                body = r.read().decode()
                assert r.headers["Content-Type"].startswith("text/html")
            assert "ray_tpu dashboard" in body
            assert "/api/nodes" in body  # consumes the REST surface
        finally:
            head.stop()
    finally:
        cluster.shutdown()
