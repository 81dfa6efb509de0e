"""Tracing (ray_tpu/util/tracing.py).

Mirrors the reference's python/ray/tests/test_tracing.py: spans wrap
task/actor submission and execution, execution spans parent to the
submission span via the context carried in the task spec, and tracing
is strictly opt-in."""

import json
import os

import pytest

import ray_tpu
from ray_tpu.util import tracing


@pytest.fixture
def traced_runtime():
    # hermetic sampling: a prior test (or env override) leaving
    # tracing_sample_rate < 1.0 in the Config singleton would silently
    # drop spans here and turn the [0] lookups into flakes
    from ray_tpu._private.config import Config
    from ray_tpu.core import runtime as rt_mod

    cfg = Config.instance()
    old_rate = cfg.tracing_sample_rate
    cfg.tracing_sample_rate = 1.0
    tracing.reset_sampling()
    # defeat the fast-lane submit-span rate limit (one span per 10ms):
    # back-to-back submits — outer.remote() then inner.remote() inside
    # it — would otherwise record only the first span (the old flake)
    old_interval = rt_mod._SUBMIT_SPAN_MIN_INTERVAL_S
    rt_mod._SUBMIT_SPAN_MIN_INTERVAL_S = 0.0
    # the buffer is the process's: spans an earlier test of this worker
    # left in it (another ``add.remote``) are not this test's
    tracing.shutdown_tracing()
    tracing.setup_tracing()
    rt = ray_tpu.init(num_cpus=2)
    yield rt
    ray_tpu.shutdown()
    tracing.shutdown_tracing()
    rt_mod._SUBMIT_SPAN_MIN_INTERVAL_S = old_interval
    cfg.tracing_sample_rate = old_rate
    tracing.reset_sampling()


def _spans_named(pattern):
    # span names are module-qualified (task::<module>.<qualname>.<phase>)
    return [s for s in tracing.get_buffered_spans() if pattern in s.name]


def test_tracing_off_by_default():
    ray_tpu.init(num_cpus=1)

    @ray_tpu.remote
    def f():
        return 1

    assert ray_tpu.get(f.remote()) == 1
    assert not tracing.get_buffered_spans()
    ray_tpu.shutdown()


def _traced_add():
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(1, 2)) == 3


@pytest.mark.parametrize("entry", ["observability", "ray_tpu", "gcs"])
def test_timeline_renders_a_traced_tasks_spans(traced_runtime, tmp_path,
                                               entry):
    """The documented call returns what the program records: the
    tracing buffer's finished spans as Chrome complete events."""
    import json
    import os

    from ray_tpu import gcs, observability

    def finished():
        return {s.span_id for s in tracing.get_buffered_spans()
                if s.end_time is not None}

    _traced_add()
    before = finished()
    if entry == "observability":
        events = observability.timeline()
    elif entry == "gcs":
        events = gcs.timeline()
    else:
        with open(ray_tpu.timeline(str(tmp_path / "timeline.json"))) as f:
            events = json.load(f)
    submit, = [e for e in events if "add.remote" in e["name"]]
    execute, = [e for e in events if "add.execute" in e["name"]]
    for e in (submit, execute):
        assert e["ph"] == "X" and e["dur"] >= 0 and e["pid"] == os.getpid()
    assert execute["args"]["trace_id"] == submit["args"]["trace_id"]
    assert execute["args"]["parent_id"] == submit["args"]["span_id"]
    # one snapshot of the buffer, taken somewhere between the two reads
    # here: a raylet's tick (this runtime's, after the result is out, or
    # one an earlier test left running) records its ``scheduler.tick``
    # spans from its own thread whenever tracing is on
    rendered = {e["args"]["span_id"] for e in events}
    assert len(rendered) == len(events)
    assert before <= rendered <= finished()


def test_timeline_is_empty_when_tracing_is_off():
    from ray_tpu import observability

    ray_tpu.init(num_cpus=1)
    try:
        _traced_add()
        assert observability.timeline() == []
    finally:
        ray_tpu.shutdown()


def test_span_duration_is_monotonic_under_a_stepping_wall_clock(monkeypatch):
    """The wall clock places a span; its length is perf_counter's, so a
    clock stepped back during the span cannot make it negative."""
    wall = iter([1000.0] + [900.0] * 50)
    monkeypatch.setattr(tracing.time, "time", lambda: next(wall))
    tracing.setup_tracing()
    try:
        with tracing.start_span("stepped") as span:
            pass
        assert span.start_time == 1000.0
        assert 0 <= span.end_time - span.start_time < 5.0
        assert 0 <= span.to_dict()["duration_ms"] < 5000.0
    finally:
        tracing.shutdown_tracing()


def test_task_spans_and_parenting(traced_runtime):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(1, 2)) == 3
    submits = _spans_named("add.remote")
    execs = _spans_named("add.execute")
    assert len(submits) == 1 and len(execs) == 1
    # execution parents to submission, same trace
    assert execs[0].trace_id == submits[0].trace_id
    assert execs[0].parent_id == submits[0].span_id
    assert execs[0].status == "OK"
    assert execs[0].to_dict()["duration_ms"] >= 0


def test_actor_spans(traced_runtime):
    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    submits = _spans_named("A.ping.remote")
    execs = _spans_named("A.ping.execute")
    assert len(submits) == 1 and len(execs) == 1
    assert execs[0].trace_id == submits[0].trace_id


def test_error_span_status(traced_runtime):
    @ray_tpu.remote
    def boom():
        raise ValueError("x")

    with pytest.raises(ValueError):
        ray_tpu.get(boom.remote())
    execs = _spans_named("boom.execute")
    assert execs and execs[0].status.startswith("ERROR")


def test_nested_tasks_share_trace(traced_runtime):
    @ray_tpu.remote
    def inner():
        return 1

    @ray_tpu.remote
    def outer():
        return ray_tpu.get(inner.remote()) + 1

    assert ray_tpu.get(outer.remote()) == 2
    # the worker thread closes outer's execution span concurrently with
    # the driver's get() returning — wait for it to land in the buffer
    # instead of racing straight into the [0]
    import time as _time
    deadline = _time.monotonic() + 5.0
    while _time.monotonic() < deadline and not (
            _spans_named("outer.execute")
            and _spans_named("inner.remote")):
        _time.sleep(0.05)
    outer_exec = _spans_named("outer.execute")[0]
    inner_submit = _spans_named("inner.remote")[0]
    # inner was submitted from inside outer's execution span (same thread)
    assert inner_submit.trace_id == outer_exec.trace_id
    assert inner_submit.parent_id == outer_exec.span_id


def test_json_file_exporter(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    tracing.setup_tracing(tracing.JsonFileExporter(path))
    try:
        ray_tpu.init(num_cpus=1)

        @ray_tpu.remote
        def f():
            return 1

        ray_tpu.get(f.remote())
        ray_tpu.shutdown()
        assert os.path.exists(path)
        lines = [json.loads(ln) for ln in open(path)]
        assert any("f.execute" in ln["name"] for ln in lines)
    finally:
        tracing.shutdown_tracing()


def test_startup_hook():
    ray_tpu.init(num_cpus=1,
                 _tracing_startup_hook=tracing.setup_tracing)
    try:
        assert tracing.is_tracing_enabled()

        @ray_tpu.remote
        def f():
            return 7

        assert ray_tpu.get(f.remote()) == 7
        assert _spans_named("f.remote")
    finally:
        ray_tpu.shutdown()
        tracing.shutdown_tracing()


# ------------------------------------------------ sampling (seeded, RC03)
@pytest.fixture
def _sample_rate():
    from ray_tpu._private.config import Config

    cfg = Config.instance()
    old = cfg.tracing_sample_rate

    def set_rate(rate):
        cfg.tracing_sample_rate = rate
        tracing.reset_sampling()

    yield set_rate
    cfg.tracing_sample_rate = old
    tracing.reset_sampling()


@pytest.mark.tracing
def test_sampling_seeded_deterministic(_sample_rate):
    """Head-based sampling draws from the fault-plane seeded RNG: an
    active plan seed replays the exact same accept/reject sequence
    (raycheck RC03 — no unseeded randomness on control paths)."""
    from ray_tpu.cluster import fault_plane

    _sample_rate(0.3)
    fault_plane.install_plane(
        fault_plane.FaultPlane({"seed": 7, "rules": []}))
    try:
        def draw():
            tracing.reset_sampling()
            return [tracing._sample() for _ in range(300)]

        first, second = draw(), draw()
        assert first == second
        assert 30 < sum(first) < 180  # the rate is actually applied
    finally:
        fault_plane.install_plane(None)


@pytest.mark.tracing
def test_sampling_rate_edges(_sample_rate):
    _sample_rate(1.0)
    assert all(tracing._sample() for _ in range(10))
    _sample_rate(0.0)
    assert not any(tracing._sample() for _ in range(10))


@pytest.mark.tracing
def test_unsampled_trace_propagates_but_never_exports(_sample_rate):
    """rate=0: the root span still flows (children see the negative
    decision, the wire context says sampled=0) but nothing is buffered
    anywhere."""
    _sample_rate(0.0)
    tracing.setup_tracing()
    try:
        with tracing.start_span("root") as root:
            assert root is not None and not root.sampled
            ctx = tracing.current_context()
            assert ctx is not None and not ctx.sampled
            wire = ctx.to_dict()
            assert wire["sampled"] == "0"
            with tracing.start_span("child") as child:
                assert not child.sampled
        assert not tracing.get_buffered_spans()
        # server side of the same decision: no handler span either
        assert tracing.record_remote_span(
            "rpc.x", wire, 0.0, 1.0) is None
    finally:
        tracing.shutdown_tracing()


@pytest.mark.tracing
@pytest.mark.observability
def test_cross_process_trace_and_merged_timeline(tmp_path, _sample_rate):
    """One sampled driver call produces ONE trace crossing >= 3
    processes (driver, GCS server, raylet server), and `cli.py timeline
    --address` merges every node's flight-recorder buffer into a single
    chrome://tracing file."""
    import json as _json

    from ray_tpu.cluster.process_cluster import (
        ClusterClient,
        ProcessCluster,
    )
    from ray_tpu.cluster.rpc import RpcClient
    from ray_tpu.scripts.cli import main as cli_main

    _sample_rate(1.0)
    tracing.setup_tracing()
    cluster = ProcessCluster(heartbeat_period_ms=100)
    try:
        for _ in range(2):
            cluster.add_node(num_cpus=2)
        cluster.wait_for_nodes(2)
        client = ClusterClient(cluster.gcs_address)
        try:
            with tracing.start_span("driver.request") as root:
                assert root.sampled
                trace_id = root.trace_id
                ref = client.submit(lambda: 40 + 2, ())
                assert client.get(ref) == 42
                client.cluster_view()  # a GCS hop inside the same trace
        finally:
            client.close()

        # driver-side spans for the trace live in this process's buffer
        driver_spans = [s for s in tracing.get_buffered_spans()
                        if s.trace_id == trace_id]
        assert driver_spans

        gcs = RpcClient(cluster.gcs_address)
        try:
            dumps = gcs.call("collect_timeline", timeout=30.0)["dumps"]
        finally:
            gcs.close()
        assert len(dumps) == 3  # the GCS itself + both raylets
        assert all("error" not in d for d in dumps)
        by_role = {}
        for dump in dumps:
            for span in dump["spans"]:
                if span["trace_id"] == trace_id:
                    by_role.setdefault(dump["role"], []).append(span)
        assert "gcs" in by_role, "GCS recorded no span for the trace"
        assert "raylet" in by_role, "no raylet recorded the trace"
        # >= 3 distinct processes participated in the one trace
        pids = {d["pid"] for d in dumps
                if any(s["trace_id"] == trace_id for s in d["spans"])}
        pids.add(os.getpid())
        assert len(pids) >= 3
        # the executing raylet recorded the task body itself
        all_remote = [s for spans in by_role.values() for s in spans]
        assert any(s["name"] == "task.execute" for s in all_remote)
        assert any(s["name"].startswith("rpc.") for s in all_remote)
        # every remote span parents back into the driver's trace
        assert all(s["parent_id"] for s in all_remote)

        # the merged chrome://tracing file covers every node
        out = str(tmp_path / "timeline.json")
        assert cli_main(["timeline", "--address", cluster.gcs_address,
                         "--output", out]) == 0
        data = _json.loads(open(out).read())
        procs = [e["args"]["name"] for e in data["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"]
        assert len(procs) == 3  # one process lane per dump
        # raylet dumps carry their live thread roots: each becomes a
        # named thread lane, labeled with the SAME root label raycheck
        # RC16/RC17 reports use (threads.root_label one-source-of-truth)
        tnames = [e["args"]["name"] for e in data["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "thread_name"]
        assert any("raylet_server.RayletServer._heartbeat_loop" in n
                   for n in tnames), tnames
        merged = [e for e in data["traceEvents"]
                  if e["ph"] == "X" and e["args"].get("trace_id")
                  == trace_id]
        assert {e["pid"] for e in merged} >= {1, 2} or len(
            {e["pid"] for e in merged}) >= 2
    finally:
        cluster.shutdown()
        tracing.shutdown_tracing()


@pytest.mark.tracing
@pytest.mark.observability
def test_scheduler_tick_anatomy_spans_and_histogram(_sample_rate):
    """A traced busy tick records the scheduler.tick span tree (root +
    named phase children laid end to end) and feeds the
    scheduler_phase_ms histogram."""
    from ray_tpu.core.raylet import _TickPhases
    from ray_tpu.observability.metrics import scheduler_phase_ms

    _sample_rate(1.0)
    tracing.setup_tracing()
    # defeat the per-raylet anatomy rate limit for the whole drive
    old_interval = _TickPhases.MIN_INTERVAL_S
    _TickPhases.MIN_INTERVAL_S = 0.0
    before = {p: scheduler_phase_ms.count_value(tags={"phase": p})
              for p in _TickPhases.PHASES}
    try:
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote
        def f(x):
            return x + 1

        assert ray_tpu.get([f.remote(i) for i in range(32)]) == list(
            range(1, 33))
        roots = [s for s in tracing.get_buffered_spans()
                 if s.name == "scheduler.tick"]
        assert roots, "no tick anatomy span tree recorded"
        root = roots[-1]
        children = [s for s in tracing.get_buffered_spans()
                    if s.parent_id == root.span_id]
        assert children
        phase_names = {c.name for c in children}
        assert phase_names <= {f"scheduler.tick.{p}"
                               for p in _TickPhases.PHASES}
        # children tile the root: laid end-to-end from the root start
        for c in children:
            assert c.trace_id == root.trace_id
            assert c.start_time >= root.start_time - 1e-6
        observed = sum(
            scheduler_phase_ms.count_value(tags={"phase": p}) - before[p]
            for p in _TickPhases.PHASES)
        assert observed > 0
    finally:
        _TickPhases.MIN_INTERVAL_S = old_interval
        ray_tpu.shutdown()
        tracing.shutdown_tracing()


@pytest.mark.tracing
def test_rpc_trace_kwarg_rides_only_sampled(_sample_rate):
    """The client injects ``_trace`` onto RPC frames only for sampled
    contexts; the server pops it before schema validation (RC07) and
    records an rpc.<method> handler span."""
    from ray_tpu.cluster.rpc import RpcClient, RpcServer

    calls = {}

    class Svc:
        def ping(self):
            calls["seen"] = True
            return {"ok": True}

    server = RpcServer("127.0.0.1", 0)
    server.register("ping", Svc().ping)
    server.start()
    _sample_rate(1.0)
    tracing.setup_tracing()
    try:
        client = RpcClient(f"127.0.0.1:{server.port}")
        try:
            with tracing.start_span("driver.root") as root:
                client.call("ping", timeout=5.0)
            # the server process IS this process: its handler span is
            # in the buffer, parented into the driver trace
            handler = [s for s in tracing.get_buffered_spans()
                       if s.name == "rpc.ping"]
            assert handler and handler[0].trace_id == root.trace_id
            assert "queue_wait_ms" in handler[0].attributes
            assert handler[0].attributes["method"] == "ping"
        finally:
            client.close()
    finally:
        server.stop()
        tracing.shutdown_tracing()
