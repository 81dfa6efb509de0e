"""North-star benchmark suite. Prints exactly ONE JSON line.

Headline metric — the scheduling plane, measured HONESTLY: a 100k-task
queue in 32 scheduling classes over a 256-node x 8-resource cluster is
*drained*: every tick runs the fused device solve (scheduler/policy.py
schedule_tick_fused), then the exact int64 oversubscription repair, then
COMMITS the placements — the queue shrinks, node availability drops, and
tasks placed in the previous tick complete and free their resources
(a one-tick task pipeline). The timed region covers solve + repair +
commit. Reported: sustained placements/s over the full drain and per-tick
latency percentiles.

Baseline proxy (BASELINE.md: the reference publishes no number for this
metric): the closest single-node figure is 1M queued tasks drained in
175.02 s ~= 5,714 tasks/s on an m4.16xlarge
(release/release_logs/1.9.0/scalability/single_node.json).

The train step and its kernels are not measured here: their yardstick is
``BENCHMARK.json`` (``python3 -m benchmark.run``), their record
``PERF_LEDGER.jsonl`` and ``PERF.md``.
"""

import json
import os
import sys
import time

import numpy as np


def _process_shed_total() -> float:
    """Sum of this process's overload-plane shed counters (task
    backpressure + RPC admission sheds). Bench rows sample it before
    and after their timed region: the delta must stay 0 on the happy
    path — a refactor that starts shedding under normal load is a
    regression the overload plane would otherwise mask as 'slow'."""
    from ray_tpu.observability.metrics import get_metric

    total = 0.0
    for name in ("ray_tpu_tasks_shed", "ray_tpu_rpc_requests_shed"):
        m = get_metric(name)
        if m is not None:
            total += sum(m.series().values())
    return total


def _integrity_store_micro_pct(nbytes: int = 1024 * 1024,
                               iters: int = 8) -> float:
    """Checksum cost at the STORE layer: the same put+get loop through
    a ByteStore with the integrity plane on vs off (one digest at put,
    fused into the admit copy — byte_store._admit_locked). With the
    hardware CRC32C backend (integrity.CHECKSUM_IMPL == "crc32c") the
    digest runs near memcpy speed and this prices out to a few tens of
    percent of a bare heap admit; on the zlib.crc32 fallback several-
    hundred percent is the expected intrinsic cost. At the transfer
    seams the same crc is amortized against pickling + TCP and prices
    out to low single digits of the broadcast wall time (broadcast_
    integrity_overhead_pct). Tracked so a digest-backend or accidental
    double-hash regression shows up in the trajectory."""
    from ray_tpu._private.config import Config
    from ray_tpu.cluster.byte_store import ByteStore

    payload = bytearray(np.random.default_rng(0).integers(
        0, 255, size=nbytes, dtype=np.uint8).tobytes())
    cfg = Config.instance()
    old = cfg.integrity_enabled
    times = {}
    try:
        for flag in (False, True):
            cfg.integrity_enabled = flag
            store = ByteStore(capacity=4 * nbytes, use_shm=False)
            try:
                store.put(b"warm" + b"\x00" * 24, payload)  # warm-up
                t0 = time.perf_counter()
                for i in range(iters):
                    oid = i.to_bytes(28, "big")
                    store.put(oid, payload)
                    store.get(oid)
                    store.delete(oid)
                times[flag] = time.perf_counter() - t0
            finally:
                store.close()
    finally:
        cfg.integrity_enabled = old
    if not times[False]:
        return 0.0
    return round(100.0 * (times[True] - times[False]) / times[False], 1)


def _tick_anatomy_and_tracing_overhead() -> dict:
    """Scheduler tick anatomy + observability-plane cost, on the LIVE
    tier: a synthetic multi-node cluster drained through the actual
    ``Raylet.schedule_tick`` (the pipeline bench's fused solve sits
    inside), once with ``observability_plane_enabled`` off and once on.

    Reports (a) ``tracing_overhead_pct`` — the plane's whole cost on
    the tick wall (phase timers + histogram observes; bar: <= 2%, and
    the off drive IS the zero-overhead baseline), and (b) the per-phase
    breakdown from the ``scheduler_phase_ms`` histogram next to the
    externally-timed tick wall — ``tick_phase_coverage_pct`` must stay
    >= 90 or the named phases no longer account for where tick time
    goes."""
    from ray_tpu._private.config import Config
    from ray_tpu._private.ids import JobID, NodeID, TaskID
    from ray_tpu.core.raylet import ClusterState, Raylet, _PendingTask
    from ray_tpu.core.task_spec import (
        TaskKind,
        TaskSpec,
        scheduling_class_of,
    )
    from ray_tpu.observability.metrics import scheduler_phase_ms

    n_nodes, n_tasks, n_classes = 64, 8_192, 16

    class _FrozenDeps:
        # dependencies never ready: placements commit, nothing executes,
        # so the timed region is pure scheduling pipeline
        def wait_ready(self, spec, callback):
            pass

        def wait_ready_batch(self, tasks, batch_callback, callback):
            # fastlane batch fan-out seam: same freeze, so the ON
            # drive measures the bulk dispatch path it would really run
            pass

    def _build():
        rng = np.random.default_rng(0)
        cluster = ClusterState()
        deps = _FrozenDeps()
        head = None
        for _ in range(n_nodes):
            # every task demands PIN, which only the head offers: the
            # full 64-node batched solve runs, but placements stay
            # local — a spillback would recursively tick the TARGET
            # raylet and double-count its phases against our wall
            resources = ({"CPU": 1e6, "PIN": 1e6} if head is None
                         else {"CPU": float(rng.integers(8, 32))})
            raylet = Raylet(NodeID.from_random(), resources, cluster,
                            deps)
            cluster.register(raylet)
            head = head or raylet
        demands = [{"CPU": float(rng.integers(1, 4)), "PIN": 0.001}
                   for _ in range(n_classes)]
        job = JobID.from_int(9)
        parent = TaskID.for_task(None)
        with head._lock:
            for i in range(n_tasks):
                spec = TaskSpec(
                    kind=TaskKind.NORMAL, task_id=TaskID.for_task(None),
                    job_id=job, parent_task_id=parent, name=f"b{i}",
                    resources=dict(demands[i % n_classes]))
                spec.scheduling_class = scheduling_class_of(
                    spec.resource_request(cluster.ids))
                task = _PendingTask(spec, lambda r, w: None, 0)
                head._pending.append(task)
                head._by_task_id[spec.task_id] = task
        return head

    from ray_tpu.core.raylet import _TickPhases

    def _drive(plane_on: bool) -> float:
        cfg = Config.instance()
        old = cfg.observability_plane_enabled
        cfg.observability_plane_enabled = plane_on
        try:
            head = _build()
            wall = 0.0
            for _ in range(64):
                t0 = time.perf_counter()
                head.schedule_tick()
                wall += time.perf_counter() - t0
                with head._lock:
                    if not head._pending:
                        break
            return wall
        finally:
            cfg.observability_plane_enabled = old

    def _phase_sums() -> dict:
        return {p: scheduler_phase_ms.sum_value(tags={"phase": p}) or 0.0
                for p in _TickPhases.PHASES}

    # defeat the anatomy rate limit: the interleaved drives run many
    # ticks per MIN_INTERVAL_S, and a sampled-out tick would leak its
    # wall time out of the phase histogram and sink coverage
    old_interval = _TickPhases.MIN_INTERVAL_S
    _TickPhases.MIN_INTERVAL_S = 0.0
    try:
        _drive(True)  # warmup (jit/import residue on both paths)
        _drive(False)
        # interleave the on/off drives (best-of-5 each) so drift in the
        # process — allocator state, CPU clocks — hits both sides alike
        walls_on, walls_off = [], []
        before = _phase_sums()
        for _ in range(5):
            walls_off.append(_drive(False))
            walls_on.append(_drive(True))
        after = _phase_sums()
    finally:
        _TickPhases.MIN_INTERVAL_S = old_interval
    t_off, t_on = min(walls_off), min(walls_on)
    phase_ms = {p: round(after[p] - before[p], 2) for p in after}
    covered_ms = sum(phase_ms.values())
    wall_on_ms = sum(walls_on) * 1e3
    return {
        "tracing_overhead_pct": (round(100.0 * (t_on - t_off) / t_off, 1)
                                 if t_off else 0.0),
        "tick_phase_ms": phase_ms,
        "tick_phase_coverage_pct": (round(100.0 * covered_ms
                                          / wall_on_ms, 1)
                                    if wall_on_ms else 0.0),
    }


def _submit_micro_tracing_overhead_pct() -> float:
    """The submit micro (tiny no-op tasks through the in-process
    runtime, ray_perf's single_client row) with the observability plane
    on vs off — the per-submit cost of the plane's guards on the
    submit/execute path (bar: <= 2%)."""
    import ray_tpu
    from ray_tpu._private.config import Config

    started_here = not ray_tpu.is_initialized()
    if started_here:
        ray_tpu.init()

    @ray_tpu.remote
    def tiny():
        return b"ok"

    def best_rate() -> float:
        n, best = 300, 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            ray_tpu.get([tiny.remote() for _ in range(n)])
            best = max(best, n / (time.perf_counter() - t0))
        return best

    cfg = Config.instance()
    old = cfg.observability_plane_enabled
    try:
        best_rate()  # warmup
        cfg.observability_plane_enabled = False
        r_off = best_rate()
        cfg.observability_plane_enabled = True
        r_on = best_rate()
    finally:
        cfg.observability_plane_enabled = old
        if started_here:
            ray_tpu.shutdown()
    # time-per-task overhead: (1/r_on - 1/r_off) / (1/r_off)
    return round(100.0 * (r_off / r_on - 1.0), 1) if r_on else 0.0


def _submit_attribution_us() -> dict:
    """Where a single ``f.remote()`` microsecond goes (dispatch fast
    lane, r07): per-submit wall attributed at the REAL seam boundaries
    of the in-process tier —

      encode : remote() entry -> ``_submit_to_raylet`` entry (options
               resolve, TaskSpec build, return-id mint, refcounting;
               the part the TaskTemplate freeze attacks)
      rpc    : ``_submit_to_raylet`` entry -> ``Raylet.submit`` entry
               (routing + the backpressure guard wrapper)
      lock   : ``Raylet.submit`` entry -> ``WorkerPool.submit`` entry
               (admission check, node-lock allocate, cluster sync,
               dep check)
      wakeup : inside ``WorkerPool.submit`` (idle-worker reserve or
               spawn, run-queue put, worker notify)

    measured over a burst of no-op submits with the fast lane ON;
    phase stamps only attribute main-thread submits (worker-thread
    handoffs re-enter the same seams and are excluded).

    The on/off A-B columns (``driver_submit_us_{off,on}``) isolate the
    DRIVER-side submit path — the burst runs with delivery into the
    raylet stubbed out, so executing no-ops can't steal the GIL from
    the timed region and the columns compare exactly what the
    TaskTemplate freeze attacks: options resolve + spec build +
    id/refcount mint per call. The OFF column is the exact
    pre-fast-lane path, so ``driver_submit_speedup_x`` is the
    acceptance A/B (bar: >= 2x cheaper per call)."""
    import threading

    import ray_tpu
    from ray_tpu._private.config import Config
    from ray_tpu.core import runtime as rt_mod

    started_here = not ray_tpu.is_initialized()
    if started_here:
        ray_tpu.init()

    @ray_tpu.remote
    def tiny():
        return None

    rt = rt_mod.global_runtime
    raylet = rt.head_raylet
    pool = raylet.worker_pool
    main_tid = threading.get_ident()
    acc = {"encode": 0.0, "rpc": 0.0, "lock": 0.0, "wakeup": 0.0}
    state = {"t0": 0.0, "t_str": 0.0, "t_sub": 0.0}
    orig_str = rt._submit_to_raylet
    orig_sub = raylet.submit
    orig_ws = pool.submit

    def str_wrap(spec):
        if threading.get_ident() == main_tid:
            t = time.perf_counter()
            state["t_str"] = t
            acc["encode"] += t - state["t0"]
        return orig_str(spec)

    def sub_wrap(spec, on_dispatch, spillback_count=0):
        if threading.get_ident() == main_tid:
            t = time.perf_counter()
            acc["rpc"] += t - state["t_str"]
            state["t_sub"] = t
            state["armed"] = True
        return orig_sub(spec, on_dispatch, spillback_count)

    def ws_wrap(fn, *args):
        # one stamp per submit: a backlog drain inside schedule_tick
        # re-enters this seam on the same thread, and re-attributing it
        # would double-count the lock span
        if threading.get_ident() != main_tid or not state.get("armed"):
            return orig_ws(fn, *args)
        state["armed"] = False
        t = time.perf_counter()
        acc["lock"] += t - state["t_sub"]
        out = orig_ws(fn, *args)
        acc["wakeup"] += time.perf_counter() - t
        return out

    def burst(n: int = 400) -> float:
        """Mean per-submit µs over the burst (submit wall only; the
        drain get() is outside the timed region)."""
        refs = []
        wall = 0.0
        for _ in range(n):
            t0 = time.perf_counter()
            state["t0"] = t0
            refs.append(tiny.remote())
            wall += time.perf_counter() - t0
        ray_tpu.get(refs)
        return wall / n * 1e6

    def driver_burst(n: int = 1000) -> float:
        """Per-call µs of the driver submit path alone: delivery into
        the raylet is a no-op sink, so nothing executes and nothing
        contends — the timed region is options resolve + spec build +
        id/refcount mint, identically bounded in both modes. Refs are
        HELD across the burst (a real driver holds them until get), so
        ref destruction is not billed to the submit."""
        rt._submit_to_raylet = lambda spec: None
        refs = []
        append = refs.append
        try:
            t0 = time.perf_counter()
            for _ in range(n):
                append(tiny.remote())
            return (time.perf_counter() - t0) / n * 1e6
        finally:
            rt._submit_to_raylet = orig_str
            del refs

    cfg = Config.instance()
    old = cfg.dispatch_fastlane_enabled
    try:
        burst()  # warmup (import/jit residue, pool spin-up)
        driver_burst(200)
        best_on, best_off = float("inf"), float("inf")
        for _ in range(5):
            cfg._set("dispatch_fastlane_enabled", False)
            best_off = min(best_off, driver_burst())
            cfg._set("dispatch_fastlane_enabled", True)
            best_on = min(best_on, driver_burst())
        # attribution pass: seams stamped, fast lane ON, real delivery
        rt._submit_to_raylet = str_wrap
        raylet.submit = sub_wrap
        pool.submit = ws_wrap
        n_attr = 400
        try:
            total_attr = burst(n_attr)
        finally:
            rt._submit_to_raylet = orig_str
            raylet.submit = orig_sub
            pool.submit = orig_ws
    finally:
        cfg._set("dispatch_fastlane_enabled", old)
        if started_here:
            ray_tpu.shutdown()
    phases = {k: round(v / n_attr * 1e6, 2) for k, v in acc.items()}
    phases["other"] = round(
        max(0.0, total_attr - sum(phases.values())), 2)
    return {
        "driver_submit_us_off": round(best_off, 2),
        "driver_submit_us_on": round(best_on, 2),
        "driver_submit_speedup_x": (round(best_off / best_on, 2)
                                    if best_on else 0.0),
        "submit_us_e2e": round(total_attr, 2),
        "submit_phase_us": phases,
    }


def _pipeline_ab_live() -> dict:
    """Tentpole A-B (r06): the SAME seeded 100k-task queue drained
    through the LIVE Raylet tier twice — ``scheduler_pipeline_enabled``
    off (the exact pre-pipeline single-buffered tick) and on (the
    drain loop: double-buffered device solves against the
    DeviceMatrixMirror's delta-synced buffers, vectorized commit and
    batched spillback). Same cluster seed, same task stream, same
    config otherwise.

    Reports, per mode: sustained placements/s and drain wall; plus
    ``solve_commit_overlap_pct`` — the share of solve-adjacent time the
    host spent COMMITTING while a device solve was in flight (overlap
    phase / (overlap + blocked-pull solve phase); 0 by construction
    when off, where the tick blocks on the solve before committing) —
    and ``matrix_upload_bytes_per_tick_{off,on}``: off re-coerces and
    re-uploads the full total+available+alive matrix every device
    solve; on uploads only the mirror's dirty-row deltas (full re-syncs
    every scheduler_matrix_sync_period refreshes)."""
    from ray_tpu._private.config import Config
    from ray_tpu._private.ids import JobID, NodeID, TaskID
    from ray_tpu.core.raylet import (
        ClusterState,
        Raylet,
        _PendingTask,
        _TickPhases,
    )
    from ray_tpu.core.task_spec import (
        TaskKind,
        TaskSpec,
        scheduling_class_of,
    )
    from ray_tpu.observability.metrics import scheduler_phase_ms

    n_nodes, n_tasks, n_classes = 256, 100_000, 32

    class _FrozenDeps:
        # dependencies never ready: placements commit and hold
        # resources, nothing executes — the drive is pure scheduling
        def wait_ready(self, spec, callback):
            pass

        def wait_ready_batch(self, tasks, batch_callback, callback):
            # fastlane batch fan-out seam: same freeze, so the ON
            # drive measures the bulk dispatch path it would really run
            pass

    def _build():
        rng = np.random.default_rng(0)
        cluster = ClusterState()
        deps = _FrozenDeps()
        raylets = []
        head = None
        for _ in range(n_nodes):
            # every demand includes PIN, which only the head offers:
            # the full 256-node solve runs every batch, but placements
            # stay local — the A-B measures the tick pipeline itself
            # (solve/commit/mirror/dispatch), not the per-task
            # spillback resolution a capacity-starved head would
            # degenerate into (that path has its own tests)
            resources = ({"CPU": 1e6, "PIN": 1e6} if head is None
                         else {"CPU": float(rng.integers(8, 32))})
            raylet = Raylet(NodeID.from_random(), resources, cluster,
                            deps)
            cluster.register(raylet)
            head = head or raylet
            raylets.append(raylet)
        # 32 DISTINCT scheduling classes (scheduling_class_of dedups by
        # resource key, so the demand must vary per class)
        demands = [{"CPU": round(1.0 + c * 0.125, 3), "PIN": 0.001}
                   for c in range(n_classes)]
        job = JobID.from_int(11)
        parent = TaskID.for_task(None)
        with head._lock:
            for i in range(n_tasks):
                spec = TaskSpec(
                    kind=TaskKind.NORMAL, task_id=TaskID.for_task(None),
                    job_id=job, parent_task_id=parent, name=f"ab{i}",
                    resources=dict(demands[i % n_classes]))
                spec.scheduling_class = scheduling_class_of(
                    spec.resource_request(cluster.ids))
                task = _PendingTask(spec, lambda r, w: None, 0)
                head._pending.append(task)
                head._by_task_id[spec.task_id] = task
        return cluster, head, raylets

    def _phase(p: str) -> float:
        return scheduler_phase_ms.sum_value(tags={"phase": p}) or 0.0

    def _drive(pipeline_on: bool) -> dict:
        cfg = Config.instance()
        old_pipe = cfg.scheduler_pipeline_enabled
        old_cells = cfg.scheduler_device_solve_min_cells
        old_plane = cfg.observability_plane_enabled
        old_interval = _TickPhases.MIN_INTERVAL_S
        cfg._set("scheduler_pipeline_enabled", pipeline_on)
        # route every batched class through the device solve: the A-B
        # compares full-reupload+blocking-pull (off) against
        # mirror-delta+async-pull (on), which needs the device path
        # engaged in BOTH modes
        cfg._set("scheduler_device_solve_min_cells", 0)
        cfg.observability_plane_enabled = True  # phase sums feed the
        #                                         overlap share below
        _TickPhases.MIN_INTERVAL_S = 0.0        # instrument every tick
        try:
            cluster, head, raylets = _build()
            before = {p: _phase(p) for p in _TickPhases.PHASES}
            tick_s = []
            t0 = time.perf_counter()
            for _ in range(4096):
                t1 = time.perf_counter()
                head.schedule_tick()
                tick_s.append(time.perf_counter() - t1)
                with head._lock:
                    if not head._pending:
                        break
            drain_s = time.perf_counter() - t0
            after = {p: _phase(p) for p in _TickPhases.PHASES}
        finally:
            _TickPhases.MIN_INTERVAL_S = old_interval
            cfg._set("scheduler_pipeline_enabled", old_pipe)
            cfg._set("scheduler_device_solve_min_cells", old_cells)
            cfg.observability_plane_enabled = old_plane
        infeasible = sum(len(r._infeasible) for r in raylets)
        leftover = sum(len(r._pending) for r in raylets)
        placed = n_tasks - infeasible - leftover
        phases = {p: after[p] - before[p] for p in after}
        matrix = cluster.matrix
        # per-device-solve upload of the OFF path, by construction: the
        # single tick re-coerces total+available to f32 and re-uploads
        # them (plus alive) for every fused solve
        full_bytes = (int(matrix.total.shape[0]) * int(matrix.width)
                      * 4 * 2 + int(matrix.alive.nbytes))
        mirror = cluster.device_mirror
        return {
            "placed": placed,
            "infeasible": infeasible,
            "leftover": leftover,
            "drain_s": drain_s,
            "rate": placed / drain_s if drain_s else 0.0,
            "tick_s": tick_s,
            "phases": phases,
            "full_upload_bytes": full_bytes,
            "mirror_upload_bytes": (mirror.upload_bytes_total
                                    if mirror else 0),
            "mirror_solves": ((mirror.full_syncs + mirror.delta_syncs)
                              if mirror else 0),
            "mirror_full_syncs": mirror.full_syncs if mirror else 0,
        }

    off = _drive(False)
    on = _drive(True)
    solve_ms = on["phases"].get("solve", 0.0)
    overlap_ms = on["phases"].get("overlap", 0.0)
    out = {
        "pipeline_off_placements_per_s": round(off["rate"], 1),
        "pipeline_on_placements_per_s": round(on["rate"], 1),
        "pipeline_speedup": (round(on["rate"] / off["rate"], 2)
                             if off["rate"] else 0.0),
        "pipeline_off_drain_s": round(off["drain_s"], 3),
        "pipeline_on_drain_s": round(on["drain_s"], 3),
        "pipeline_off_p99_tick_ms": round(float(np.percentile(
            np.array(off["tick_s"]) * 1e3, 99)), 3),
        # the pipelined drain runs inside ONE outer call; its per-batch
        # latency is the drain wall over the number of device solves
        "pipeline_on_mean_batch_ms": round(
            1e3 * on["drain_s"] / max(on["mirror_solves"], 1), 3),
        "pipeline_on_batches": on["mirror_solves"],
        "pipeline_on_mirror_full_syncs": on["mirror_full_syncs"],
        "solve_commit_overlap_pct": round(
            100.0 * overlap_ms / (overlap_ms + solve_ms), 1)
        if (overlap_ms + solve_ms) else 0.0,
        "matrix_upload_bytes_per_tick_off": off["full_upload_bytes"],
        "matrix_upload_bytes_per_tick_on": round(
            on["mirror_upload_bytes"] / max(on["mirror_solves"], 1), 1),
        # both modes must place the same task set (the pipeline may
        # SEQUENCE placements differently, never drop or invent work)
        "pipeline_infeasible_off_on": [off["infeasible"],
                                       on["infeasible"]],
    }
    if off["leftover"] or on["leftover"]:
        out["pipeline_ab_leftover"] = [off["leftover"], on["leftover"]]
    return out


def bench_scheduler() -> dict:
    import jax

    from ray_tpu.scheduler.policy import (
        BatchedHybridPolicy,
        SchedulingOptions,
    )
    from ray_tpu.scheduler.resources import to_fixed

    rng = np.random.default_rng(0)
    n_nodes, n_res, n_classes = 256, 8, 32
    total_tasks = 100_000

    total = rng.integers(8, 64, size=(n_nodes, n_res)).astype(np.int64)
    total *= to_fixed(1)
    available = total.copy()
    alive = rng.random(n_nodes) > 0.02
    # heterogeneous demands: CPU-ish always, others sparse
    reqs = np.zeros((n_classes, n_res), dtype=np.int64)
    reqs[:, 0] = rng.integers(1, 4, size=n_classes) * to_fixed(0.5)
    for c in range(n_classes):
        extra = rng.choice(n_res - 1, size=2, replace=False) + 1
        reqs[c, extra] = rng.integers(0, 3, size=2) * to_fixed(1)
    ks = rng.multinomial(total_tasks, np.ones(n_classes) / n_classes)
    ks = ks.astype(np.int64)

    policy = BatchedHybridPolicy(use_jax=True)
    opts = SchedulingOptions(spread_threshold=0.5)
    total_f = jax.device_put(total.astype(np.float32))
    alive_d = jax.device_put(alive)

    # warmup / compile on representative shapes
    out = policy.schedule_tick_fused(
        reqs.astype(np.float32), ks.astype(np.float32), total_f,
        jax.device_put(available.astype(np.float32)), alive_d, 0, opts)
    out.block_until_ready()

    # ---- the drain: queue and availability evolve tick over tick -------
    pending = ks.copy()
    placed_total = 0
    tick_times = []
    prev_usage_by_node = np.zeros((n_nodes, n_res), dtype=np.int64)
    n_ticks = 0
    shed_before = _process_shed_total()
    t_drain0 = time.perf_counter()
    while pending.sum() > 0:
        t0 = time.perf_counter()
        # tasks placed last tick complete now: free their resources
        available += prev_usage_by_node
        counts_dev = policy.schedule_tick_fused(
            reqs.astype(np.float32), pending.astype(np.float32), total_f,
            jax.device_put(available.astype(np.float32)), alive_d, 0, opts)
        counts = policy.repair_oversubscription(
            reqs, np.asarray(counts_dev), available)
        # commit: decrement queue and availability
        per_class_placed = counts.sum(axis=1)          # [C]
        usage = counts.T @ reqs                        # [N, R] int64
        available -= usage
        prev_usage_by_node = usage
        pending = pending - per_class_placed
        placed = int(per_class_placed.sum())
        placed_total += placed
        tick_times.append(time.perf_counter() - t0)
        n_ticks += 1
        if placed == 0:
            # capacity exhausted this tick even after completions freed
            # resources: the drain cannot make progress (should not
            # happen with the one-tick pipeline, but never spin)
            break
    drain_s = time.perf_counter() - t_drain0
    tick_times = np.array(tick_times)

    # ---- device-resident availability drain (tentpole (b) at the
    # solver tier): the SAME seeded queue, but availability never
    # leaves the device — pipelined_step folds last tick's freed usage
    # into the donated device buffer, solves, and pre-subtracts this
    # tick's usage in one async dispatch. Per tick the host uploads
    # only reqs+pending (~KB) and pulls only the counts, vs the loop
    # above re-uploading the full availability matrix every tick. The
    # host keeps the exact int64 shadow for the repair/commit, so
    # correctness accounting is unchanged.
    dr_upload_per_tick = (reqs.astype(np.float32).nbytes
                          + 4 * n_classes)
    warm = policy.pipelined_step(
        jax.device_put(total.astype(np.float32)),
        jax.device_put(np.zeros_like(total, dtype=np.float32)),
        jax.device_put(np.zeros_like(total, dtype=np.float32)),
        reqs.astype(np.float32), ks.astype(np.float32), total_f,
        alive_d, 0, opts)
    warm[2].block_until_ready()  # compile outside the timed region
    zeros_nr = jax.device_put(np.zeros_like(total, dtype=np.float32))
    avail_dev = jax.device_put(total.astype(np.float32))
    freed_dev = zeros_nr
    avail_host = total.copy()
    prev_usage = np.zeros_like(total)
    pending_dr = ks.copy()
    placed_dr = 0
    dr_tick_times = []
    t_dr0 = time.perf_counter()
    while pending_dr.sum() > 0:
        t0 = time.perf_counter()
        avail_dev, usage_dev, counts_dev = policy.pipelined_step(
            avail_dev, freed_dev, zeros_nr, reqs.astype(np.float32),
            pending_dr.astype(np.float32), total_f, alive_d, 0, opts)
        avail_host += prev_usage  # last tick's tasks complete now
        counts = policy.repair_oversubscription(
            reqs, np.asarray(counts_dev), avail_host)
        usage = counts.T @ reqs
        avail_host -= usage
        prev_usage = usage
        freed_dev = usage_dev  # next step frees it ON DEVICE
        per_class = counts.sum(axis=1)
        pending_dr = pending_dr - per_class
        placed = int(per_class.sum())
        placed_dr += placed
        dr_tick_times.append(time.perf_counter() - t0)
        if placed == 0:
            break
    dr_drain_s = time.perf_counter() - t_dr0
    dr_tick_times = np.array(dr_tick_times) if dr_tick_times else \
        np.zeros(1)

    # ---- integrity on-vs-off over the SAME tick (plane must be free
    # here: the solve moves no object bytes, so any delta is leakage)
    from ray_tpu._private.config import Config as _Cfg

    cfg = _Cfg.instance()
    old_flag = cfg.integrity_enabled

    def _tick_time(flag: bool, k: int = 5) -> float:
        cfg.integrity_enabled = flag
        best = float("inf")
        for _ in range(k):
            t0 = time.perf_counter()
            out = policy.schedule_tick_fused(
                reqs.astype(np.float32), ks.astype(np.float32),
                total_f, jax.device_put(total.astype(np.float32)),
                alive_d, 0, opts)
            out.block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    try:
        t_off = _tick_time(False)
        t_on = _tick_time(True)
    finally:
        cfg.integrity_enabled = old_flag
    integrity_overhead_pct = (round(100.0 * (t_on - t_off) / t_off, 1)
                              if t_off else 0.0)

    baseline_proxy = 1_000_000 / 175.02  # reference 1M-queue drain rate
    placements_per_sec = placed_total / drain_s
    out = {
        "metric": "sustained_scheduler_placements_per_sec_100k_drain",
        "value": round(placements_per_sec, 1),
        "unit": "placements/s",
        "vs_baseline": round(placements_per_sec / baseline_proxy, 2),
        "drained": placed_total,
        "queue": total_tasks,
        "ticks": n_ticks,
        "drain_s": round(drain_s, 3),
        "p99_tick_ms": round(float(np.percentile(tick_times, 99) * 1e3), 3),
        "mean_tick_ms": round(float(tick_times.mean() * 1e3), 3),
        "nodes": n_nodes,
        "classes": n_classes,
        # overload-plane guard: the drain must not shed on the happy
        # path (before/after delta of the process's shed counters)
        "scheduler_shed_delta": round(
            _process_shed_total() - shed_before, 1),
        # integrity-plane guard: the SAME fused tick with the plane on
        # vs off — the drain moves no object bytes, so this must stay
        # ~0; a nonzero trend means checksum work leaked into the
        # scheduling hot path
        "integrity_overhead_pct": integrity_overhead_pct,
        # device-resident availability (pipelined_step): same drain,
        # availability held on device across ticks — the host moves
        # ~KBs per tick instead of the full matrix
        "device_resident_placements_per_sec": round(
            placed_dr / dr_drain_s, 1) if dr_drain_s else 0.0,
        "device_resident_p99_tick_ms": round(
            float(np.percentile(dr_tick_times, 99) * 1e3), 3),
        "device_resident_drained": placed_dr,
        "device_resident_upload_bytes_per_tick": dr_upload_per_tick,
        "matrix_upload_bytes_per_tick_fused_loop":
            int(total.astype(np.float32).nbytes),
    }
    # ---- tentpole A-B: pipeline on/off over the same seeded 100k
    # drain on the LIVE raylet tier (solve_commit_overlap_pct +
    # matrix_upload_bytes_per_tick_{off,on} live here)
    out.update(_pipeline_ab_live())
    # observability-plane guards: tick anatomy (phase breakdown must
    # cover >= 90% of externally-timed tick wall) + the plane's cost on
    # the live schedule_tick and the submit micro (both bars: <= 2%)
    out.update(_tick_anatomy_and_tracing_overhead())
    out["submit_micro_tracing_overhead_pct"] = (
        _submit_micro_tracing_overhead_pct())
    # dispatch fast lane (r07): submit-path attribution + the driver
    # submit on/off A-B (bar: >= 2x cheaper per call with the lane on)
    out.update(_submit_attribution_us())
    return out


def _memcpy_floor_mib_s() -> float:
    """The host's raw copy rate right now. Every replica is at
    minimum one memcpy into the consumer's segment, so aggregate
    broadcast rate cannot beat this — and on the burst-throttled
    1-vCPU build box it swings 0.2-0.9 GiB/s between runs, so it
    must be sampled around the timed region, not once."""
    import numpy as np

    src = np.zeros(64 * 1024 * 1024, dtype=np.uint8)
    dst = np.empty_like(src)
    dst[:] = src  # untimed warm-up: fault in both mappings (a
    #               first-touch copy measures page faults, not copy
    #               bandwidth, understating the floor ~2x)
    t0 = time.perf_counter()
    dst[:] = src
    return 64 / (time.perf_counter() - t0)


def _broadcast_probe(mib: int, n_consumers: int, extra_env: dict,
                     driver_knobs: dict, store_mib: int) -> dict:
    """One broadcast measurement at an arbitrary data-plane config:
    boots a fresh (producer + n) cluster whose raylets carry
    ``extra_env`` (RAY_TPU_* data-plane knobs) and whose driver Config
    carries ``driver_knobs`` (the broadcast planner runs driver-side),
    times ONE broadcast, and returns rate + plan + path counters.
    Used by the A/B, per-topology, and scale sub-rows — the main row
    keeps its own richer bracket."""
    import numpy as np

    from ray_tpu._private.config import Config
    from ray_tpu.cluster.process_cluster import ClusterClient, ProcessCluster

    Config.reset()
    cfg = Config.instance()
    for k, v in driver_knobs.items():
        cfg._set(k, v)
    store_bytes = store_mib * 1024 * 1024
    cluster = ProcessCluster(heartbeat_period_ms=500,
                             num_heartbeats_timeout=120)
    try:
        producer = cluster.add_node(num_cpus=1, num_workers=1,
                                    object_store_memory=store_bytes,
                                    extra_env=extra_env)
        consumers = [cluster.add_node(num_cpus=1, num_workers=1,
                                      object_store_memory=store_bytes,
                                      extra_env=extra_env)
                     for _ in range(n_consumers)]
        cluster.wait_for_nodes(1 + n_consumers)
        client = ClusterClient(cluster.gcs_address)
        try:
            size = mib * 1024 * 1024
            ref = client.submit(
                lambda n=size: np.zeros(n, dtype=np.uint8),
                node_id=producer)
            client.get(client.submit(lambda a: int(a[-1]), (ref,),
                                     node_id=producer))
            floor_before = _memcpy_floor_mib_s()
            t0 = time.perf_counter()
            confirmed = client.broadcast(ref, consumers)
            push_s = time.perf_counter() - t0
            floor_after = _memcpy_floor_mib_s()
            plan = client.last_broadcast_plan or {}
            chunks_in = chunks_fwd = adopts = 0
            overlaps = []
            for nid in consumers:
                stats = cluster.node_stats(nid)
                f = stats.get("fetches", {})
                chunks_in += f.get("chunks_in", 0)
                chunks_fwd += f.get("chunks_forwarded", 0)
                ov = f.get("cut_through_overlap_pct")
                if ov is not None:
                    overlaps.append(ov)
                adopts += stats.get("store", {}).get("num_shm_adopts", 0)
        finally:
            client.close()
    finally:
        cluster.shutdown()
        Config.reset()
    rate = mib * confirmed / push_s if confirmed else 0.0
    floor = min(floor_before, floor_after)
    return {
        "MiB_per_s": round(rate, 1),
        "pct_of_memcpy_floor": round(100 * rate / floor, 1)
        if floor else 0.0,
        "s": round(push_s, 3),
        "per_node_ms": round(1e3 * push_s / n_consumers, 1),
        "confirmed": confirmed,
        "topology": plan.get("topology"),
        "depth": plan.get("depth"),
        "fanout": plan.get("fanout"),
        "chunks_in": chunks_in,
        "chunks_forwarded": chunks_fwd,
        "shm_adopts": adopts,
        "cut_through_overlap_pct": (
            round(sum(overlaps) / len(overlaps), 1) if overlaps
            else None),
    }


def _broadcast_subrows(mib: int, n_consumers: int, on_rate: float) -> dict:
    """The data-plane A/B and shape sub-rows around the main broadcast
    row: pipeline OFF at the main shape (the legacy fan-out the
    acceptance bar compares against), each topology forced down the
    chunk-stream path (same-host adoption disabled via stream_only so
    the pipelined framing itself is what's measured), and the 8-vs-32
    node scale row (per-node cost must stay ~flat as the tree widens).
    """
    out: dict = {}
    # ---- A/B: exact pre-PR path at the main shape ----
    try:
        # verify_shm_reads pinned OFF here: the r07 baseline this
        # speedup is quoted against ran verify-off (the pre-pipeline
        # default), and the legacy seg-to-seg copy is the one path
        # where the knob still buys a full crc pass
        off = _broadcast_probe(
            mib, n_consumers,
            {"RAY_TPU_data_plane_pipeline_enabled": "0",
             "RAY_TPU_integrity_verify_shm_reads": "0"},
            {"data_plane_pipeline_enabled": False,
             "integrity_verify_shm_reads": False},
            store_mib=mib + 512)
        out["broadcast_off_MiB_per_s"] = off["MiB_per_s"]
        out["broadcast_off_pct_of_memcpy_floor"] = (
            off["pct_of_memcpy_floor"])
        out["broadcast_on_vs_off_speedup"] = (
            round(on_rate / off["MiB_per_s"], 2)
            if off["MiB_per_s"] else None)
    except Exception as e:  # noqa: BLE001 — sub-row must not sink the row
        out["broadcast_off_error"] = f"{type(e).__name__}: {e}"
    # ---- shm-read verify cost on the pipelined path ----
    # integrity_verify_shm_reads defaults ON since this PR (adoption
    # verifies by an O(1) trailer-digest compare); price the residual
    # by re-running the main shape with the knob forced OFF and
    # comparing against the main row's verify-on rate (bar: <= 5%)
    try:
        nov = _broadcast_probe(
            mib, n_consumers,
            {"RAY_TPU_data_plane_pipeline_enabled": "1",
             "RAY_TPU_integrity_verify_shm_reads": "0"},
            {"data_plane_pipeline_enabled": True,
             "integrity_verify_shm_reads": False},
            store_mib=mib + 512)
        out["broadcast_noverify_MiB_per_s"] = nov["MiB_per_s"]
        out["broadcast_shm_verify_overhead_pct"] = (
            round(100.0 * (nov["MiB_per_s"] - on_rate)
                  / nov["MiB_per_s"], 1)
            if nov["MiB_per_s"] else None)
    except Exception as e:  # noqa: BLE001
        out["broadcast_shm_verify_error"] = f"{type(e).__name__}: {e}"
    # ---- per-topology chunk-stream rows ----
    stream_mib = min(mib, 256)
    for topo in ("binomial", "chain", "flat"):
        try:
            row = _broadcast_probe(
                stream_mib, n_consumers,
                {"RAY_TPU_data_plane_pipeline_enabled": "1",
                 "RAY_TPU_data_plane_stream_only": "1",
                 "RAY_TPU_data_plane_topology": topo},
                {"data_plane_pipeline_enabled": True,
                 "data_plane_stream_only": True,
                 "data_plane_topology": topo},
                store_mib=stream_mib + 256)
            out[f"broadcast_stream_{topo}"] = {
                k: row[k] for k in
                ("MiB_per_s", "pct_of_memcpy_floor", "s", "depth",
                 "fanout", "chunks_in", "chunks_forwarded",
                 "cut_through_overlap_pct", "confirmed")}
            out[f"broadcast_stream_{topo}"]["payload_mib"] = stream_mib
        except Exception as e:  # noqa: BLE001
            out[f"broadcast_stream_{topo}_error"] = (
                f"{type(e).__name__}: {e}")
    # ---- scale row: per-node cost at 8 vs 32 consumers ----
    try:
        scale8 = _broadcast_probe(
            64, 8, {"RAY_TPU_data_plane_pipeline_enabled": "1"},
            {"data_plane_pipeline_enabled": True}, store_mib=256)
        scale32 = _broadcast_probe(
            64, 32, {"RAY_TPU_data_plane_pipeline_enabled": "1"},
            {"data_plane_pipeline_enabled": True}, store_mib=256)
        out["broadcast_scale_8_per_node_ms"] = scale8["per_node_ms"]
        out["broadcast_scale_32_per_node_ms"] = scale32["per_node_ms"]
        out["broadcast_scale_32_confirmed"] = scale32["confirmed"]
        out["broadcast_scale_per_node_ratio"] = (
            round(scale32["per_node_ms"] / scale8["per_node_ms"], 2)
            if scale8["per_node_ms"] else None)
    except Exception as e:  # noqa: BLE001
        out["broadcast_scale_error"] = f"{type(e).__name__}: {e}"
    return out


def bench_object_broadcast() -> dict:
    """Cross-process object broadcast at the reference's shape: a 1 GiB
    payload pre-placed on every consumer node through the binomial-tree
    push plane (offer/begin/chunk/end + PushManager throttling), then
    verified by a task on each node reading it locally. Baseline: the
    reference moves 1 GiB to 50 nodes in 74.81 s — 50 GiB / 74.81 s ≈
    684 MiB/s aggregate
    (release/release_logs/1.9.0/scalability/object_store.json)."""
    import numpy as np

    from ray_tpu.cluster.process_cluster import ClusterClient, ProcessCluster

    memcpy_floor_mib_s = _memcpy_floor_mib_s

    mib = int(os.environ.get("RAY_TPU_BENCH_BROADCAST_MIB", "1024"))
    n_consumers = int(os.environ.get("RAY_TPU_BENCH_BROADCAST_NODES", "8"))
    store_bytes = (mib + 512) * 1024 * 1024
    # RAM guard: every node's store is prefaulted at boot (resident
    # tmpfs), ~1.35x store_bytes with headroom. On a host without the
    # ~17 GB this shape needs, shrink the payload rather than letting
    # the OOM killer SIGKILL a raylet mid-boot (observed rc=-9)
    requested_mib = mib
    requested_nodes = n_consumers
    try:
        with open("/proc/meminfo") as f:
            avail_kb = next(int(line.split()[1]) for line in f
                            if line.startswith("MemAvailable:"))
        budget = int(avail_kb * 1024 * 0.6)
        need = int((n_consumers + 1) * store_bytes * 1.35)
        if need > budget:
            # solve for the payload directly (footprint is
            # (n+1) * (mib + 512 MiB) * 1.35): a linear scale of mib
            # would leave the +512 MiB per-store floor unshrunk and
            # still bust the budget
            fit = int(budget / (1.35 * (n_consumers + 1) * 2**20) - 512)
            if fit < 16:
                # even a near-zero payload busts the budget (the
                # per-store floor dominates): shed consumers before
                # shrinking below a meaningful payload
                while n_consumers > 2 and fit < 16:
                    n_consumers -= 2
                    fit = int(budget / (1.35 * (n_consumers + 1)
                                        * 2**20) - 512)
            if fit < 1:
                # a doomed boot would end in an OOM SIGKILL mid-row;
                # fail the row legibly instead
                return {"broadcast_error":
                        "insufficient MemAvailable for even a minimal "
                        "broadcast cluster; row skipped",
                        "broadcast_MiB_per_s": 0.0}
            mib = max(1, min(mib, fit))
            store_bytes = (mib + 512) * 1024 * 1024
    except (OSError, StopIteration):
        pass  # no meminfo: proceed at the requested shape
    # GiB-scale pushes saturate a small host's cores; heartbeats must
    # tolerate ~a minute of starvation before declaring nodes dead
    cluster = ProcessCluster(heartbeat_period_ms=500,
                             num_heartbeats_timeout=120)
    try:
        producer = cluster.add_node(num_cpus=1, num_workers=1,
                                    object_store_memory=store_bytes)
        consumers = [cluster.add_node(num_cpus=1, num_workers=1,
                                      object_store_memory=store_bytes)
                     for _ in range(n_consumers)]
        cluster.wait_for_nodes(1 + n_consumers)
        client = ClusterClient(cluster.gcs_address)
        try:
            size = mib * 1024 * 1024
            ref = client.submit(
                lambda n=size: np.zeros(n, dtype=np.uint8),
                node_id=producer)
            client.get(client.submit(lambda a: int(a[-1]), (ref,),
                                     node_id=producer))  # materialized
            # warm consumer workers outside the timed region
            for nid in consumers:
                client.get(client.submit(
                    lambda: int(np.zeros(1)[0]), node_id=nid))
            # which path moved the bytes: same-host shm memcpy vs
            # chunked TCP stream. Counters are sampled immediately
            # before AND after the timed region and differenced — the
            # per-node values are cumulative since boot, and any
            # inbound push outside the bracket (warm-up retries, a
            # reordered earlier row) must not be attributed to the
            # broadcast path.
            def _push_counters():
                shm = stream = 0
                for nid in consumers:
                    f = cluster.node_stats(nid).get("fetches", {})
                    shm += f.get("push_shm_in", 0)
                    stream += f.get("push_stream_in", 0)
                return shm, stream

            def _integrity_verified_bytes():
                # integrity-plane counter across every node: payload
                # bytes that passed a checksum seam. Differenced around
                # the timed bracket; with the sampled crc32 rate it
                # prices the verification work inside broadcast_s.
                total = 0.0
                for nid in [producer] + consumers:
                    integ = cluster.node_stats(nid).get(
                        "integrity", {})
                    total += integ.get("bytes_verified", 0.0)
                return total

            def _crc_rate_bytes_per_s():
                from ray_tpu.cluster import integrity as _integ

                sample = np.zeros(64 * 1024 * 1024, dtype=np.uint8)
                _integ.checksum(sample[:1024 * 1024])  # warm
                t0 = time.perf_counter()
                _integ.checksum(sample)
                return sample.nbytes / (time.perf_counter() - t0)

            def _cluster_shed_total():
                # overload-plane counters across every node: task
                # backpressure + push sheds + RPC admission sheds.
                # Differenced around the timed bracket like the push
                # counters — a broadcast that trips shedding on the
                # happy path is a regression, not just "slow".
                total = 0
                for nid in [producer] + consumers:
                    ov = cluster.node_stats(nid).get("overload", {})
                    total += (ov.get("tasks_shed", 0)
                              + ov.get("push_shed", 0))
                    rpc_ov = ov.get("rpc") or {}
                    total += (rpc_ov.get("shed_queue_full", 0)
                              + rpc_ov.get("shed_deadline", 0))
                return total

            floor_before = memcpy_floor_mib_s()
            shed_before = _cluster_shed_total()
            verified_before = _integrity_verified_bytes()
            shm_in0, stream_in0 = _push_counters()
            # ---- timed: binomial-tree push to every consumer --------
            t0 = time.perf_counter()
            confirmed = client.broadcast(ref, consumers)
            push_s = time.perf_counter() - t0
            bcast_plan = dict(client.last_broadcast_plan or {})
            shm_in1, stream_in1 = _push_counters()
            adopts = 0
            overlaps = []
            for nid in consumers:
                stats = cluster.node_stats(nid)
                adopts += stats.get("store", {}).get(
                    "num_shm_adopts", 0)
                ov = stats.get("fetches", {}).get(
                    "cut_through_overlap_pct")
                if ov is not None:
                    overlaps.append(ov)
            verified_after = _integrity_verified_bytes()
            shed_after = _cluster_shed_total()
            floor_after = memcpy_floor_mib_s()
            crc_rate = _crc_rate_bytes_per_s()
            shm_in = shm_in1 - shm_in0
            stream_in = stream_in1 - stream_in0
            # every node now reads its LOCAL replica (zero transfer)
            refs = [client.submit(lambda a: int(a[-1]), (ref,),
                                  node_id=nid) for nid in consumers]
            for r in refs:
                client.get(r, timeout=120.0)
            total_s = time.perf_counter() - t0
        finally:
            client.close()
    finally:
        cluster.shutdown()
    # rate credits only CONFIRMED replicas: a push that gave up on some
    # nodes must not report bandwidth it never delivered
    rate = mib * confirmed / push_s if confirmed else 0.0
    floor = min(floor_before, floor_after)
    out = {
        "broadcast_MiB_per_s": round(rate, 1),
        "broadcast_payload_mib": mib,
        "broadcast_nodes": n_consumers,
        "broadcast_confirmed": confirmed,
        "broadcast_s": round(push_s, 3),
        "broadcast_read_s": round(total_s - push_s, 3),
        # reference row: 1 GiB x 50 nodes in 74.81 s on a real network;
        # this is 1 host's loopback — the proxy is aggregate MiB/s
        "broadcast_vs_baseline": round(rate / 684.0, 3),
        "broadcast_shm_fastpath_in": shm_in,
        "broadcast_stream_in": stream_in,
        "broadcast_shed_delta": shed_after - shed_before,
        # integrity plane: verified bytes inside the bracket priced at
        # the host's sampled crc32 rate, as a share of the broadcast
        # wall time — the checksum cost of verification-on (acceptance
        # bar: <= 5%), plus the plane-on-vs-off store micro
        "broadcast_integrity_verified_mib": round(
            (verified_after - verified_before) / 2**20, 1),
        "broadcast_integrity_overhead_pct": round(
            100.0 * ((verified_after - verified_before) / crc_rate)
            / push_s, 2) if push_s else 0.0,
        "integrity_store_put_get_overhead_pct":
            _integrity_store_micro_pct(),
        "broadcast_host_memcpy_MiB_s": [round(floor_before, 1),
                                        round(floor_after, 1)],
        "broadcast_pct_of_memcpy_floor": round(100 * rate / floor, 1)
        if floor else 0.0,
        # data-plane pipeline: the planned tree and which path moved
        # the replicas (same-host adoption vs chunk stream)
        "broadcast_topology": bcast_plan.get("topology"),
        "broadcast_tree_depth": bcast_plan.get("depth"),
        "broadcast_tree_fanout": bcast_plan.get("fanout"),
        "broadcast_shm_adopts": adopts,
        "broadcast_cut_through_overlap_pct": (
            round(sum(overlaps) / len(overlaps), 1) if overlaps
            else None),
    }
    out.update(_broadcast_subrows(mib, n_consumers, rate))
    if mib != requested_mib or n_consumers != requested_nodes:
        # the shape was shrunk by the RAM guard: the row must not read
        # as a measurement of the requested shape
        out["broadcast_ram_guard"] = (
            f"shape shrunk {requested_mib} MiB x {requested_nodes} -> "
            f"{mib} MiB x {n_consumers} to fit MemAvailable")
    if confirmed < n_consumers:
        out["broadcast_error"] = (
            f"only {confirmed}/{n_consumers} replicas confirmed")
    return out


def bench_serve() -> dict:
    """Serve resilience row: open-loop sustained-QPS latency against a
    replicated deployment, CALM vs under a seeded storm (replica kills
    + handler stalls + reply-corrupt bursts derived from one
    RAY_TPU_FAULT_PLAN seed — cluster/fault_plane.StormPlan). Reports
    p50/p99 completion latency, goodput, and the WRONG-ANSWER count
    with the resilience plane on (acceptance bar: zero wrong, storm
    goodput >= 70% of calm), plus the overload-plane shed/backpressure
    counter deltas the other rows already sample."""
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.cluster import fault_plane
    from ray_tpu.cluster.fault_plane import FaultPlane, StormPlan
    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.observability.metrics import get_metric

    def counter_total(name):
        m = get_metric(name)
        return sum(m.series().values()) if m is not None else 0.0

    qps, phase_s, n_replicas = 150.0, 3.0, 3
    seed = fault_plane.storm_seed_from_env(default=1234)
    storm = StormPlan(seed, duration_s=phase_s)
    shed_before = _process_shed_total()
    bp_before = counter_total("ray_tpu_serve_requests_backpressured")

    ray_tpu.init(num_cpus=8)
    serve.start()

    @serve.deployment(num_replicas=n_replicas, max_concurrent_queries=16,
                      health_check_period_s=0.1,
                      health_check_timeout_s=1.0,
                      health_check_failure_threshold=2,
                      graceful_shutdown_timeout_s=2.0)
    def bench_model(x=0):
        return "w" * 64 + f"|{x * 31 + 7}"

    def expected(x):
        return "w" * 64 + f"|{x * 31 + 7}"

    def open_loop(handle, duration_s):
        """Issue at the schedule regardless of completions; completion
        timestamps come from the object store's availability hook so
        head-of-line blocking in collection doesn't distort latency."""
        store = rt_mod.global_runtime.object_store
        done, sent = {}, []
        t0 = time.monotonic()
        i = 0
        while time.monotonic() - t0 < duration_s:
            target = t0 + i / qps
            delay = target - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                ref = handle.remote(i)
                t_send = time.monotonic()

                def _cb(i=i, t_send=t_send):
                    done[i] = time.monotonic() - t_send

                store.on_available(ref.id(), _cb)
                sent.append((i, ref))
            except Exception:
                sent.append((i, None))  # backpressured
            i += 1
        correct = wrong = failed = 0
        for i, ref in sent:
            if ref is None:
                failed += 1
                continue
            try:
                value = ray_tpu.get(ref, timeout=15.0)
            except Exception:
                failed += 1
                continue
            if value == expected(i):
                correct += 1
            else:
                wrong += 1
        lats = sorted(v for k, v in done.items())
        return correct, wrong, failed, len(sent), lats

    def pct(lats, q):
        if not lats:
            return 0.0
        return round(
            1000.0 * lats[min(len(lats) - 1,
                              int(q / 100.0 * len(lats)))], 2)

    out = {}
    try:
        bench_model.deploy()
        h = bench_model.get_handle()
        ray_tpu.get([h.remote(0)])  # warm routing + replicas

        calm_c, calm_w, calm_f, calm_n, calm_lats = open_loop(h, phase_s)
        calm_goodput = 100.0 * calm_c / max(calm_n, 1)

        fault_plane.install_plane(FaultPlane(storm.plan()))
        stop = threading.Event()

        def kill_driver():
            controller = ray_tpu.get_actor("SERVE_CONTROLLER")
            t0 = time.monotonic()
            for ev in storm.kill_events():
                if ev["target"] != "replica":
                    continue
                delay = ev["t"] - (time.monotonic() - t0)
                if delay > 0 and stop.wait(delay):
                    return
                try:
                    _, replicas = ray_tpu.get(
                        controller.get_replicas.remote("bench_model"))
                    if replicas:
                        ray_tpu.kill(
                            replicas[ev["ordinal"] % len(replicas)])
                except Exception:
                    return
        killer = threading.Thread(target=kill_driver, daemon=True)
        killer.start()
        try:
            st_c, st_w, st_f, st_n, st_lats = open_loop(h, phase_s)
        finally:
            stop.set()
            killer.join(timeout=5.0)
            fault_plane.clear_plane()
        storm_goodput = 100.0 * st_c / max(st_n, 1)

        out = {
            "serve_qps_target": qps,
            "serve_replicas": n_replicas,
            "serve_storm_seed": seed,
            "serve_calm_p50_ms": pct(calm_lats, 50),
            "serve_calm_p99_ms": pct(calm_lats, 99),
            "serve_calm_goodput_pct": round(calm_goodput, 1),
            "storm_p50_ms": pct(st_lats, 50),
            "storm_p99_ms": pct(st_lats, 99),
            "storm_goodput_pct": round(storm_goodput, 1),
            "storm_goodput_vs_calm_pct": round(
                100.0 * storm_goodput / calm_goodput, 1)
            if calm_goodput else 0.0,
            # the acceptance bar: the resilience plane turns seeded
            # corruption into detections, never silent wrongness
            "wrong_answers": calm_w + st_w,
            "serve_storm_failed": st_f,
            "serve_shed_delta": _process_shed_total() - shed_before,
            "serve_backpressured_delta":
                counter_total("ray_tpu_serve_requests_backpressured")
                - bp_before,
        }
    finally:
        fault_plane.clear_plane()
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
    return out


def bench_actor_churn() -> dict:
    """Actor lifecycle churn through the warm worker pool + batched
    create/kill wire path: repeated create→call→kill waves against a
    two-node cluster whose pools have finished pre-forking, so the
    timed region measures lease/specialize/reset churn rather than
    interpreter boot. Baseline: the pre-pool path forked one worker
    per create and serialized every lifecycle RPC — ~1.6 creates/s
    (the reference's actor-launch scalability bar is 234 actors/s,
    release/release_logs/1.9.0/scalability/single_node.json ilk).
    Reports create/call/kill rates, the warm-hit ratio over the timed
    bracket, and the GCS batch counters proving the waves rode the
    coalesced wire path."""
    from concurrent.futures import ThreadPoolExecutor

    from ray_tpu.cluster.process_cluster import ClusterClient, ProcessCluster

    n_nodes = 2
    warm = 8
    waves = int(os.environ.get("RAY_TPU_BENCH_CHURN_WAVES", "4"))
    wave_size = n_nodes * warm  # matches total warm capacity

    class ChurnActor:
        def __init__(self, x=0):
            self.x = x

        def bump(self):
            self.x += 1
            return self.x

    # pool pre-forking briefly starves raylet heartbeats on a small
    # host; tolerate it rather than declaring the node dead mid-boot
    cluster = ProcessCluster(heartbeat_period_ms=200,
                             num_heartbeats_timeout=60)
    out = {}
    try:
        nids = [cluster.add_node(
            num_cpus=wave_size,
            extra_env={"RAY_TPU_worker_pool_warm_size": str(warm)})
            for _ in range(n_nodes)]
        cluster.wait_for_nodes(n_nodes)
        client = ClusterClient(cluster.gcs_address)
        try:
            # boot wave excluded from the timed region: wait for every
            # pool to report its warm complement via heartbeats
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                idle = sum(
                    cluster.node_stats(n)["pool"].get("warm_idle", 0)
                    for n in nids)
                if idle >= n_nodes * warm:
                    break
                time.sleep(0.2)

            def pool_totals():
                hits = misses = 0
                for n in nids:
                    p = cluster.node_stats(n)["pool"]
                    hits += p.get("warm_hits", 0)
                    misses += p.get("warm_misses", 0)
                return hits, misses

            create_s = call_s = kill_s = 0.0
            created = 0
            with ThreadPoolExecutor(max_workers=wave_size) as ex:
                # two UNTIMED warm-up waves: pre-forked workers still
                # pay first-use interpreter/import residue, and the
                # first kill wave's pool returns need one cycle to
                # settle — churn rate is the steady state, the boot
                # cost is already priced by actor_create_latency_ms
                for _ in range(2):
                    hs = list(ex.map(
                        lambda i: client.create_actor(ChurnActor, (i,)),
                        range(wave_size)))
                    list(ex.map(client.kill_actor, hs))
                    time.sleep(0.5)
                h0, m0 = pool_totals()
                for _ in range(waves):
                    t0 = time.monotonic()
                    handles = list(ex.map(
                        lambda i: client.create_actor(ChurnActor, (i,)),
                        range(wave_size)))
                    create_s += time.monotonic() - t0
                    created += len(handles)
                    t0 = time.monotonic()
                    assert all(ex.map(lambda h: h.bump(), handles))
                    call_s += time.monotonic() - t0
                    t0 = time.monotonic()
                    list(ex.map(client.kill_actor, handles))
                    kill_s += time.monotonic() - t0
                    time.sleep(0.5)  # let reset workers rejoin pools
            # heartbeat lag: give the final counters a beat to land
            time.sleep(0.5)
            hits, misses = (a - b for a, b in
                            zip(pool_totals(), (h0, m0)))
            batch = client.cluster_view().get("actor_batch", {})
            out = {
                "actor_churn_creates_per_s":
                    round(created / create_s, 1) if create_s else 0.0,
                "actor_churn_calls_per_s":
                    round(created / call_s, 1) if call_s else 0.0,
                "actor_churn_kills_per_s":
                    round(created / kill_s, 1) if kill_s else 0.0,
                "actor_churn_actors": created,
                "actor_churn_warm_hit_pct": round(
                    100.0 * hits / max(hits + misses, 1), 1),
                "actor_churn_creates_batched":
                    int(batch.get("creates_batched", 0)),
                "actor_churn_kills_batched":
                    int(batch.get("kills_batched", 0)),
            }
        finally:
            client.close()
    finally:
        cluster.shutdown()
    return out


def bench_chaos() -> dict:
    """Chaos row (fault-hardened fast lanes): mixed submit/actor/
    broadcast load against a three-node process cluster, CALM vs under
    a seeded storm — driver-frame duplication across the whole batched
    wire surface plus a raylet killed mid-frame (kill schedule from
    StormPlan's ``kill_mid_frame`` kind, one RAY_TPU_FAULT_PLAN seed),
    the killed node replaced in place like an autoscaler would.
    Acceptance bar with every fast lane ON: zero wrong answers, zero
    lost tasks, zero duplicated executions (the per-row idempotence
    tokens dedupe replayed batch frames), storm goodput >= 70% of
    calm. A separate dedupe probe duplicates EVERY submit frame and
    counts actual task executions through a side-effect marker file."""
    import tempfile

    from ray_tpu.cluster import fault_plane
    from ray_tpu.cluster.fault_plane import FaultPlane, StormPlan
    from ray_tpu.cluster.process_cluster import ClusterClient, ProcessCluster

    from concurrent.futures import ThreadPoolExecutor

    seed = fault_plane.storm_seed_from_env(default=1234)
    storm = StormPlan(seed, duration_s=3.0, kinds=("kill_mid_frame",))
    # long enough that the storm's FIXED recovery costs (the ~1.5s
    # heartbeat death verdict window, during which in-flight ops on the
    # victim stall) amortize against steady-state throughput instead of
    # dominating the ratio
    n_tasks = 2400

    class ChaosActor:
        def __init__(self):
            self.n = 0

        def bump(self, k):
            self.n += k
            return self.n

    def run_phase(client, cluster, nodes, kill_ordinal=None):
        """One mixed wave: tasks throughout, an actor create/call/kill
        every 20 submits, a broadcast every 40 — with an optional
        raylet kill (+ in-place replacement) halfway through.

        Every op runs on a worker-thread pool (closed-loop per thread,
        open-loop overall): an op that lands on the dying node pays the
        ~2s death verdict + lineage resubmit *concurrently* while the
        other threads keep the survivors saturated. A serial loop would
        measure latency-sum — one actor create stalled on the victim
        would gate every op queued behind it — which is not goodput.
        """
        import threading

        lock = threading.Lock()

        def task_op(i):
            r = client.submit(lambda i=i: i * 31 + 7)
            return (1 if client.get(r, timeout=120.0) == i * 31 + 7
                    else -1)

        def actor_op(i):
            h = client.create_actor(ChaosActor)
            try:
                ok = h.bump(i) == i
            finally:
                client.kill_actor(h)
            return 3 if ok else -1

        def bcast_op(i):
            ref = client.put(os.urandom(128 * 1024))
            with lock:
                peers = [n for n in nodes if n != ref.node_id]
            return client.broadcast(ref, peers)

        ops_list = []
        for i in range(n_tasks):
            ops_list.append((task_op, i))
            if i % 20 == 19:
                ops_list.append((actor_op, i))
            if i % 40 == 39:
                ops_list.append((bcast_op, i))

        n_done = [0]
        durations = []
        kill_at = len(ops_list) // 2

        kill_window = [None, None]
        kill_thread = [None]

        def kill_and_replace():
            # kill + replace in place; the replacement boots while the
            # other threads keep going (spilling to the survivors)
            kill_window[0] = time.monotonic()
            with lock:
                victim = nodes[kill_ordinal % len(nodes)]
            cluster.kill_node(victim)
            with lock:
                # membership updates on the DEATH, not on the
                # replacement: broadcasts must stop targeting the
                # victim now, not after the fresh node's multi-second
                # boot
                nodes.remove(victim)
            fresh = cluster.add_node(num_cpus=2)
            with lock:
                nodes.append(fresh)
            kill_window[1] = time.monotonic()

        def run_op(item):
            fn, i = item
            t_op = time.monotonic()
            got = 0  # lost unless an attempt lands
            for attempt in range(3):
                # an op interrupted by the node kill surfaces a loud
                # error (ActorDiedError, dead broadcast peer) — the
                # retrying-workload contract: back off past the death
                # verdict and retry; never count a *surfaced* failure
                # as silent loss
                try:
                    got = fn(i)
                    break
                except Exception:
                    time.sleep(1.0 * (attempt + 1))
                    continue
            durations.append((time.monotonic() - t_op, fn.__name__, i,
                              time.monotonic(), attempt))
            with lock:
                n_done[0] += 1
                fire = (kill_ordinal is not None
                        and n_done[0] == kill_at)
            if fire:
                # the kill + autoscaler-style replacement run on their
                # own thread: booting the fresh node takes seconds and
                # is infrastructure work, not workload — it must not
                # pin down one of the 16 workload threads (the ops
                # still pay the death verdict + lineage resubmit
                # concurrently; that cost stays in the measurement)
                kill_thread[0] = threading.Thread(
                    target=kill_and_replace, daemon=True)
                kill_thread[0].start()
            return got

        wrong = lost = ops = 0
        t0 = time.monotonic()
        with ThreadPoolExecutor(max_workers=16) as ex:
            for got in ex.map(run_op, ops_list):
                if got > 0:
                    ops += got
                elif got == 0:
                    lost += 1
                else:
                    wrong += 1
        # the clock stops when the last workload op lands; the
        # replacement node may still be booting — wait for it OFF the
        # clock so the next phase starts from a full cluster
        elapsed = time.monotonic() - t0
        if kill_thread[0] is not None:
            kill_thread[0].join(timeout=60.0)
        if os.environ.get("RAY_TPU_CHAOS_DEBUG"):
            import sys
            for d in sorted(durations, reverse=True)[:12]:
                print(f"slow-op dur={d[0]:.2f} {d[1]}[{d[2]}] "
                      f"end=+{d[3] - t0:.2f}s retries={d[4]}",
                      file=sys.stderr)
            buckets = {}
            for d in durations:
                buckets.setdefault(int(d[3] - t0), [0, 0])
                buckets[int(d[3] - t0)][0] += 1
                buckets[int(d[3] - t0)][1] += d[4]
            if kill_window[0] is not None:
                print(f"kill fired=+{kill_window[0] - t0:.2f}s "
                      f"replaced=+{kill_window[1] - t0:.2f}s",
                      file=sys.stderr)
            for sec in sorted(buckets):
                n, rt = buckets[sec]
                print(f"t+{sec:02d}s: {n:3d} ops done, "
                      f"{rt} retries", file=sys.stderr)
        return ops, wrong, lost, elapsed

    def dedupe_probe(client):
        """Every submit_task_batch frame delivered twice; the marker
        file counts actual executions — the tokens must hold the line
        at exactly one per task."""
        marker = tempfile.mktemp(prefix="ray_tpu_chaos_")

        def task(p, i):
            fd = os.open(p, os.O_WRONLY | os.O_APPEND | os.O_CREAT,
                         0o644)
            try:
                os.write(fd, f"{i}\n".encode())
            finally:
                os.close(fd)
            return i

        n = 40
        fault_plane.install_plane(FaultPlane({"seed": seed, "rules": [{
            "src_role": "driver", "direction": "request",
            "method": "submit_task_batch", "action": "duplicate",
            "prob": 1.0}]}))
        try:
            refs = [client.submit(task, args=(marker, i))
                    for i in range(n)]
            for r in refs:
                client.get(r, timeout=120.0)
        finally:
            fault_plane.clear_plane()
        time.sleep(2.0)  # stragglers from a double-queued row
        try:
            with open(marker) as f:
                executed = len(f.read().splitlines())
            os.unlink(marker)
        except FileNotFoundError:
            executed = 0
        return max(0, executed - n)

    cluster = ProcessCluster(heartbeat_period_ms=100,
                             num_heartbeats_timeout=15)
    out = {}
    try:
        nodes = [cluster.add_node(num_cpus=2) for _ in range(3)]
        cluster.wait_for_nodes(3)
        client = ClusterClient(cluster.gcs_address)
        try:
            client.get(client.submit(lambda: 1))  # warm the lanes
            for _ in range(6):
                # warm each node's worker pool: actor creates cold-fork
                # otherwise, which would deflate the CALM baseline (the
                # storm phase runs second, against warm pools) and flatter
                # the storm/calm ratio
                h = client.create_actor(ChaosActor)
                h.bump(1)
                client.kill_actor(h)
            calm_ops, calm_w, calm_l, calm_s = run_phase(
                client, cluster, list(nodes))
            kills = storm.kill_events()
            fault_plane.install_plane(FaultPlane({
                "seed": seed, "rules": [{
                    "src_role": "driver", "direction": "request",
                    "method": "*_batch", "action": "duplicate",
                    "prob": float(os.environ.get(
                        "RAY_TPU_CHAOS_DUP_PROB", "0.7"))}]}))
            try:
                st_ops, st_w, st_l, st_s = run_phase(
                    client, cluster, list(cluster.node_addresses),
                    kill_ordinal=(kills[0]["ordinal"] if kills else 0))
            finally:
                fault_plane.clear_plane()
            # second calm phase AFTER the storm: host-load drift over
            # the bench's lifetime moves a single calm baseline by 2x
            # between runs — bracketing the storm and pooling the two
            # calm waves cancels the drift instead of letting the ratio
            # ride on which minute the host was busiest
            calm2_ops, calm2_w, calm2_l, calm2_s = run_phase(
                client, cluster, list(cluster.node_addresses))
            calm_ops += calm2_ops
            calm_s += calm2_s
            calm_w += calm2_w
            calm_l += calm2_l
            dup = dedupe_probe(client)
            calm_goodput = calm_ops / calm_s if calm_s else 0.0
            storm_goodput = st_ops / st_s if st_s else 0.0
            out = {
                "chaos_storm_seed": seed,
                "chaos_calm_ops_per_s": round(calm_goodput, 1),
                "chaos_storm_ops_per_s": round(storm_goodput, 1),
                "chaos_storm_vs_calm_pct": round(
                    100.0 * storm_goodput / calm_goodput, 1)
                if calm_goodput else 0.0,
                # the acceptance bar: hardened lanes turn storms into
                # retries and dedupes, never silent wrongness
                "chaos_wrong_answers": calm_w + st_w,
                "chaos_lost_tasks": calm_l + st_l,
                "chaos_dup_executions": dup,
            }
        finally:
            client.close()
    finally:
        cluster.shutdown()
    return out


def bench_preemption() -> dict:
    """Preemption row (elastic capacity): mixed submit/actor load on a
    three-node process cluster, CALM vs a seeded preemption storm — the
    victim raylet gets a spot-style eviction notice (StormPlan's
    ``preempt_node`` kind, one seed), the GCS drains it inside the
    window (actors migrate, sole-copy objects re-replicate), and the
    eviction lands as SIGKILL when the notice expires. A live
    autoscaler loop (StandardAutoscaler + ClusterNodeProvider over the
    same cluster) replaces the reclaimed capacity from its min_workers
    floor. Bars: zero wrong answers, zero lost tasks, exactly-once
    through the drain window (marker-file probe), the pre-storm
    sole-copy object survives, storm goodput >= 70% of calm."""
    import tempfile
    import threading

    from ray_tpu.autoscaler import (
        ClusterNodeProvider,
        Monitor,
        StandardAutoscaler,
    )
    from ray_tpu.cluster import fault_plane
    from ray_tpu.cluster.fault_plane import StormPlan
    from ray_tpu.cluster.process_cluster import ClusterClient, ProcessCluster

    from concurrent.futures import ThreadPoolExecutor

    seed = fault_plane.storm_seed_from_env(default=4321)
    storm = StormPlan(seed, duration_s=3.0, kinds=("preempt_node",))
    n_tasks = int(os.environ.get("RAY_TPU_PREEMPT_TASKS", "1600"))

    class SpotActor:
        def __init__(self):
            self.n = 0

        def bump(self, k):
            self.n += k
            return self.n

    def run_phase(client, cluster, preempt=None):
        """One mixed wave (tasks + an actor create/call/kill every 20
        submits) on a 16-thread pool; ``preempt`` optionally carries
        (victim_node, notice_s): halfway through, the victim gets the
        eviction notice and dies by SIGKILL when it expires — while the
        autoscaler loop (already running) back-fills the capacity."""
        lock = threading.Lock()

        def task_op(i):
            r = client.submit(lambda i=i: i * 31 + 7)
            return (1 if client.get(r, timeout=120.0) == i * 31 + 7
                    else -1)

        def actor_op(i):
            h = client.create_actor(SpotActor)
            try:
                ok = h.bump(i) == i
            finally:
                client.kill_actor(h)
            return 3 if ok else -1

        ops_list = []
        for i in range(n_tasks):
            ops_list.append((task_op, i))
            if i % 20 == 19:
                ops_list.append((actor_op, i))

        n_done = [0]
        fire_at = len(ops_list) // 2
        evict_thread = [None]

        def evict():
            victim, notice_s = preempt
            try:
                cluster.preempt_node(victim, notice_s=notice_s,
                                     reason="spot reclaim")
            except Exception:
                pass  # notice lost: the SIGKILL below still lands
            time.sleep(notice_s)
            try:
                cluster.kill_node(victim)  # the reclaim itself
            except KeyError:
                pass  # autoscaler already terminated it

        def run_op(item):
            fn, i = item
            got = 0
            for attempt in range(3):
                try:
                    got = fn(i)
                    break
                except Exception:
                    time.sleep(1.0 * (attempt + 1))
                    continue
            with lock:
                n_done[0] += 1
                fire = preempt is not None and n_done[0] == fire_at
            if fire:
                evict_thread[0] = threading.Thread(target=evict,
                                                   daemon=True)
                evict_thread[0].start()
            return got

        wrong = lost = ops = 0
        t0 = time.monotonic()
        with ThreadPoolExecutor(max_workers=16) as ex:
            for got in ex.map(run_op, ops_list):
                if got > 0:
                    ops += got
                elif got == 0:
                    lost += 1
                else:
                    wrong += 1
        elapsed = time.monotonic() - t0
        if evict_thread[0] is not None:
            evict_thread[0].join(timeout=60.0)
        return ops, wrong, lost, elapsed

    def drain_probe(client, cluster, victim, notice_s):
        """Exactly-once through the drain window: marker-file tasks
        pinned to the victim, the eviction notice lands mid-queue, the
        drain must neither drop nor re-run them (executions == n)."""
        marker = tempfile.mktemp(prefix="ray_tpu_preempt_")

        def task(p, i):
            fd = os.open(p, os.O_WRONLY | os.O_APPEND | os.O_CREAT,
                         0o644)
            try:
                os.write(fd, f"{i}\n".encode())
            finally:
                os.close(fd)
            return i

        n = 40
        refs = [client.submit(task, args=(marker, i), node_id=victim)
                for i in range(n)]
        cluster.preempt_node(victim, notice_s=notice_s, reason="probe")
        for ref in refs:
            client.get(ref, timeout=120.0)
        time.sleep(1.0)  # straggler writes
        try:
            with open(marker) as f:
                executed = len(f.read().splitlines())
            os.unlink(marker)
        except FileNotFoundError:
            executed = 0
        return executed - n

    cluster = ProcessCluster(heartbeat_period_ms=100,
                             num_heartbeats_timeout=15)
    out = {}
    monitor = None
    try:
        nodes = [cluster.add_node(num_cpus=2) for _ in range(3)]
        cluster.wait_for_nodes(3)
        client = ClusterClient(cluster.gcs_address)
        try:
            client.get(client.submit(lambda: 1))  # warm the lanes
            for _ in range(6):
                h = client.create_actor(SpotActor)
                h.bump(1)
                client.kill_actor(h)

            events = storm.kill_events()
            ev = events[0] if events else {"ordinal": 0, "notice_s": 2.0}
            victim = nodes[ev["ordinal"] % len(nodes)]
            # a generous window on loaded hosts: the notice jitter is
            # the storm's, the floor keeps the drain schedulable
            notice_s = max(float(ev.get("notice_s", 2.0)), 2.0)

            # a sole-copy payload living ONLY on the victim: the drain
            # must move it off before the eviction lands
            payload = os.urandom(64 * 1024)
            sole_ref = client.submit(lambda p=payload: p, node_id=victim)
            assert client.get(sole_ref, timeout=60.0) == payload

            autoscaler = StandardAutoscaler(
                {"available_node_types": {
                    "worker": {"resources": {"CPU": 2},
                               "min_workers": 3, "max_workers": 4}},
                 "max_workers": 4, "idle_timeout_s": 3600.0},
                ClusterNodeProvider({"worker_node_type": "worker"},
                                    cluster=cluster))
            monitor = Monitor(autoscaler, interval_s=1.0)
            monitor.start()

            calm_ops, calm_w, calm_l, calm_s = run_phase(client, cluster)
            st_ops, st_w, st_l, st_s = run_phase(
                client, cluster, preempt=(victim, notice_s))
            calm2_ops, calm2_w, calm2_l, calm2_s = run_phase(
                client, cluster)
            calm_ops += calm2_ops
            calm_s += calm2_s
            calm_w += calm2_w
            calm_l += calm2_l

            # let the reconcile loop converge before reading the
            # elastic-capacity counters: replacing the evicted node IS
            # the scenario, and on a saturated 1-core host the monitor
            # thread can be starved for the whole load phase — give it
            # an unloaded window to land the min_workers top-up
            converge_deadline = time.monotonic() + 90.0
            while time.monotonic() < converge_deadline:
                alive_now = sum(
                    1 for i in client.cluster_view()["nodes"].values()
                    if i["alive"])
                if autoscaler.num_launches >= 1 and alive_now >= 3:
                    break
                time.sleep(1.0)

            # exactly-once probe LAST (its long notice leaves the probe
            # node draining; nothing runs after that could care)
            probe_victim = next(
                nid for nid, info in
                client.cluster_view()["nodes"].items() if info["alive"]
                and info.get("state") != "DRAINING")
            dup = drain_probe(client, cluster, probe_victim,
                              notice_s=30.0)

            sole_survived = False
            try:
                sole_survived = client.get(sole_ref,
                                           timeout=60.0) == payload
            except Exception:
                sole_survived = False

            view = client.cluster_view()
            drain_stats = view.get("drain", {})
            alive_after = sum(1 for i in view["nodes"].values()
                              if i["alive"])
            calm_goodput = calm_ops / calm_s if calm_s else 0.0
            storm_goodput = st_ops / st_s if st_s else 0.0
            out = {
                "preempt_storm_seed": seed,
                "preempt_notice_s": notice_s,
                "preempt_calm_ops_per_s": round(calm_goodput, 1),
                "preempt_storm_ops_per_s": round(storm_goodput, 1),
                "preempt_storm_vs_calm_pct": round(
                    100.0 * storm_goodput / calm_goodput, 1)
                if calm_goodput else 0.0,
                "preempt_wrong_answers": calm_w + st_w,
                "preempt_lost_tasks": calm_l + st_l,
                "preempt_dup_executions": max(0, dup),
                "preempt_sole_copy_survived": bool(sole_survived),
                "preempt_drains_completed": drain_stats.get(
                    "drains_completed", 0),
                "preempt_notices_seen": drain_stats.get(
                    "preemption_notices", 0),
                "preempt_objects_rereplicated": drain_stats.get(
                    "objects_rereplicated", 0),
                "preempt_autoscaler_launches": autoscaler.num_launches,
                "preempt_alive_nodes_after": alive_after,
            }
        finally:
            if monitor is not None:
                monitor.stop()
                autoscaler.load_metrics.close()
            client.close()
    finally:
        cluster.shutdown()
    return out


ALL_ROWS = ("scheduler", "broadcast", "serve", "actor_churn", "chaos",
            "preemption")


def _selected_rows() -> set:
    """--rows scheduler,serve — run row groups independently."""
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--rows", default=",".join(ALL_ROWS),
                   help="comma-separated subset of: " + ",".join(ALL_ROWS))
    args, _ = p.parse_known_args()
    rows = {r.strip() for r in args.rows.split(",") if r.strip()}
    unknown = rows - set(ALL_ROWS)
    if unknown:
        raise SystemExit(f"unknown --rows {sorted(unknown)}; "
                         f"choose from {ALL_ROWS}")
    return rows


def main():
    """Runs the selected rows on the backend JAX resolves and prints one
    JSON line stamped with the device. A row that raises ends the run
    with a traceback and a non-zero exit: no fallback, no ``*_error``
    key."""
    import jax

    from ray_tpu._private.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows = _selected_rows()
    if "scheduler" in rows:
        result = bench_scheduler()
    else:
        result = {"metric": "partial_bench_rows", "value": 1.0,
                  "unit": "rows", "vs_baseline": 1.0,
                  "rows": sorted(rows)}
    dev = jax.devices()[0]
    result["backend"] = dev.platform
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}
    row_fns = (("broadcast", bench_object_broadcast),
               ("serve", bench_serve), ("actor_churn", bench_actor_churn),
               ("chaos", bench_chaos), ("preemption", bench_preemption))
    for name, fn in row_fns:
        if name in rows:
            result.update(fn())
    print(json.dumps(result))


if __name__ == "__main__":
    main()
