"""Pipelined scheduler tick suite (marker: scheduler_pipeline).

Covers the r06 tentpole and its satellites: the double-buffered drain
loop vs the single-buffered reference tick (same drained task set, exact
availability accounting, no lost or invented work), the per-instance
tick-anatomy rate limiter, the DeviceMatrixMirror freshness protocol
(delta folds, version-jump and periodic full re-syncs, the debug drift
check), repair_oversubscription's f32 edge cases, the gate to the
device solve, and a raycheck-clean assertion over every file this PR
touched (with RC01 pinned live so "clean" keeps meaning something).

The live drives freeze dispatch (dependencies never ready) so
placements and queue/infeasible membership are the whole observable
state.
"""

import os
import threading
import time

import numpy as np
import pytest

from ray_tpu._private.config import Config
from ray_tpu._private.ids import JobID, NodeID, TaskID
from ray_tpu.core.raylet import (
    ClusterState,
    Raylet,
    _PendingTask,
    _TickPhases,
    _TickRateLimiter,
)
from ray_tpu.core.task_spec import (
    TaskKind,
    TaskSpec,
    scheduling_class_of,
)
from ray_tpu.scheduler import policy as policy_mod
from ray_tpu.scheduler.policy import (
    BatchedHybridPolicy,
    DeviceMatrixMirror,
)
from ray_tpu.scheduler.resources import to_fixed

pytestmark = pytest.mark.scheduler_pipeline


class _FrozenDeps:
    def wait_ready(self, spec, callback):
        pass


def _build_cluster(n_nodes, seed=0):
    rng = np.random.default_rng(seed)
    cluster = ClusterState()
    deps = _FrozenDeps()
    raylets = []
    for _ in range(n_nodes):
        resources = {
            "CPU": float(rng.integers(4, 32)),
            "MEM": float(rng.integers(8, 64)),
        }
        raylets.append(Raylet(NodeID.from_random(), resources, cluster,
                              deps))
        cluster.register(raylets[-1])
    return cluster, raylets


def _enqueue(cluster, head, n_tasks, n_classes, seed=1,
             infeasible_every=0):
    rng = np.random.default_rng(seed)
    demands = []
    for c in range(n_classes):
        d = {"CPU": float(rng.integers(1, 4))}
        if c % 3 == 0:
            d["MEM"] = float(rng.integers(1, 8))
        demands.append(d)
    job = JobID.from_int(5)
    parent = TaskID.for_task(None)
    specs = []
    with head._lock:
        for i in range(n_tasks):
            if infeasible_every and i % infeasible_every == 0:
                d = {"CPU": 1e9}  # no node can ever host this
            else:
                d = demands[i % n_classes]
            spec = TaskSpec(
                kind=TaskKind.NORMAL, task_id=TaskID.for_task(None),
                job_id=job, parent_task_id=parent, name=f"t{i}",
                resources=dict(d))
            spec.scheduling_class = scheduling_class_of(
                spec.resource_request(cluster.ids))
            task = _PendingTask(spec, lambda r, w: None, 0)
            head._pending.append(task)
            head._by_task_id[spec.task_id] = task
            specs.append(spec)
    return specs


def _drain(head, max_ticks=256):
    for _ in range(max_ticks):
        head.schedule_tick()
        with head._lock:
            if not head._pending:
                break


def _task_states(specs, raylets):
    """task name -> ('run'|'queued'|'infeasible'|'pending')."""
    name_of = {s.task_id: s.name for s in specs}
    states = {}
    for raylet in raylets:
        with raylet._lock:
            for tid in raylet._running:
                states[name_of[tid]] = "run"
            for q in raylet._dispatch_queues.values():
                for task in q:
                    states[name_of[task.spec.task_id]] = "queued"
            for task in raylet._infeasible:
                states[name_of[task.spec.task_id]] = "infeasible"
            for task in raylet._pending:
                states[name_of[task.spec.task_id]] = "pending"
    return states


@pytest.fixture
def pipeline_cfg():
    """Force the device solve for any batched class, restore after."""
    cfg = Config.instance()
    saved = {
        "scheduler_pipeline_enabled": cfg.scheduler_pipeline_enabled,
        "scheduler_device_solve_min_cells":
            cfg.scheduler_device_solve_min_cells,
        "scheduler_pipeline_debug_check":
            cfg.scheduler_pipeline_debug_check,
        "scheduler_matrix_sync_period": cfg.scheduler_matrix_sync_period,
    }
    cfg._set("scheduler_device_solve_min_cells", 0)
    try:
        yield cfg
    finally:
        for k, v in saved.items():
            cfg._set(k, v)


# --------------------------------------------------------------- tentpole


@pytest.mark.parametrize("device", [True, False])
def test_pipeline_drains_same_task_set_as_single(pipeline_cfg, device):
    """Pipeline on vs off over the same seeded queue: every task ends in
    the same terminal category set (drained vs infeasible), nothing is
    lost or duplicated, and the exact int64 availability never goes
    negative. Placement SEQUENCE may differ (the pipelined solve is
    stale by one batch, then exact-repaired) — membership must not."""
    cfg = pipeline_cfg
    cfg._set("scheduler_device_solve_min_cells", 0 if device else -1)
    cfg._set("scheduler_pipeline_debug_check", True)
    results = {}
    for pipeline_on in (False, True):
        cfg._set("scheduler_pipeline_enabled", pipeline_on)
        cluster, raylets = _build_cluster(24)
        specs = _enqueue(cluster, raylets[0], 6_000, 8,
                         infeasible_every=997)
        _drain(raylets[0])
        states = _task_states(specs, raylets)
        assert len(states) == len(specs), "tasks lost or duplicated"
        assert "pending" not in states.values(), "queue failed to drain"
        with cluster.lock:
            cluster.refresh_locked()
            assert np.all(cluster.matrix.available >= 0)
        results[pipeline_on] = {
            name for name, st in states.items() if st == "infeasible"}
    assert results[True] == results[False], (
        "pipeline changed the infeasible set")


def _build_pinned_cluster(n_decoys=7):
    """Head with huge capacity + a PIN resource only it owns, plus
    decoy nodes: every placement lands locally (no spillback cascade —
    a peer's submit() re-ticks it, which re-ticks the head), so batch
    accounting per schedule_tick call is exact."""
    cluster = ClusterState()
    deps = _FrozenDeps()
    head = Raylet(NodeID.from_random(),
                  {"CPU": 1e6, "PIN": 1e6}, cluster, deps)
    cluster.register(head)
    raylets = [head]
    for i in range(n_decoys):
        raylets.append(Raylet(NodeID.from_random(),
                              {"CPU": 16.0 + i}, cluster, deps))
        cluster.register(raylets[-1])
    return cluster, head, raylets


def _enqueue_pinned(cluster, head, n_tasks, n_classes):
    job = JobID.from_int(5)
    parent = TaskID.for_task(None)
    specs = []
    with head._lock:
        for i in range(n_tasks):
            d = {"CPU": round(1.0 + (i % n_classes) * 0.125, 3),
                 "PIN": 0.001}
            spec = TaskSpec(
                kind=TaskKind.NORMAL, task_id=TaskID.for_task(None),
                job_id=job, parent_task_id=parent, name=f"p{i}",
                resources=d)
            spec.scheduling_class = scheduling_class_of(
                spec.resource_request(cluster.ids))
            task = _PendingTask(spec, lambda r, w: None, 0)
            head._pending.append(task)
            head._by_task_id[spec.task_id] = task
            specs.append(spec)
    return specs


def test_pipeline_off_is_single_buffered_and_mirror_free(pipeline_cfg):
    """The master switch off keeps the old tick: no DeviceMatrixMirror
    is ever built, and each schedule_tick call consumes at most one
    batch (the pipelined drain would empty the whole queue in one)."""
    cfg = pipeline_cfg
    cfg._set("scheduler_pipeline_enabled", False)
    old_batch = cfg.scheduler_max_tasks_per_tick
    cfg._set("scheduler_max_tasks_per_tick", 512)
    try:
        cluster, head, raylets = _build_pinned_cluster()
        _enqueue_pinned(cluster, head, 2_048, 4)
        head.schedule_tick()
        assert cluster.device_mirror is None
        with head._lock:
            remaining = len(head._pending)
        assert remaining == 2_048 - 512, (
            "pipeline-off tick must consume exactly one batch")
    finally:
        cfg._set("scheduler_max_tasks_per_tick", old_batch)


def test_pipelined_drain_empties_queue_in_one_call(pipeline_cfg):
    cfg = pipeline_cfg
    cfg._set("scheduler_pipeline_enabled", True)
    old_batch = cfg.scheduler_max_tasks_per_tick
    cfg._set("scheduler_max_tasks_per_tick", 512)
    try:
        cluster, raylets = _build_cluster(8)
        specs = _enqueue(cluster, raylets[0], 2_048, 4)
        raylets[0].schedule_tick()
        with raylets[0]._lock:
            assert not raylets[0]._pending
        states = _task_states(specs, raylets)
        assert len(states) == len(specs)
        assert "pending" not in states.values()
        # the device path ran against the shared mirror
        assert cluster.device_mirror is not None
        assert cluster.device_mirror.full_syncs >= 1
    finally:
        cfg._set("scheduler_max_tasks_per_tick", old_batch)


def test_epoch_fence_discards_stale_device_solve(pipeline_cfg):
    """A node dying between a device solve's dispatch and its commit
    bumps the topology epoch; ``_finish_device_batch`` must discard the
    stale device counts wholesale, re-solve on host against the
    repaired matrix, and never commit a placement onto the dead node
    after the death (placements made while it was alive are lineage's
    problem, not the fence's)."""
    from ray_tpu.cluster import overload as _overload
    from ray_tpu.observability.metrics import tick_epoch_fences

    cfg = pipeline_cfg
    cfg._set("scheduler_pipeline_enabled", True)
    cluster, raylets = _build_cluster(8, seed=11)
    head, dead = raylets[0], raylets[-1]
    specs = _enqueue(cluster, head, 2_000, 4, seed=2)
    orig = head._pipeline_front_half
    snap = {}

    def front_half_then_kill(cfg2, opts, batch, ph):
        out = orig(cfg2, opts, batch, ph)
        if out[0] is not None and "pre_death" not in snap:
            # death lands exactly in the fence window: a solve is in
            # flight, its commit has not run yet
            cluster.unregister(dead.node_id)
            with dead._lock:
                snap["pre_death"] = (
                    set(dead._running)
                    | {t.spec.task_id for q in
                       dead._dispatch_queues.values() for t in q}
                    | {t.spec.task_id for t in dead._pending})
        return out

    head._pipeline_front_half = front_half_then_kill
    before = sum(tick_epoch_fences.series().values())
    try:
        _drain(head)
    finally:
        head._pipeline_front_half = orig
        _overload.reset()  # the fence feeds the scheduler lane breaker
    assert "pre_death" in snap, "no device solve was ever in flight"
    assert sum(tick_epoch_fences.series().values()) > before
    states = _task_states(specs, raylets)
    assert len(states) == len(specs), "tasks lost or duplicated"
    assert "pending" not in states.values()
    with dead._lock:
        post = (set(dead._running)
                | {t.spec.task_id for q in
                   dead._dispatch_queues.values() for t in q}
                | {t.spec.task_id for t in dead._pending})
    assert post <= snap["pre_death"], (
        "fenced tick committed placements onto the dead node")


def test_epoch_fence_off_reroutes_via_commit_guard(pipeline_cfg):
    """``tick_epoch_fencing=False``: the stale counts commit anyway and
    the commit-time ``target is None`` guard reroutes groups aimed at
    the vanished node through the per-task path — correctness holds,
    but no fence is counted."""
    from ray_tpu.cluster import overload as _overload
    from ray_tpu.observability.metrics import tick_epoch_fences

    cfg = pipeline_cfg
    cfg._set("scheduler_pipeline_enabled", True)
    old_fence = cfg.tick_epoch_fencing
    cfg._set("tick_epoch_fencing", False)
    cluster, raylets = _build_cluster(8, seed=11)
    head, dead = raylets[0], raylets[-1]
    specs = _enqueue(cluster, head, 2_000, 4, seed=2)
    orig = head._pipeline_front_half
    state = {"killed": False}

    def front_half_then_kill(cfg2, opts, batch, ph):
        out = orig(cfg2, opts, batch, ph)
        if out[0] is not None and not state["killed"]:
            state["killed"] = True
            cluster.unregister(dead.node_id)
        return out

    head._pipeline_front_half = front_half_then_kill
    before = sum(tick_epoch_fences.series().values())
    try:
        _drain(head)
    finally:
        head._pipeline_front_half = orig
        cfg._set("tick_epoch_fencing", old_fence)
        _overload.reset()
    assert state["killed"]
    assert sum(tick_epoch_fences.series().values()) == before
    states = _task_states(specs, raylets)
    assert len(states) == len(specs), "tasks lost or duplicated"
    assert "pending" not in states.values()


def test_spillback_batched_single_frame_per_target(pipeline_cfg):
    """Remote placements fan out through submit_batch: one pending
    extension per target raylet, and the spilled tasks land with
    spillback_count bumped."""
    cfg = pipeline_cfg
    cfg._set("scheduler_pipeline_enabled", True)
    cluster, raylets = _build_cluster(4)
    head, target = raylets[0], raylets[1]
    calls = []
    original = target.submit_batch

    def spy(tasks):
        calls.append([t.spillback_count for t in tasks])
        return original(tasks)

    target.submit_batch = spy
    try:
        job = JobID.from_int(6)
        parent = TaskID.for_task(None)
        tasks = []
        for i in range(5):
            spec = TaskSpec(
                kind=TaskKind.NORMAL, task_id=TaskID.for_task(None),
                job_id=job, parent_task_id=parent, name=f"s{i}",
                resources={"CPU": 1.0})
            spec.scheduling_class = scheduling_class_of(
                spec.resource_request(cluster.ids))
            tasks.append(_PendingTask(spec, lambda r, w: None, 0))
        head._spillback_batched([(t, target) for t in tasks])
        assert calls == [[1] * 5], (
            "expected ONE batched frame with the hop count bumped, "
            f"got {calls}")
        # every task must land SOMEWHERE in the cluster (the target's
        # own tick may legally re-place or even dispatch them)
        names = {t.spec.name for t in tasks}
        landed = set()
        name_of = {t.spec.task_id: t.spec.name for t in tasks}
        for raylet in raylets:
            with raylet._lock:
                landed |= {t.spec.name for t in raylet._pending
                           if t.spec.name in names}
                landed |= {t.spec.name
                           for q in raylet._dispatch_queues.values()
                           for t in q if t.spec.name in names}
                landed |= {name_of[tid] for tid in raylet._running
                           if tid in name_of}
        assert landed == names, f"lost tasks: {names - landed}"
        assert head.num_spilled_back == 5
    finally:
        target.submit_batch = original


# ------------------------------------------------- satellite 1: rate limit


def test_tick_limiter_is_per_instance():
    """Two raylets tick inside the same MIN_INTERVAL_S window: each has
    its own limiter, so BOTH get instrumented anatomy (the old class
    global let one chatty raylet starve every other instance)."""
    cluster_a, raylets_a = _build_cluster(1, seed=1)
    cluster_b, raylets_b = _build_cluster(1, seed=2)
    now = time.monotonic()
    assert raylets_a[0]._tick_limiter is not raylets_b[0]._tick_limiter
    ph_a = _TickPhases(True, raylets_a[0]._tick_limiter)
    ph_b = _TickPhases(True, raylets_b[0]._tick_limiter)
    assert ph_a.enabled and ph_b.enabled, (
        "a fresh raylet's first tick must always be instrumented, "
        "regardless of other raylets' ticks")
    # within the window the SAME raylet is sampled out...
    ph_a2 = _TickPhases(True, raylets_a[0]._tick_limiter)
    assert not ph_a2.enabled
    # ...until its limiter is reset (the bench/test defeat hook)
    raylets_a[0]._tick_limiter.reset()
    assert _TickPhases(True, raylets_a[0]._tick_limiter).enabled


def test_tick_limiter_thread_safe_single_winner():
    """N threads race one limiter inside one interval: exactly one
    acquires (the old unsynchronized read-modify-write could admit
    several)."""
    limiter = _TickRateLimiter()
    results = []
    barrier = threading.Barrier(8)

    def worker():
        barrier.wait()
        results.append(limiter.try_acquire(time.monotonic(), 3600.0))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(results) == 1


# ---------------------------------------------- satellite 3: repair edges


class TestRepairOversubscription:
    @pytest.mark.parametrize("wrong_by", [-1, 0, 1])
    def test_device_quotient_is_exact_below_2pow24(self, monkeypatch,
                                                   wrong_by):
        """The solve's float32 quotient equals ``a // b`` wherever
        a + b < 2^24, whichever way the device's division is off: a
        capacity one too LOW is a legal, different placement that the
        repair below can never see."""
        import jax.numpy as jnp

        rng = np.random.default_rng(7)
        a = rng.integers(0, 2 ** 24 - 2 ** 20, 200_000)
        b = rng.integers(1, 2 ** 20, 200_000)
        # a division that misses on every tenth pair
        monkeypatch.setattr(jnp, "floor", lambda x: (
            np.floor(x) + wrong_by * (np.arange(x.size) % 10 == 0)))
        q = BatchedHybridPolicy._floor_div(
            jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32))
        assert np.array_equal(np.asarray(q).astype(np.int64), a // b)

    def test_f32_boundary_2pow24(self):
        """Availability just past 2^24: f32 rounds the capacity up by
        one; the exact int64 repair must clamp it back."""
        avail = np.array([[2 ** 24 + 1]], dtype=np.int64)
        reqs = np.array([[3]], dtype=np.int64)
        exact_cap = (2 ** 24 + 1) // 3
        # a device solve that believed f32((2^24+1)/3) could claim one
        # extra placement
        counts = np.array([[exact_cap + 1]], dtype=np.int64)
        repaired = BatchedHybridPolicy.repair_oversubscription(
            reqs, counts, avail)
        assert repaired[0, 0] == exact_cap
        assert int(avail[0, 0]) - int(repaired[0, 0]) * 3 >= 0

    def test_evict_from_fully_committed_node(self):
        """A node with zero availability (every unit committed) must
        come back with zero placements, and the spare node keeps its
        legitimate counts."""
        avail = np.array([[0, 0], [to_fixed(8), to_fixed(4)]],
                         dtype=np.int64)
        reqs = np.array([[to_fixed(1), to_fixed(1)]], dtype=np.int64)
        counts = np.array([[3, 4]], dtype=np.int64)  # 3 on the full node
        repaired = BatchedHybridPolicy.repair_oversubscription(
            reqs, counts, avail)
        assert repaired[0, 0] == 0
        assert repaired[0, 1] == 4
        usage = repaired.T @ reqs
        assert np.all(avail - usage >= 0)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_seeded_random_never_negative(self, seed):
        """Random matrices with deliberately oversubscribed counts: the
        post-repair int64 availability is >= 0 everywhere."""
        rng = np.random.default_rng(seed)
        n, r, c = 17, 5, 7
        avail = rng.integers(0, 2 ** 26, size=(n, r)).astype(np.int64)
        reqs = rng.integers(0, 2 ** 12, size=(c, r)).astype(np.int64)
        reqs[rng.random((c, r)) < 0.4] = 0
        reqs[:, 0] = np.maximum(reqs[:, 0], 1)  # no all-zero demand
        counts = rng.integers(0, 2 ** 15, size=(c, n)).astype(np.int64)
        repaired = BatchedHybridPolicy.repair_oversubscription(
            reqs, counts, avail)
        usage = repaired.T @ reqs
        assert usage.dtype == np.int64
        assert np.all(avail - usage >= 0)
        assert np.all(repaired >= 0)
        assert np.all(repaired <= counts)

    def test_fast_path_matches_clamp_loop(self):
        """When the whole batch fits, the vectorized fast path must
        return exactly what the per-class clamp loop would."""
        rng = np.random.default_rng(7)
        n, r, c = 9, 4, 5
        reqs = rng.integers(1, 50, size=(c, r)).astype(np.int64)
        counts = rng.integers(0, 20, size=(c, n)).astype(np.int64)
        # availability built to fit the entire batch exactly
        avail = (counts.T @ reqs) + rng.integers(
            0, 10, size=(n, r)).astype(np.int64)

        def reference_loop(reqs, counts, avail):
            counts = counts.copy()
            avail = avail.astype(np.int64).copy()
            for ci in range(counts.shape[0]):
                req = reqs[ci]
                pos = req > 0
                if pos.any():
                    cap = np.min(avail[:, pos] // req[pos], axis=1)
                    counts[ci] = np.minimum(counts[ci],
                                            np.maximum(cap, 0))
                avail -= counts[ci][:, None] * req[None, :]
            return counts

        fast = BatchedHybridPolicy.repair_oversubscription(
            reqs, counts, avail)
        assert np.array_equal(fast, reference_loop(reqs, counts, avail))
        assert np.array_equal(fast, counts)  # fits -> untouched


# ------------------------------------------- the gate to the device solve


def _device_solves() -> int:
    from ray_tpu.observability.metrics import scheduler_device_solves

    return int(sum(scheduler_device_solves.series().values()))


class TestDeviceSolveGate:
    """Whether a live tick takes the jitted solve is decided in this
    process from what it observes — the config switch and the tick's
    (nodes x batched classes) cell count — and nothing else: no probe,
    no verdict cached between processes."""

    @pytest.mark.parametrize("short_by,expect_device", [
        (0, True),       # exactly the threshold
        (1, False),      # one cell short: numpy
        (None, False),   # the switch: off
    ])
    def test_gate_is_switch_and_cell_count(self, pipeline_cfg, short_by,
                                           expect_device):
        cluster, raylets = _build_cluster(24)
        specs = _enqueue(cluster, raylets[0], 2_000, 8)
        cells = 24 * len({s.scheduling_class for s in specs})
        pipeline_cfg._set("scheduler_device_solve_min_cells",
                          -1 if short_by is None else cells + short_by)
        before = _device_solves()
        _drain(raylets[0])
        assert "pending" not in _task_states(specs, raylets).values()
        assert (_device_solves() > before) == expect_device

    @pytest.mark.parametrize("pipeline_on", [True, False])
    def test_failing_device_solve_raises(self, pipeline_cfg, monkeypatch,
                                         pipeline_on):
        """A device solve that fails surfaces from the tick: nothing
        falls back to numpy behind the caller's back."""
        pipeline_cfg._set("scheduler_pipeline_enabled", pipeline_on)
        cluster, raylets = _build_cluster(8)
        _enqueue(cluster, raylets[0], 500, 4)

        def broken(*args, **kwargs):
            raise RuntimeError("device solve failed")

        monkeypatch.setattr(policy_mod.shared_batched_policy(True),
                            "schedule_tick_fused", broken)
        with pytest.raises(RuntimeError, match="device solve failed"):
            raylets[0].schedule_tick()


# --------------------------------------------- mirror freshness protocol


class TestDeviceMatrixMirror:
    def _matrix(self, n_nodes=4):
        cluster, raylets = _build_cluster(n_nodes)
        with cluster.lock:
            cluster.refresh_locked()
        return cluster, raylets, cluster.matrix

    def test_full_then_delta_then_periodic_full(self):
        cluster, raylets, matrix = self._matrix()
        mirror = DeviceMatrixMirror()
        t, a, al, up = mirror.refresh(matrix, sync_period=2)
        assert mirror.full_syncs == 1 and mirror.delta_syncs == 0
        assert up > 0
        assert np.array_equal(
            np.asarray(a), matrix.available.astype(np.float32))
        # a row-level change (no version bump) folds as a delta
        raylets[1].local_resources.available[0] -= to_fixed(1)
        cluster.sync(raylets[1])
        with cluster.lock:
            cluster.refresh_locked()
        assert matrix.version == mirror._version
        _, a, _, up = mirror.refresh(matrix, sync_period=2)
        assert mirror.delta_syncs == 1 and mirror.full_syncs == 1
        assert 0 < up < matrix.available.nbytes  # bytes ~ dirty rows
        assert np.array_equal(
            np.asarray(a), matrix.available.astype(np.float32))
        # clean refreshes upload nothing...
        _, _, _, up = mirror.refresh(matrix, sync_period=2)
        assert up == 0
        # ...until the periodic full re-sync fires (2 refreshes since)
        mirror.refresh(matrix, sync_period=2)
        assert mirror.full_syncs == 2

    def test_version_jump_forces_full_resync(self):
        cluster, raylets, matrix = self._matrix()
        mirror = DeviceMatrixMirror()
        mirror.refresh(matrix, sync_period=1000)
        deps = _FrozenDeps()
        newcomer = Raylet(NodeID.from_random(), {"CPU": 4.0}, cluster,
                          deps)
        cluster.register(newcomer)  # new slot -> version bump
        with cluster.lock:
            cluster.refresh_locked()
        _, a, _, _ = mirror.refresh(matrix, sync_period=1000)
        assert mirror.full_syncs == 2
        assert np.asarray(a).shape[0] == matrix.available.shape[0]

    def test_debug_check_catches_unreported_mutation(self):
        """A host-matrix write that bypasses the dirty-row protocol is
        exactly the bug class debug_check exists for."""
        cluster, raylets, matrix = self._matrix()
        mirror = DeviceMatrixMirror()
        mirror.refresh(matrix, sync_period=1000, debug_check=True)
        matrix.available[2, 0] -= to_fixed(2)  # no _dirty_rows entry
        with pytest.raises(AssertionError, match="drifted"):
            mirror.refresh(matrix, sync_period=1000, debug_check=True)

    def test_delta_bucket_padding_is_idempotent(self):
        """Dirty-row counts between bucket sizes pad by repeating the
        last row; the scatter must stay exact."""
        cluster, raylets, matrix = self._matrix(n_nodes=8)
        mirror = DeviceMatrixMirror()
        mirror.refresh(matrix, sync_period=100)
        for slot in (1, 3, 6):  # 3 dirty rows -> bucket of 4
            raylets[slot].local_resources.available[0] -= to_fixed(1)
            cluster.sync(raylets[slot])
        with cluster.lock:
            cluster.refresh_locked()
        _, a, _, _ = mirror.refresh(matrix, sync_period=100)
        assert np.array_equal(
            np.asarray(a), matrix.available.astype(np.float32))


# ------------------------------------ satellite 5: raycheck-clean assertion


TOUCHED_FILES = [
    "ray_tpu/core/raylet.py",
    "ray_tpu/scheduler/policy.py",
    "ray_tpu/scheduler/resources.py",
    "ray_tpu/_private/config.py",
]

RAYCHECK_RULES = "RC01,RC02,RC03,RC05,RC10"


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_raycheck_clean_on_touched_files():
    """Every file the pipelined-tick PR touched stays clean under the
    static rules: no blocking calls under a lock (RC01), no wall-clock
    deadline math (RC02), no unseeded randomness (RC03/RC05), no
    unbounded queues (RC10)."""
    from ray_tpu.tools.raycheck.__main__ import main

    paths = [os.path.join(_repo_root(), p) for p in TOUCHED_FILES]
    for p in paths:
        assert os.path.exists(p), p
    rc = main(paths + ["--rules", RAYCHECK_RULES])
    assert rc == 0, "raycheck found violations in touched files"


def test_raycheck_rc01_still_fires(tmp_path):
    """Pin RC01: a sleep under a lock-named `with` must be flagged —
    otherwise the clean assertion above proves nothing."""
    from ray_tpu.tools.raycheck.__main__ import main

    core = tmp_path / "core"  # RC01 is scoped to cluster/core/serve
    core.mkdir()
    bad = core / "bad_lock_sleep.py"
    bad.write_text(
        "import time\n"
        "def f(self):\n"
        "    with self._lock:\n"
        "        time.sleep(1.0)\n")
    rc = main([str(tmp_path), "--rules", "RC01"])
    assert rc != 0, "RC01 failed to flag a sleep under a lock"
