"""Quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py [--seed N]      one chip: every phase below
    python chip_smoke.py --chips 4       four chips: the sharded steps only

One process; it imports JAX itself, sets no platform, and starts no child
that needs the chip (process workers get the CPU backend — see
ray_tpu/cluster/child_env.py). It exits non-zero before any phase unless
``jax.devices()[0].platform == "tpu"``. No phase is wrapped in an
``except`` that lets the run go on: a failure is a traceback and a
non-zero exit. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, with the device as JAX reports it.

Phases with one chip, each through the entry points a user calls:

  scheduler   256 heterogeneous nodes x 32 scheduling classes x 100 000
              pending tasks drained through Raylet.submit /
              Raylet.schedule_tick at the default
              scheduler_device_solve_min_cells. Every drain: no node
              over capacity in exact int64. The default (pipelined)
              tick: its solves ran on the tpu, and each equals the numpy
              solve of the inputs it was given. The single-buffered
              tick, once through the device solve and once through the
              numpy policy from the same seed: same counts, 0 differing
              (class, node) cells. The solve's float32 quotient against
              integer division. Then ray_tpu.init() /
              cluster_utils.Cluster / @remote tasks that return values,
              and two process workers.
  kernels     flash_attention forward and gradients at B4-S2048-H16-D128
              bf16 against attention_reference in float32.
  train       train.Trainer(backend="jax", num_workers=1) whose function
              builds build_train_step at the full width of the 632 M
              dense model (depth as published: 12 layers), checks the
              Pallas forward and both backward kernels are in the
              compiled step, and takes three steps on one fixed batch.

With ``--chips 4``: the GSPMD steps (dp+fsdp+sp with ring attention;
dp+fsdp+tp with the flash kernels) and the pipeline step
(pp+tp) of ``__graft_entry__.dryrun_multichip`` at hidden 2048 /
head_dim 128 / sequence 2048 with depth cut to 4 layers, each against
the same step on a one-device mesh from the same seed and batch.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time

import numpy as np

# Widths of the dense model this smoke has always run (~632 M parameters
# at 12 layers). Depth is the only thing a phase may cut.
WIDTHS = dict(vocab_size=32_000, hidden=2048, heads=16, kv_heads=8,
              intermediate=5632, max_seq=2048)
SEQ = 2048
FULL_DEPTH = 12
FOUR_CHIP_DEPTH = 4
# The train phase's batch: the compiler puts 3.79e9 B of arguments and
# 8.13e9 B of temporaries in 16 GB at 16; the r05 headline batch of 40
# compiles with almost no room. Batch is not width.
BATCH = 16

# bf16 carries 8 significand bits (eps = 2^-8). The kernels round P and
# dS to bf16 before their second matmul and the outputs once more, so a
# few eps of the largest reference value is what exact math allows.
KERNEL_TOL = 2e-2
# One device vs four: same seed, same batch, bf16 activations, float32
# loss; only summation order and the attention tier differ. Loss and
# gradient norm are held at every step (the fsdp and pipeline cases
# measured <= 9.5e-4 on the chip at every step).
LOSS_RTOL = 1e-2
GRAD_NORM_RTOL = 1e-2

N_NODES, N_CLASSES, N_TASKS = 256, 32, 100_000


def say(msg: str) -> None:
    print(msg, flush=True)


def compiles_since(since: float = 0.0, by_name: bool = False) -> str:
    """The compiles that ended after ``since`` (time.perf_counter), as
    the program's own registry recorded them: the persistent cache's hits
    and misses and what missed; ``by_name`` lists every program with the
    phases of its builds (trace, lower, and the cache's read or the
    compile) and, where its executable was noted, the bytes of its code:
    what it takes of the compile cache."""
    from ray_tpu.observability import device_programs

    events = device_programs.compiles(since)
    count = {c: sum(e.cache == c for e in events)
             for c in ("hit", "miss", "off")}
    out = f"cache hits {count['hit']}, misses {count['miss']}"
    if count["off"]:
        out += f", not asked of the cache {count['off']}"
    if not by_name:
        listed = [e for e in events if e.cache != "hit"]
        if listed:
            out += ": " + ", ".join(
                f"{e.program} {e.cache} {e.seconds:.1f} s" for e in listed)
        return out
    programs = {}  # program -> {phase: seconds}, in order of appearance
    for e in device_programs.builds(since):
        if (e.phase, e.cache) == ("compile", "hit"):
            continue  # what the hit took is its cache_read event
        phases = programs.setdefault(e.program, {})
        phase = e.phase.replace("_", " ")
        phases[phase] = phases.get(phase, 0.0) + e.seconds
    listed = []
    for program, phases in programs.items():
        said = program + " " + " + ".join(
            f"{phase} {seconds:.1f}" for phase, seconds in phases.items())
        memory = device_programs.memory_of(program)
        listed.append(said + " s" + (
            f", generated code {memory['generated_code']} B"
            if memory else ""))
    return out + ": " + ", ".join(listed) if listed else out


# --------------------------------------------------------------------------
# scheduler
# --------------------------------------------------------------------------

# (CPU, memory GiB, TPU, object_store_memory GiB, disk GiB, net Gbit/s):
# two CPU shapes and the two v5e host shapes. Every quantity stays under
# 2^24 fixed-point units, where float32 holds integers exactly.
MACHINES = (
    (32, 128, 0, 32, 400, 10),
    (96, 384, 0, 96, 1000, 25),
    (112, 192, 4, 48, 500, 100),
    (224, 384, 8, 96, 1000, 200),
)
RESOURCE_NAMES = ("CPU", "memory", "TPU", "object_store_memory", "disk",
                  "net")


class FrozenDeps:
    """Dependency manager whose tasks never become ready: placements
    commit and hold resources, nothing executes, so the placements are
    the whole observable state."""

    def wait_ready(self, spec, callback):
        pass

    def wait_ready_batch(self, tasks, batch_callback, callback):
        pass


@contextlib.contextmanager
def recorded_solves():
    """Every fused solve the live tick dispatches while this is open,
    as (host copies of what it was given, what it returned)."""
    from ray_tpu.scheduler.policy import shared_batched_policy

    policy = shared_batched_policy(use_jax=True)
    solve = policy.schedule_tick_fused
    calls = []

    def recording(reqs, ks, total, available, alive, local_slot, opts):
        counts = solve(reqs, ks, total, available, alive, local_slot, opts)
        calls.append(([np.asarray(x) for x in
                       (reqs, ks, total, available, alive)],
                      local_slot, opts, counts))
        return counts

    policy.schedule_tick_fused = recording  # shadows the method
    try:
        yield calls
    finally:
        del policy.schedule_tick_fused


def cells_differing_from_host_solve(calls) -> int:
    """(class, node) cells on which a recorded device solve differs from
    the numpy policy solving the same inputs in int64."""
    from ray_tpu.scheduler.policy import BatchedHybridPolicy

    host = BatchedHybridPolicy(use_jax=False)
    differing = 0
    for (reqs, ks, total, avail, alive), local_slot, opts, counts in calls:
        reqs, ks, total, avail = (np.asarray(x).astype(np.int64)
                                  for x in (reqs, ks, total, avail))
        want = host.schedule_classes(reqs, ks, total, avail, alive,
                                     local_slot, opts)
        got = np.asarray(counts)
        assert got.shape == want.shape == (N_CLASSES, N_NODES), got.shape
        differing += int((got != want).sum())
    return differing


def quotient_check(seed: int, n: int = 2_000_000):
    """The solve's float32 quotient on the device against integer
    division, over random integers in the range where it claims to be
    exact (a + b < 2^24) and over the fixed-point lattice this phase's
    capacities and demands lie on. Returns (pairs that differ, pairs)."""
    import jax

    from ray_tpu.scheduler.policy import BatchedHybridPolicy

    rng = np.random.default_rng(seed + 2)
    lattice_a = np.arange(0, 10_000_001, 2500)
    lattice_b = np.array([2500, 5000, 7500, 10_000, 15_000, 20_000, 30_000,
                          40_000, 50_000, 100_000, 1000])
    a = np.concatenate([rng.integers(0, (1 << 24) - (1 << 20), n),
                        np.repeat(lattice_a, len(lattice_b))])
    b = np.concatenate([rng.integers(1, 1 << 20, n),
                        np.tile(lattice_b, len(lattice_a))])
    q = jax.jit(BatchedHybridPolicy._floor_div)(
        a.astype(np.float32), b.astype(np.float32))
    assert next(iter(q.devices())).platform == jax.devices()[0].platform
    return int((np.asarray(q).astype(np.int64) != a // b).sum()), len(a)


def build_scheduler_cluster(seed: int):
    from ray_tpu._private.ids import NodeID
    from ray_tpu.core.raylet import ClusterState, Raylet

    rng = np.random.default_rng(seed)
    cluster = ClusterState()
    deps = FrozenDeps()
    raylets = []
    for _ in range(N_NODES):
        machine = MACHINES[int(rng.integers(len(MACHINES)))]
        resources = {name: float(v)
                     for name, v in zip(RESOURCE_NAMES, machine) if v}
        licenses = int(rng.integers(0, 5))
        if licenses:
            resources["license"] = float(licenses)
        raylet = Raylet(NodeID.from_random(), resources, cluster, deps)
        cluster.register(raylet)
        raylets.append(raylet)
    return cluster, raylets


def make_demands(seed: int):
    """32 distinct demand vectors: small CPU+memory tasks, a share that
    also needs disk, network, a license or TPU chips."""
    rng = np.random.default_rng(seed + 1)
    demands, seen = [], set()
    while len(demands) < N_CLASSES:
        c = len(demands)
        d = {"CPU": float(rng.choice([0.25, 0.5, 0.75, 1.0, 1.5])),
             "memory": float(rng.choice([0.5, 1.0, 1.5, 2.0, 3.0]))}
        if c % 3 == 0:
            d["disk"] = float(rng.choice([1.0, 5.0, 10.0]))
        if c % 4 == 1:
            d["net"] = float(rng.choice([0.1, 0.5, 1.0]))
        if c % 5 == 2:
            d["object_store_memory"] = float(rng.choice([0.25, 1.0]))
        if c % 16 == 3:
            d["TPU"] = float(rng.choice([1.0, 4.0]))
        if c % 16 == 7:
            d["license"] = 1.0
        key = tuple(sorted(d.items()))
        if key not in seen:
            seen.add(key)
            demands.append(d)
    return demands


def drain(seed: int, device: bool, pipelined: bool) -> dict:
    """Queue N_TASKS on the head raylet and drain them through the live
    tick. ``device=False`` turns the jitted solve off with the config
    switch (min_cells < 0); ``pipelined=False`` takes the single-buffered
    tick. With both True every knob is at its default."""
    from ray_tpu._private.config import Config
    from ray_tpu._private.ids import JobID, TaskID
    from ray_tpu.core.raylet import _PendingTask
    from ray_tpu.core.task_spec import (
        TaskKind,
        TaskSpec,
        scheduling_class_of,
    )

    cfg = Config.instance()
    default_cells = cfg.scheduler_device_solve_min_cells
    default_pipeline = cfg.scheduler_pipeline_enabled
    if not device:
        cfg._set("scheduler_device_solve_min_cells", -1)
    cfg._set("scheduler_pipeline_enabled", pipelined)
    try:
        cluster, raylets = build_scheduler_cluster(seed)
        head = raylets[0]
        demands = make_demands(seed)
        job = JobID.from_int(21)
        parent = TaskID.for_task(None)
        class_of = {}
        specs = []
        for i in range(N_TASKS):
            c = i % N_CLASSES
            spec = TaskSpec(
                kind=TaskKind.NORMAL, task_id=TaskID.for_task(None),
                job_id=job, parent_task_id=parent, name=f"t{i}",
                resources=dict(demands[c]))
            spec.scheduling_class = scheduling_class_of(
                spec.resource_request(cluster.ids))
            class_of[spec.task_id] = c
            specs.append(spec)
        assert len({s.scheduling_class for s in specs}) == N_CLASSES

        def on_dispatch(raylet, worker_id):
            raise AssertionError("frozen dispatch executed")

        # the backlog a burst of submitters leaves behind, built the way
        # tests/test_device_scheduler_live.py builds it ...
        with head._lock:
            for spec in specs[:-1]:
                task = _PendingTask(spec, on_dispatch, 0)
                head._pending.append(task)
                head._by_task_id[spec.task_id] = task
        # ... and the submit whose tick finds it
        t0 = time.perf_counter()
        head.submit(specs[-1], on_dispatch)
        while head._pending:
            head.schedule_tick()
        drain_s = time.perf_counter() - t0

        matrix = cluster.matrix
        width = matrix.width
        run = np.zeros((N_CLASSES, N_NODES), dtype=np.int64)
        queued = np.zeros_like(run)
        usage = np.zeros((N_NODES, width), dtype=np.int64)
        infeasible = 0
        for raylet in raylets:
            slot = matrix.slot_of(raylet.node_id)
            with raylet._lock:
                assert not raylet._pending, "a raylet kept pending tasks"
                for task in raylet._running_tasks:
                    run[class_of[task.spec.task_id], slot] += 1
                    usage[slot] += task.spec.resource_request(
                        cluster.ids).dense(width)
                for q in raylet._dispatch_queues.values():
                    for task in q:
                        queued[class_of[task.spec.task_id], slot] += 1
                infeasible += len(raylet._infeasible)
        assert int(run.sum() + queued.sum()) + infeasible == N_TASKS, (
            run.sum(), queued.sum(), infeasible)
        # no node over capacity, in exact int64 fixed point
        over = int((usage > matrix.total).sum())
        assert over == 0, f"{over} (node, resource) cells over capacity"
        for raylet in raylets:
            left = raylet.local_resources.available
            assert all(v >= 0 for v in left.values()), left
            raylet.shutdown()
        return {"run": run, "queued": queued, "infeasible": infeasible,
                "drain_s": drain_s}
    finally:
        cfg._set("scheduler_device_solve_min_cells", default_cells)
        cfg._set("scheduler_pipeline_enabled", default_pipeline)


def cells_differing(a: dict, b: dict) -> int:
    return int(((a["run"] != b["run"]) | (a["queued"] != b["queued"])).sum())


def fused_solve_times(seed: int, n: int = 30):
    """Seconds of one fused solve at the live tick's shapes, host clock
    around upload + solve + block_until_ready."""
    from ray_tpu.scheduler.policy import (
        SchedulingOptions,
        shared_batched_policy,
    )
    from ray_tpu.scheduler.resources import ResourceRequest

    cluster, raylets = build_scheduler_cluster(seed)
    with cluster.lock:
        cluster.refresh_locked()
    matrix = cluster.matrix
    reqs = np.stack([
        ResourceRequest.from_map(d, cluster.ids).dense(matrix.width)
        for d in make_demands(seed)])
    ks = np.full(N_CLASSES, N_TASKS // N_CLASSES, dtype=np.int64)
    opts = SchedulingOptions.default()
    policy = shared_batched_policy(use_jax=True)
    times = []
    for _ in range(n + 3):  # the first three warm up
        t0 = time.perf_counter()
        policy.schedule_tick_fused(
            reqs, ks, matrix.total, matrix.available, matrix.alive, 0,
            opts).block_until_ready()
        times.append(time.perf_counter() - t0)
    for raylet in raylets:
        raylet.shutdown()
    return reqs.shape, matrix.total.shape, times[3:]


def phase_scheduler(seed: int) -> None:
    import jax

    from ray_tpu._private.config import Config
    from ray_tpu.observability.metrics import scheduler_device_solves

    # main() has already refused any platform but "tpu"
    platform = jax.devices()[0].platform
    cells = Config.instance().scheduler_device_solve_min_cells
    say(f"[scheduler] {N_NODES} nodes x {N_CLASSES} classes x {N_TASKS} "
        f"pending tasks, seed {seed}, scheduler_device_solve_min_cells="
        f"{cells} (default), {N_NODES * N_CLASSES} cells a tick")
    assert N_NODES * N_CLASSES >= cells > 0

    def report(name: str, d: dict) -> None:
        say(f"[scheduler] {name}: drained in {d['drain_s']:.2f} s; "
            f"running {int(d['run'].sum())}, "
            f"queued {int(d['queued'].sum())}, infeasible "
            f"{d['infeasible']}; no node over capacity (int64)")

    def device_solves() -> int:
        series = scheduler_device_solves.series()
        assert set(series) <= {(platform,)}, series
        return int(series.get((platform,), 0))

    differing, n_pairs = quotient_check(seed)
    say(f"[scheduler] float32 floor-divide of the solve on {platform} vs "
        f"integer division: {differing} of {n_pairs} pairs differ")
    assert differing == 0

    # every knob at its default: the pipelined tick. Its device solve is
    # dispatched one batch before its counts are committed and is
    # exact-repaired then, so it is held to capacity, to the device, and
    # solve by solve to the numpy policy on the inputs it was given
    assert device_solves() == 0
    mark = time.perf_counter()
    with recorded_solves() as calls:
        live = drain(seed, device=True, pipelined=True)
    n_live = device_solves()
    report("default (pipelined, device)", live)
    say(f"[scheduler] its device solves ran on: {platform} x {n_live} "
        f"(first one compiled inside the drain, {compiles_since(mark)})")
    assert n_live == len(calls) > 0
    differing = cells_differing_from_host_solve(calls)
    say(f"[scheduler] cells on which those {n_live} solves differ from "
        f"the numpy solve of the same inputs: {differing} of "
        f"{n_live * N_CLASSES * N_NODES}")
    assert differing == 0, "a device solve differs from the host solve"
    # the numpy policy under the same tick solves inline, on the matrix
    # as it is when the batch commits: a different (fresher) input, so
    # placements are printed against it, not held to it
    host = drain(seed, device=False, pipelined=True)
    report("pipelined, numpy           ", host)
    assert device_solves() == n_live
    assert host["infeasible"] == live["infeasible"]
    say(f"[scheduler] pipelined device vs pipelined numpy: "
        f"{cells_differing(live, host)} of {N_CLASSES * N_NODES} cells "
        f"differ (the device solve is one batch stale by design)")

    # device solve against the host reference end to end: placement
    # identity is defined on the single-buffered tick, where both solve
    # the same state
    dev = drain(seed, device=True, pipelined=False)
    n_dev = device_solves() - n_live
    report("single-buffered, device", dev)
    ref = drain(seed, device=False, pipelined=False)
    report("single-buffered, numpy ", ref)
    say(f"[scheduler] device solves: {n_dev} on {platform} in the device "
        f"drain, {device_solves() - n_live - n_dev} in the numpy drain")
    assert n_dev > 0 and device_solves() == n_live + n_dev
    assert int(dev["run"].sum()) == int(ref["run"].sum())
    assert int(dev["queued"].sum()) == int(ref["queued"].sum())
    assert dev["infeasible"] == ref["infeasible"]
    differing = cells_differing(dev, ref)
    say(f"[scheduler] (class, node) cells on which the single-buffered "
        f"device and numpy drains differ: {differing} of "
        f"{N_CLASSES * N_NODES}")
    assert differing == 0, (
        "the device solve placed differently from the host reference")

    reqs_shape, total_shape, times = fused_solve_times(seed)
    ms = np.array(times) * 1e3
    say(f"[scheduler] fused solve alone, reqs {reqs_shape} x matrix "
        f"{total_shape}, upload + solve + block_until_ready, "
        f"{len(ms)} calls: median {np.median(ms):.3f} ms, min "
        f"{ms.min():.3f}, max {ms.max():.3f}")


def phase_public_api() -> None:
    """init() / Cluster / @remote through the runtime, then the process
    tier: its workers are children and must come up on the CPU backend
    while this process holds the chip."""
    import ray_tpu
    from ray_tpu._native.shm_store import _SO, native_available
    from ray_tpu.cluster_utils import Cluster

    n_plain, n_slot = 4000, 1000
    cluster = Cluster(head_node_args={"num_cpus": 8})
    try:
        for i in range(7):
            cluster.add_node(num_cpus=8,
                             resources={"slot": 2.0} if i % 2 else None)

        @ray_tpu.remote
        def square(i):
            return i * i

        @ray_tpu.remote(num_cpus=0.5, resources={"slot": 0.25})
        def where(i):
            return i, ray_tpu.get_runtime_context().get_node_id()

        t0 = time.perf_counter()
        refs = [square.remote(i) for i in range(n_plain)]
        slot_refs = [where.remote(i) for i in range(n_slot)]
        squares = ray_tpu.get(refs, timeout=600)
        placed = ray_tpu.get(slot_refs, timeout=600)
        dt = time.perf_counter() - t0
        assert squares == [i * i for i in range(n_plain)]
        assert [i for i, _ in placed] == list(range(n_slot))
        nodes = {n for _, n in placed}
        assert len(nodes) > 1, nodes
        say(f"[public api] Cluster of 8 nodes: {n_plain + n_slot} @remote "
            f"tasks returned the right values in {dt:.2f} s; the "
            f"{n_slot} that need the custom resource ran on "
            f"{len(nodes)} nodes")
    finally:
        cluster.shutdown()

    had_so = os.path.exists(_SO)
    t0 = time.perf_counter()
    native = native_available()
    say(f"[public api] native shm store: "
        + (f"available ({'found' if had_so else 'built with g++'} in "
           f"{time.perf_counter() - t0:.2f} s)" if native else
           "UNAVAILABLE here; process workers use the pipe transport"))
    ray_tpu.init(num_cpus=2, worker_mode="process", num_process_workers=2)
    try:
        @ray_tpu.remote
        def child_view(payload):
            import jax

            return (os.getpid(), os.environ.get("JAX_PLATFORMS"),
                    jax.devices()[0].platform, float(payload.sum()))

        payload = np.arange(1 << 20, dtype=np.float32)  # 4 MiB
        views = ray_tpu.get(
            [child_view.remote(payload) for _ in range(4)], timeout=300)
        for pid, env, platform, total in views:
            assert pid != os.getpid()
            assert (env, platform) == ("cpu", "cpu"), (env, platform)
            assert total == float(payload.sum())
        say(f"[public api] process workers (pids "
            f"{sorted({v[0] for v in views})}) run JAX on the cpu backend "
            f"while this process ({os.getpid()}) holds the chip")
    finally:
        ray_tpu.shutdown()


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention_reference, flash_attention

    b, s, h, d = 4, SEQ, WIDTHS["heads"], WIDTHS["hidden"] // WIDTHS["heads"]
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(key, (b, s, h, d), jnp.bfloat16)
               for key in (kq, kk, kv))
    w = jax.random.normal(kw, (b, s, h, d), jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, True).astype(jnp.float32) * w)

    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))
    flash_grad = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref = jax.jit(lambda q, k, v: attention_reference(q, k, v, True))
    ref_grad = jax.jit(jax.grad(loss(attention_reference),
                                argnums=(0, 1, 2)))

    mark = t0 = time.perf_counter()
    flash_c = flash.lower(q, k, v).compile()
    grad_c = flash_grad.lower(q, k, v).compile()
    compile_s = time.perf_counter() - t0
    k_fwd = count_kernels(flash_c.as_text())
    k_bwd = count_kernels(grad_c.as_text())
    say(f"[kernels] flash_attention B{b}-S{s}-H{h}-D{d} bf16 causal: "
        f"compiled fwd + grad in {compile_s:.1f} s ({compiles_since(mark)}); "
        f"tpu_custom_call in fwd {k_fwd}, in fwd+bwd {k_bwd}")
    assert k_fwd == {"flash_fwd": 1, "flash_bwd_dq": 0, "flash_bwd_dkdv": 0,
                     "attn_delta": 0, "rope_lanes": 0, "other": 0}, k_fwd
    assert k_bwd == {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkdv": 1,
                     "attn_delta": 1, "rope_lanes": 0, "other": 0}, k_bwd

    got = [flash_c(q, k, v), *grad_c(q, k, v)]
    want = [ref(*f32), *ref_grad(*f32)]
    jax.block_until_ready((got, want))
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        a = np.asarray(a.astype(jnp.float32))
        r = np.asarray(r)
        assert a.shape == r.shape and np.isfinite(a).all(), name
        err = float(np.abs(a - r).max())
        peak = float(np.abs(r).max())
        say(f"[kernels] {name}: max |kernel - float32 reference| = "
            f"{err:.4g}, reference peak {peak:.4g}, ratio "
            f"{err / peak:.4g} (tolerance {KERNEL_TOL})")
        assert err <= KERNEL_TOL * peak, name

    t0 = time.perf_counter()
    for _ in range(10):
        out = flash_c(q, k, v)
    out.block_until_ready()
    fwd_ms = (time.perf_counter() - t0) / 10 * 1e3
    t0 = time.perf_counter()
    for _ in range(10):
        grads = grad_c(q, k, v)
    jax.block_until_ready(grads)
    say(f"[kernels] 10 calls each: fwd {fwd_ms:.2f} ms, fwd+bwd "
        f"{(time.perf_counter() - t0) / 10 * 1e3:.2f} ms a call")


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

# the three attention kernels, the backward's delta beside them, and the
# rotation of q and k on their way in (ops/layers.py)
KERNEL_NAMES = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv", "attn_delta",
                "rope_lanes")


def count_kernels(compiled_text: str) -> dict:
    """tpu_custom_call instructions of a compiled program, by the name
    ops/attention.py and ops/layers.py give each pallas_call."""
    counts = {name: 0 for name in KERNEL_NAMES}
    counts["other"] = 0
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        # op_name=".../flash_fwd/pallas_call", or with the transforms it
        # went through: ".../transpose(jvp(flash_bwd_dq))/pallas_call"
        name = next((n for n in KERNEL_NAMES
                     if re.search(rf"[/(]{n}\)*/pallas_call", line)),
                    "other")
        counts[name] += 1
    return counts


def dense_config(layers: int):
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tfm

    return tfm.ModelConfig(
        layers=layers, dtype=jnp.bfloat16, remat=True, remat_policy="full",
        logits_chunk=256, **WIDTHS)


def phase_train(seed: int) -> None:
    import jax

    import ray_tpu
    from ray_tpu import train
    from ray_tpu.train.trainer import Trainer

    def train_func():
        from ray_tpu.models.training import build_train_step
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh

        cfg = dense_config(FULL_DEPTH)
        mesh = build_mesh(MeshSpec())
        step, init_fn = build_train_step(cfg, mesh)
        t0 = time.perf_counter()
        params, opt_state = init_fn(jax.random.PRNGKey(seed))
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(params))
        tokens = jax.random.randint(
            jax.random.PRNGKey(seed + 1), (BATCH, SEQ + 1), 0,
            cfg.vocab_size)
        jax.block_until_ready((params, opt_state, tokens))
        init_s = time.perf_counter() - t0

        mark = t0 = time.perf_counter()
        compiled = step.lower(params, opt_state, tokens).compile()
        compile_s = time.perf_counter() - t0
        kernels = count_kernels(compiled.as_text())
        say(f"[train] L{cfg.layers}-H{cfg.hidden}-I{cfg.intermediate}-"
            f"h{cfg.heads}/kv{cfg.kv_heads}-V{cfg.vocab_size}-S{SEQ} bf16 "
            f"full remat logits_chunk={cfg.logits_chunk}, "
            f"{n_params / 1e6:.1f} M parameters, batch {BATCH}: init "
            f"{init_s:.1f} s, step compiled in {compile_s:.1f} s "
            f"({compiles_since(mark)})")
        say(f"[train] tpu_custom_call in the compiled step: {kernels}")
        # full remat: the forward kernel runs in the forward scan and
        # again in the backward scan's recompute, beside dq and dk/dv;
        # q and k are rotated in each of the three passes
        assert kernels == {"flash_fwd": 2, "flash_bwd_dq": 1,
                           "flash_bwd_dkdv": 1, "attn_delta": 1,
                           "rope_lanes": 6, "other": 0}, kernels

        losses, seconds = [], []
        for i in range(3):
            t0 = time.perf_counter()
            params, opt_state, metrics = compiled(params, opt_state, tokens)
            loss = float(metrics["loss"])  # waits for the step
            seconds.append(time.perf_counter() - t0)
            losses.append(loss)
            train.report(step=i + 1, loss=loss,
                         grad_norm=float(metrics["grad_norm"]))
        return {"losses": losses, "seconds": seconds}

    ray_tpu.init(num_cpus=2)
    try:
        trainer = Trainer(backend="jax", num_workers=1, max_retries=0)
        try:
            result, = trainer.run(train_func)
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
    losses, seconds = result["losses"], result["seconds"]
    say(f"[train] three steps on one fixed batch through train.Trainer: "
        f"loss {', '.join(f'{x:.4f}' for x in losses)}; seconds a step "
        f"{', '.join(f'{x:.3f}' for x in seconds)} "
        f"({BATCH * SEQ} tokens a step)")
    assert all(np.isfinite(losses)), losses
    assert losses[2] < losses[0], losses
    stats = jax.devices()[0].memory_stats()
    say(f"[train] peak_bytes_in_use {stats['peak_bytes_in_use']} of "
        f"bytes_limit {stats.get('bytes_limit')} after the steps")


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def four_chip_cases():
    """(name, mesh spec on four devices, builder) for each sharded step.
    A builder takes a mesh and returns (step, init_fn, batch)."""
    from ray_tpu.models.training import (
        build_pipeline_train_step,
        build_train_step,
    )
    from ray_tpu.parallel.mesh import MeshSpec

    def fsdp(mesh):
        cfg = dense_config(FOUR_CHIP_DEPTH)
        return (*build_train_step(cfg, mesh, fsdp=True), 4)

    def pipeline(mesh):
        # the pipeline path's loss is unchunked, as dryrun_multichip's
        cfg = dense_config(FOUR_CHIP_DEPTH)
        pp = mesh.shape["pp"]
        return (*build_pipeline_train_step(
            cfg, mesh, num_microbatches=2 if pp > 1 else 1), 4)

    return (
        # an sp axis: ring attention in a shard_map over the whole mesh
        ("gspmd dense dp2(fsdp) x sp2(ring)", MeshSpec(dp=2, sp=2), fsdp),
        ("gspmd dense dp2(fsdp) x tp2", MeshSpec(dp=2, tp=2), fsdp),
        ("pipeline pp2 x tp2", MeshSpec(pp=2, tp=2), pipeline),
    )


def run_steps(build, mesh, seed: int, n_steps: int = 3):
    import jax

    step, init_fn, batch = build(mesh)
    params, opt_state = init_fn(jax.random.PRNGKey(seed))
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, SEQ + 1), 0,
        WIDTHS["vocab_size"])
    placement = {
        "/".join(str(getattr(k, "key", k)) for k in path): (
            leaf.sharding.spec,
            len({s.device for s in leaf.addressable_shards}),
            len({str(s.index) for s in leaf.addressable_shards}))
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)}
    t0 = time.perf_counter()
    out = []
    for i in range(n_steps):
        params, opt_state, metrics = step(params, opt_state, tokens)
        out.append((float(metrics["loss"]), float(metrics["grad_norm"])))
        if i == 0:
            first_s = time.perf_counter() - t0
    return out, placement, first_s


def phase_four_chips(seed: int) -> None:
    import jax

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    devices = jax.devices()
    for name, spec, build in four_chip_cases():
        assert spec.size == 4
        say(f"[4 chips] {name}: hidden {WIDTHS['hidden']}, head_dim "
            f"{WIDTHS['hidden'] // WIDTHS['heads']}, seq {SEQ}, "
            f"{FOUR_CHIP_DEPTH} layers, bf16")
        mesh = build_mesh(spec, devices)
        four, placement, t4 = run_steps(build, mesh, seed)
        one, _, t1 = run_steps(build, build_mesh(MeshSpec(), devices[:1]),
                               seed)
        say(f"[4 chips]   first step (compile + run): {t4:.1f} s on four "
            f"devices, {t1:.1f} s on one")
        sharded = 0
        for path, (pspec, n_dev, n_slices) in placement.items():
            want = int(np.prod([mesh.shape[a] for a in pspec
                                if a is not None]))
            say(f"[4 chips]   {path}: {pspec} on {n_dev} devices in "
                f"{n_slices} distinct slice(s)")
            assert n_dev == 4, (path, n_dev)
            assert n_slices == want, (path, n_slices, want)
            sharded += n_slices > 1
        assert sharded, "no parameter was split across devices"
        for i, ((l4, g4), (l1, g1)) in enumerate(zip(four, one)):
            say(f"[4 chips]   step {i + 1}: loss {l4:.5f} on four vs "
                f"{l1:.5f} on one (rel {abs(l4 - l1) / abs(l1):.2e}); "
                f"grad_norm {g4:.4f} vs {g1:.4f} "
                f"(rel {abs(g4 - g1) / abs(g1):.2e})")
            assert np.isfinite([l4, g4, l1, g1]).all()
            assert abs(l4 - l1) <= LOSS_RTOL * abs(l1)
            assert abs(g4 - g1) <= GRAD_NORM_RTOL * abs(g1)
        say(f"[4 chips]   within tolerance at every step: loss "
            f"{LOSS_RTOL} relative, grad_norm {GRAD_NORM_RTOL}")
        assert four[-1][0] < four[0][0], four


# --------------------------------------------------------------------------


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = parser.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no accelerator "
                 f"(jax.devices()[0] is {dev.platform} {dev.device_kind}); "
                 f"nothing was run")
    if len(devices) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but jax.devices() has "
                 f"{len(devices)}; nothing was run")

    from ray_tpu._private.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # the registry's listener, before the first compile
    from ray_tpu.observability import device_programs  # noqa: F401
    say(f"chip_smoke: {len(devices)} x {dev.device_kind} ({dev.platform}), "
        f"jax {jax.__version__}, seed {args.seed}, compile cache at "
        f"{cache_dir} ("
        f"{'placed by JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'fixed, in the checkout'})")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(args.seed)
    else:
        phase_scheduler(args.seed)
        phase_public_api()
        phase_kernels(args.seed)
        phase_train(args.seed)
    say(f"chip_smoke: every phase passed in {time.perf_counter() - t0:.1f} "
        f"s; compiles by program: {compiles_since(by_name=True)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
