"""Traffic ``sched_burst``: a driver's bursts of tasks on a cluster.

Each burst: a fresh cluster of the configuration's nodes (every raylet in
this process, tasks frozen at the dependency seam so that placements are
the whole observable state) and ``burst_tasks`` TaskSpecs over the
configuration's scheduling classes, built while the burst clock stands
still; then the burst clock runs from the call that hands the burst to the
head raylet (``Raylet.submit_batch``, one frame, admission-exempt, which
runs the first ``schedule_tick`` itself) until the head's pending queue is
empty, ``schedule_tick`` after ``schedule_tick``. Bursts follow one another
until the burst clock has reached the window's seconds; a burst in flight
runs to its end. Closed loop, one burst at a time.

The generators are chip_smoke.py's (PR 21 proved them on the chip), copied
so that the yardstick does not move with that script: the machine shapes
and demand mix now come from the configuration's file.

Every switch of the program is at its default. The harness owns the
dependency seam (as chip_smoke's FrozenDeps does): a task's placement
latency ends when the raylet hands it to ``wait_ready`` or
``wait_ready_batch``.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark import loader
from benchmark.spans import counter_value


class SeamDeps:
    """The dependency seam, owned by the harness: tasks never become
    ready, so placements commit and hold resources and nothing executes.
    Notes when each task arrived, as (time, tasks)."""

    def __init__(self):
        self.arrivals = []

    def wait_ready(self, spec, callback):
        self.arrivals.append((time.perf_counter(), 1))

    def wait_ready_batch(self, tasks, batch_callback, callback):
        self.arrivals.append((time.perf_counter(), len(tasks)))


@contextlib.contextmanager
def recorded_solves():
    """Every fused solve the live tick dispatches while this is open, as
    (host copies of what it was given, what it returned)."""
    from ray_tpu.scheduler.policy import shared_batched_policy

    policy = shared_batched_policy(use_jax=True)
    solve = policy.schedule_tick_fused
    calls = []

    def recording(reqs, ks, total, available, alive, local_slot, opts):
        counts = solve(reqs, ks, total, available, alive, local_slot, opts)
        calls.append(([np.array(x) for x in (reqs, ks, total, available,
                                             alive)],
                      local_slot, opts.spread_threshold, counts))
        return counts

    policy.schedule_tick_fused = recording  # shadows the method
    try:
        yield calls
    finally:
        del policy.schedule_tick_fused


def build_cluster(config: dict, seed: int, deps):
    from ray_tpu._private.ids import NodeID
    from ray_tpu.core.raylet import ClusterState, Raylet

    rng = np.random.default_rng(seed)
    cluster, raylets = ClusterState(), []
    machines, names = config["machines"], config["resource_names"]
    low, high = config["licenses_per_node"]
    for _ in range(config["nodes"]):
        machine = machines[int(rng.integers(len(machines)))]
        resources = {n: float(v) for n, v in zip(names, machine) if v}
        licenses = int(rng.integers(low, high + 1))
        if licenses:
            resources["license"] = float(licenses)
        raylet = Raylet(NodeID.from_random(), resources, cluster, deps)
        cluster.register(raylet)
        raylets.append(raylet)
    return cluster, raylets


def make_demands(config: dict, seed: int):
    """Distinct demand vectors, one a scheduling class: small CPU+memory
    tasks, a share of which also needs another resource."""
    rng = np.random.default_rng(seed + 1)
    mix = config["demands"]
    demands, seen = [], set()
    while len(demands) < config["scheduling_classes"]:
        c = len(demands)
        d = {}
        for name, rule in mix.items():
            if isinstance(rule, list):
                d[name] = float(rng.choice(rule))
            elif c % rule["every"] == rule["at"]:
                d[name] = float(rng.choice(rule["choices"]))
        key = tuple(sorted(d.items()))
        if key not in seen:
            seen.add(key)
            demands.append(d)
    return demands


def make_burst(config: dict, cluster, demands, n_tasks: int, job: int):
    """(pending tasks for the head, class of each task id)."""
    from ray_tpu._private.ids import JobID, TaskID
    from ray_tpu.core.raylet import _PendingTask
    from ray_tpu.core.task_spec import (
        TaskKind,
        TaskSpec,
        scheduling_class_of,
    )

    def on_dispatch(raylet, worker_id):
        raise AssertionError("a frozen task was dispatched")

    job_id, parent = JobID.from_int(job), TaskID.for_task(None)
    n_classes = len(demands)
    tasks, class_of = [], {}
    for i in range(n_tasks):
        c = i % n_classes
        spec = TaskSpec(kind=TaskKind.NORMAL, task_id=TaskID.for_task(None),
                        job_id=job_id, parent_task_id=parent, name=f"t{i}",
                        resources=dict(demands[c]))
        spec.scheduling_class = scheduling_class_of(
            spec.resource_request(cluster.ids))
        class_of[spec.task_id] = c
        tasks.append(_PendingTask(spec, on_dispatch, 0))
    return tasks, class_of


def account(cluster, raylets, class_of) -> dict:
    """Where every task of the burst is, and the guarantees in exact
    int64: tasks not accounted for exactly once, (node, resource) cells
    over capacity."""
    matrix = cluster.matrix
    usage = np.zeros((len(raylets), matrix.width), dtype=np.int64)
    seen, running, queued, infeasible, pending = set(), 0, 0, 0, 0
    doubles = 0
    for raylet in raylets:
        slot = matrix.slot_of(raylet.node_id)
        with raylet._lock:
            pending += len(raylet._pending)
            held = list(raylet._running_tasks)
            waiting = [t for q in raylet._dispatch_queues.values() for t in q]
            stuck = list(raylet._infeasible)
        for task in held:
            usage[slot] += task.spec.resource_request(cluster.ids).dense(
                matrix.width)
        for task in held + waiting + stuck:
            doubles += task.spec.task_id in seen
            seen.add(task.spec.task_id)
        running, queued = running + len(held), queued + len(waiting)
        infeasible += len(stuck)
    missing = len(set(class_of) - seen) + len(seen - set(class_of))
    return {"running": running, "queued": queued, "infeasible": infeasible,
            "unaccounted": missing + doubles + pending,
            "over_capacity": int((usage > matrix.total[:len(raylets)]).sum())}


def one_burst(ctx, config, n_tasks: int, index: int, spans):
    """Build, hand over, drain, account. Returns what the burst counted."""
    with spans.span("build"):
        deps = SeamDeps()
        cluster, raylets = build_cluster(config, ctx.seed + 7 * index, deps)
        head = raylets[0]
        tasks, class_of = make_burst(
            config, cluster, make_demands(config, ctx.seed), n_tasks, index)
    with recorded_solves() as calls:
        start = time.perf_counter()
        with spans.span("hand_over"):
            head.submit_batch(tasks)
        ticks = 0
        while head._pending:
            with spans.span("tick"):
                head.schedule_tick()
            ticks += 1
        end = time.perf_counter()
    with spans.span("account"):
        counted = account(cluster, raylets, class_of)
        for raylet in raylets:
            raylet.shutdown()
    latencies = np.repeat([t - start for t, _ in deps.arrivals],
                          [n for _, n in deps.arrivals])
    counted.update(resources=int(cluster.matrix.width), clock=(start, end),
                   latencies=latencies, solves=calls, tasks=n_tasks,
                   extra_ticks=ticks)
    return counted


def run(ctx):
    import jax

    from ray_tpu.observability.metrics import (
        scheduler_device_solves,
        scheduler_ticks,
    )

    config, mix = ctx.cell.config, ctx.cell.workload
    n_tasks, spans, say = mix["burst_tasks"], ctx.spans, ctx.say
    platform = jax.devices()[0].platform
    if not config["nodes"] * config["scheduling_classes"] >= _min_cells() > 0:
        raise SystemExit("benchmark: at the default "
                         "scheduler_device_solve_min_cells this cluster "
                         "would not take the device solve")

    # set-up: one burst of the cell's own shapes compiles the solve
    # programs; the device programs depend on (nodes, resources, classes),
    # not on how many tasks a class has
    t0 = time.perf_counter()
    warm = one_burst(ctx, config, mix.get("warmup_tasks", n_tasks), 0, spans)
    say(f"[sched] warm-up burst of {warm['tasks']} tasks in "
        f"{warm['clock'][1] - warm['clock'][0]:.2f} s (set-up "
        f"{time.perf_counter() - t0:.2f} s with its build)")

    def counters():
        return (counter_value(scheduler_ticks),
                counter_value(scheduler_device_solves, (platform,)))

    bursts, clock = [], 0.0
    with ctx.window():
        start = time.perf_counter()
        ticks0, solves0 = counters()
        while clock < ctx.window_seconds:
            burst = one_burst(ctx, config, n_tasks, 1 + len(bursts), spans)
            clock += burst["clock"][1] - burst["clock"][0]
            bursts.append(burst)
        ticks1, solves1 = counters()
        end = time.perf_counter()
    tasks = sum(b["tasks"] for b in bursts)
    latencies = np.concatenate([b["latencies"] for b in bursts])
    placed = int(latencies.size)
    say(f"[sched] window: {len(bursts)} bursts of {n_tasks} tasks, burst "
        f"clock {clock:.3f} s of {end - start:.3f} s wall; a burst: running "
        f"{bursts[0]['running']}, queued {bursts[0]['queued']}, infeasible "
        f"{bursts[0]['infeasible']}; {placed} tasks reached the seam")
    outcome = {
        "end_to_end": {
            "placements_per_s": tasks / clock,
            "place_latency_p99_ms": 1e3 * float(np.percentile(latencies, 99)),
        },
        "attempted": tasks,
        "failed": sum(b["unaccounted"] for b in bursts),
        "window": (start, end),
        "facts": {"bursts": len(bursts), "burst_clock_s": clock,
                  "tasks": tasks, "placed": placed,
                  "ticks": ticks1 - ticks0,
                  "nodes": config["nodes"],
                  "resources": bursts[0]["resources"],
                  "classes": config["scheduling_classes"]},
        "counters": {"scheduler_ticks": ticks1 - ticks0,
                     "scheduler_device_solves": solves1 - solves0},
    }
    outcome["check"] = lambda: check(ctx, bursts)
    return outcome


def _min_cells() -> int:
    from ray_tpu._private.config import Config

    return Config.instance().scheduler_device_solve_min_cells


def check(ctx, bursts, skip_resource=None):
    """(correct, {number: {value, limit}}): every burst's accounting, and
    every device solve of the window against the plain reference on the
    inputs it was given. Exact comparisons: every limit is 0."""
    reference = loader.plugin(
        "references", ctx.cell.workload["check"]["reference"])
    differing = solves = 0
    for burst in bursts:
        for (reqs, ks, total, avail, alive), local, threshold, counts in \
                burst["solves"]:
            want = reference.place_classes(
                reqs, ks, total, avail, alive, local, threshold,
                skip_resource)
            got = np.asarray(counts).astype(np.int64)
            differing += (int((got != want).sum()) if got.shape == want.shape
                          else want.size)
            solves += 1
    numbers = {
        "solve_cells_differing": differing,
        "tasks_unaccounted": sum(b["unaccounted"] for b in bursts),
        "cells_over_capacity": sum(b["over_capacity"] for b in bursts),
        "device_solves_missing": int(solves == 0),
    }
    ctx.say(f"[sched] {solves} device solves against the plain reference: "
            f"{numbers}")
    compared = {k: {"value": v, "limit": 0} for k, v in numbers.items()}
    return all(v == 0 for v in numbers.values()), compared
