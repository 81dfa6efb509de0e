"""Shared test fixtures.

Modeled on the reference's python/ray/tests/conftest.py (ray_start_regular
:121, ray_start_cluster :201): small in-process clusters per test, always
torn down. JAX is forced onto a virtual 8-device CPU mesh so multi-chip
sharding paths compile and run without TPU hardware.
"""

import os

# Tests always run on a virtual 8-device CPU mesh, whatever the caller's
# environment says, and never on a chip: the variable is for the child
# processes tests start, the config update below for this one.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Suite-wide: skip the shm segment boot prefault (a write-touch of every
# page so GiB-scale puts run at copy speed instead of fault speed; see
# ShmStore._prefault). Test clusters boot hundreds of default-sized
# (2 GiB) stores across the suite — prefaulting them would add minutes
# of pure page-fault time per run on a throttled host while testing
# nothing (correctness is prefault-independent; the dedicated prefault
# test re-enables it explicitly). Production and bench.py keep it on.
os.environ.setdefault("RAY_TPU_SHM_PREFAULT", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

import ray_tpu  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from tier-1 (-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "fault: seeded fault-injection scenarios "
        "(tests/test_fault_injection.py; failures print their replay "
        "seed + fault plan)")
    config.addinivalue_line(
        "markers",
        "overload: overload-robustness scenarios — admission control, "
        "retry budgets, circuit breakers, backpressure "
        "(tests/test_overload.py; seeded storms print their replay "
        "seed + fault plan)")
    config.addinivalue_line(
        "markers",
        "integrity: end-to-end object-checksum scenarios — corruption "
        "detection at every data-movement seam, corruption-triggered "
        "re-pull and lineage recovery (tests/test_integrity.py)")
    config.addinivalue_line(
        "markers",
        "serve_resilience: serve resilience-plane scenarios — health "
        "probing, graceful drains, overload-aware routing, and seeded "
        "fault/overload storms (tests/test_serve_resilience.py; "
        "failing storms print their replay seed + plan)")
    config.addinivalue_line(
        "markers",
        "worker_pool: warm worker-pool and batched actor-lifecycle "
        "scenarios — warm-lease vs cold-fork parity, pool exhaustion, "
        "leased-worker crashes, clean-return vs dirty-reap, batch "
        "creates/kills with per-row failures "
        "(tests/test_worker_pool.py)")
    config.addinivalue_line(
        "markers",
        "tracing: distributed-tracing scenarios — wire-level trace "
        "propagation across processes, seeded head-based sampling, "
        "scheduler tick anatomy (tests/test_tracing.py)")
    config.addinivalue_line(
        "markers",
        "observability: observability-plane scenarios — flight "
        "recorder rings and crash dumps, merged cluster timeline, "
        "Prometheus exposition round-trips "
        "(tests/test_observability.py, tests/test_tracing.py)")
    config.addinivalue_line(
        "markers",
        "scheduler_pipeline: pipelined scheduler-tick scenarios — "
        "double-buffered device solves, device matrix mirror delta "
        "sync, vectorized commit/spillback, repair edge cases, and the "
        "raycheck-clean assertion over the touched files "
        "(tests/test_scheduler_pipeline.py)")
    config.addinivalue_line(
        "markers",
        "dispatch_fastlane: dispatch fast-lane scenarios — on/off "
        "parity of the zero-copy submit→exec path (results, retries, "
        "placements, backpressure), frozen-template spec parity, bulk "
        "dispatch grant accounting, and wire round-trip pins for the "
        "batched submit/exec frames "
        "(tests/test_dispatch_fastlane.py)")
    config.addinivalue_line(
        "markers",
        "data_plane: data-plane pipeline scenarios — chunk-tree "
        "broadcast parity per topology (ON/OFF, byte-for-byte), "
        "cut-through forwarding, same-host segment adoption, "
        "corrupt-chunk-in-flight containment, mid-broadcast node "
        "death and receive-state teardown accounting "
        "(tests/test_data_plane.py)")
    config.addinivalue_line(
        "markers",
        "chaos: chaos scenarios — random node kills against retrying "
        "workloads (tests/test_chaos.py) and fault-hardened fast "
        "lanes: exactly-once batched frames under duplicated/replayed "
        "deliveries, mixed submit/actor/broadcast load under a seeded "
        "storm with kills mid-frame and partitions mid-tree "
        "(tests/test_fastlane_chaos.py; failing storms print their "
        "replay seed + plan)")
    config.addinivalue_line(
        "markers",
        "drain: node-drain / preemption-plane scenarios — graceful "
        "drain (actor migration, sole-copy re-replication, deadline "
        "fallback), preemption notices through the heartbeat, the "
        "live autoscaler loop replacing evicted capacity, and "
        "drain_plane_enabled=False parity (tests/test_drain.py)")


@pytest.fixture
def shutdown_only():
    yield None
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_regular(request):
    kwargs = dict(num_cpus=4)
    kwargs.update(getattr(request, "param", {}))
    rt = ray_tpu.init(**kwargs)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def ray_init():
    rt = ray_tpu.init(num_cpus=8)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-node in-process cluster, reference cluster_utils.Cluster."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster()
    yield cluster
    cluster.shutdown()


@pytest.fixture(autouse=True)
def _always_shutdown():
    yield
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


# The serve_resilience/tune/workflow suites intermittently erred with
# "ray_tpu is already initialized" when an earlier module leaked a live
# Runtime past its last test (e.g. a teardown racing a background
# init, or a module-level runtime that _always_shutdown never sees).
# This module-boundary guard names the leaker and tears the runtime
# down so the *next* module starts clean instead of erroring on init.
# Set RAY_TPU_STRICT_LEAK_CHECK=1 to turn the warning into a hard
# failure when hunting the leak itself.
@pytest.fixture(autouse=True, scope="module")
def _no_leaked_runtime_between_modules(request):
    def _reap(where: str, settle_s: float = 0.0):
        # the overload/breaker registries are process-wide: a breaker
        # opened (or a retry budget drained) by one module's chaos
        # tests otherwise bleeds into the next module's first RPCs and
        # flakes its init path — reset them at every module boundary
        # alongside the runtime leak check
        import time

        from ray_tpu.cluster import overload

        overload.reset()
        # settle window: a background thread from the PREVIOUS module
        # (a tune function-trainable, a serve controller replacement)
        # can complete an init() milliseconds after this boundary
        # check, erroring the next module's first init with "called
        # twice" — poll briefly so a late-landing runtime still gets
        # reaped before any test sees it
        deadline = time.monotonic() + settle_s
        while True:
            if ray_tpu.is_initialized():
                msg = (f"leaked ray_tpu Runtime detected {where} "
                       f"module {request.node.nodeid}; tearing it "
                       f"down")
                if os.environ.get("RAY_TPU_STRICT_LEAK_CHECK") == "1":
                    ray_tpu.shutdown()
                    raise AssertionError(msg)
                import warnings

                warnings.warn(msg, stacklevel=1)
                ray_tpu.shutdown()
            if time.monotonic() >= deadline:
                return
            time.sleep(0.025)

    _reap("entering", settle_s=0.15)
    yield
    _reap("leaving")


# test_train / test_train_elastic pass standalone but flake under the
# full run: both boot process-backed worker groups whose first steps
# pay the host-side model/backend load, and a second runtime
# initializing concurrently (another test module, or another xdist
# worker) starves those boots past their readiness windows. A
# cross-process file lock — the xdist_group-style serialization that
# also covers plain parallel invocations of pytest — runs these two
# modules one test at a time; everywhere else it is a no-op.
_SERIAL_MODULES = ("test_train", "test_train_elastic")


@pytest.fixture(autouse=True)
def _serialize_train_suites(request):
    mod = getattr(getattr(request.node, "module", None), "__name__", "")
    if mod.rsplit(".", 1)[-1] not in _SERIAL_MODULES:
        yield
        return
    import fcntl
    import tempfile

    path = os.path.join(tempfile.gettempdir(),
                        "ray_tpu_train_suite.lock")
    fd = os.open(path, os.O_CREAT | os.O_RDWR, 0o644)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        fcntl.flock(fd, fcntl.LOCK_UN)
        os.close(fd)
