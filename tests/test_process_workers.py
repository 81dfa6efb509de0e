"""Process-worker execution tier (ray_tpu/cluster/).

Reference parity targets: worker_pool.h process forking + reuse,
plasma-style shm payload transport, worker-crash retry
(test_failure*.py / test_component_failures*.py patterns: kill the
worker process, assert the task retries or surfaces the right error),
actor-per-process with restart on process death (test_actor_failures).
"""

import os
import signal
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import WorkerCrashedError


@pytest.fixture
def proc_runtime():
    rt = ray_tpu.init(num_cpus=4, worker_mode="process",
                      num_process_workers=2)
    yield rt
    ray_tpu.shutdown()


def test_task_runs_in_separate_process(proc_runtime):
    @ray_tpu.remote
    def whoami():
        return os.getpid()

    pid = ray_tpu.get(whoami.remote())
    assert pid != os.getpid()
    assert pid in proc_runtime.process_pool.pids()


def test_worker_process_reuse(proc_runtime):
    @ray_tpu.remote
    def whoami():
        return os.getpid()

    pids = set(ray_tpu.get([whoami.remote() for _ in range(8)]))
    # 8 sequential-ish tasks over a 2-process pool: processes are reused,
    # not forked per task
    assert pids <= set(proc_runtime.process_pool.pids())
    assert len(pids) <= 2


def test_numpy_round_trip_via_shm(proc_runtime):
    arr = np.arange(200_000, dtype=np.float32)  # > SHM_THRESHOLD

    @ray_tpu.remote
    def double(x):
        return x * 2

    out = ray_tpu.get(double.remote(arr))
    np.testing.assert_array_equal(out, arr * 2)


def test_large_inline_frame_round_trip(proc_runtime):
    # Strings pickle inline (no out-of-band buffer), so a 1MB string
    # forces multi-chunk pipe frames in both directions — the short-read
    # regression case.
    payload = "x" * (1 << 20)

    @ray_tpu.remote
    def echo(s):
        return s + "y"

    assert ray_tpu.get(echo.remote(payload)) == payload + "y"


def test_kill_busy_actor_does_not_hang(proc_runtime):
    @ray_tpu.remote
    class Spinner:
        def getpid(self):
            return os.getpid()

        def spin(self):
            while True:
                time.sleep(0.1)

    s = Spinner.remote()
    pid = ray_tpu.get(s.getpid.remote())
    s.spin.remote()  # occupies the actor process indefinitely
    time.sleep(0.5)
    start = time.monotonic()
    ray_tpu.kill(s)  # must SIGKILL the busy process, not wait politely
    assert time.monotonic() - start < 5
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
            time.sleep(0.1)
        except ProcessLookupError:
            break
    else:
        pytest.fail("busy actor process survived kill")


def test_exception_propagates_with_type(proc_runtime):
    class CustomError(ValueError):
        pass

    @ray_tpu.remote
    def boom():
        raise CustomError("nope")

    with pytest.raises(ValueError, match="nope"):
        ray_tpu.get(boom.remote())


def test_worker_crash_retries_on_fresh_process(proc_runtime):
    marker = f"/tmp/ray_tpu_crash_{os.getpid()}"
    if os.path.exists(marker):
        os.unlink(marker)

    @ray_tpu.remote(max_retries=2)
    def die_once(path):
        if not os.path.exists(path):
            open(path, "w").close()
            os.kill(os.getpid(), signal.SIGKILL)
        return "recovered"

    try:
        assert ray_tpu.get(die_once.remote(marker),
                           timeout=30) == "recovered"
    finally:
        if os.path.exists(marker):
            os.unlink(marker)


def test_worker_crash_without_retries_errors(proc_runtime):
    @ray_tpu.remote(max_retries=0)
    def die():
        os.kill(os.getpid(), signal.SIGKILL)

    with pytest.raises(WorkerCrashedError):
        ray_tpu.get(die.remote(), timeout=30)


def test_pool_replaces_dead_workers(proc_runtime):
    @ray_tpu.remote(max_retries=0)
    def die():
        os.kill(os.getpid(), signal.SIGKILL)

    @ray_tpu.remote
    def ok():
        return 42

    with pytest.raises(WorkerCrashedError):
        ray_tpu.get(die.remote(), timeout=30)
    # the pool spawned a replacement; subsequent tasks still run
    assert ray_tpu.get(ok.remote()) == 42
    assert proc_runtime.process_pool.stats()["alive"] == 2


def test_actor_lives_in_own_process(proc_runtime):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0
            self.pid = os.getpid()

        def incr(self):
            self.n += 1
            return self.n

        def getpid(self):
            return self.pid

    c = Counter.remote()
    assert ray_tpu.get(c.incr.remote()) == 1
    assert ray_tpu.get(c.incr.remote()) == 2
    actor_pid = ray_tpu.get(c.getpid.remote())
    assert actor_pid != os.getpid()
    # actors get dedicated processes, not pool members
    assert actor_pid not in proc_runtime.process_pool.pids()


def test_actor_process_killed_restarts_with_budget(proc_runtime):
    @ray_tpu.remote(max_restarts=1, max_task_retries=1)
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

        def getpid(self):
            return os.getpid()

    c = Counter.remote()
    assert ray_tpu.get(c.incr.remote()) == 1
    pid = ray_tpu.get(c.getpid.remote())
    os.kill(pid, signal.SIGKILL)
    # next call detects the dead process, restarts the actor (state
    # resets: fresh __init__), and retries the call on the new process
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            n = ray_tpu.get(c.incr.remote(), timeout=30)
            break
        except Exception:
            time.sleep(0.2)
    assert n == 1  # fresh state after restart
    assert ray_tpu.get(c.getpid.remote()) != pid


def test_actor_process_killed_no_budget_dies(proc_runtime):
    from ray_tpu.exceptions import ActorDiedError, RayActorError

    @ray_tpu.remote(max_restarts=0)
    class A:
        def getpid(self):
            return os.getpid()

    a = A.remote()
    pid = ray_tpu.get(a.getpid.remote())
    os.kill(pid, signal.SIGKILL)
    with pytest.raises((ActorDiedError, RayActorError)):
        ray_tpu.get(a.getpid.remote(), timeout=30)


def test_runtime_env_env_vars_in_process(proc_runtime):
    @ray_tpu.remote(runtime_env={"env_vars": {"MY_FLAG": "on"}})
    def read_flag():
        return os.environ.get("MY_FLAG")

    assert ray_tpu.get(read_flag.remote()) == "on"


def test_kill_actor_terminates_process(proc_runtime):
    @ray_tpu.remote
    class A:
        def getpid(self):
            return os.getpid()

    a = A.remote()
    pid = ray_tpu.get(a.getpid.remote())
    ray_tpu.kill(a)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            break
        time.sleep(0.1)
    else:
        pytest.fail("actor process still alive after ray_tpu.kill")


def test_shutdown_reaps_all_processes():
    rt = ray_tpu.init(num_cpus=2, worker_mode="process",
                      num_process_workers=2)
    pids = rt.process_pool.pids()
    assert len(pids) == 2
    ray_tpu.shutdown()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        alive = []
        for p in pids:
            try:
                os.kill(p, 0)
                alive.append(p)
            except ProcessLookupError:
                pass
        if not alive:
            break
        time.sleep(0.1)
    assert not alive, f"leaked worker processes: {alive}"


def test_worker_processes_can_import_jax(shutdown_only, tmp_path,
                                          monkeypatch):
    """One process owns the chip — the one that called init(). A process
    worker is a child: whatever platform the parent's environment names,
    a task that imports jax there gets the CPU backend
    (cluster/child_env.py), and the user's PYTHONPATH entries survive so
    user code stays importable."""
    import os

    import ray_tpu

    user_dir = tmp_path / "userdir"
    user_dir.mkdir()
    (user_dir / "my_worker_lib.py").write_text("VALUE = 37\n")
    monkeypatch.setenv(
        "PYTHONPATH",
        os.pathsep.join([str(user_dir), os.environ.get("PYTHONPATH", "")]))
    # a worker that inherited this verbatim would reach for the parent's
    # accelerator (here: fail backend resolution)
    monkeypatch.setenv("JAX_PLATFORMS", "bogus_accelerator")

    ray_tpu.init(num_cpus=2, worker_mode="process",
                 num_process_workers=1)

    @ray_tpu.remote
    def uses_jax():
        import os

        import jax
        import jax.numpy as jnp

        import my_worker_lib

        return (os.environ["JAX_PLATFORMS"], jax.default_backend(),
                float(jax.jit(lambda x: x.sum())(jnp.ones((4, 4)))),
                my_worker_lib.VALUE)

    env, backend, val, lib_value = ray_tpu.get([uses_jax.remote()])[0]
    assert (env, backend) == ("cpu", "cpu")
    assert val == 16.0
    assert lib_value == 37          # the user's PYTHONPATH entry kept
