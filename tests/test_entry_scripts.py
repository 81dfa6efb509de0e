"""What the root entry scripts share: where the compile cache lives, and
that they run on the devices there are or fail."""

import os
import subprocess
import sys

import pytest

from ray_tpu._private import compile_cache


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of making them: the tests
    never turn the persistent cache on."""
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_dir_placed_from_outside_sets_nothing(monkeypatch, tmp_path,
                                                    config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert config_updates == []


def test_cache_dir_default_is_fixed_in_the_checkout(monkeypatch,
                                                    config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, compile_cache.CACHE_DIR_NAME)
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)] * 2
    # another process, another working directory: the same path
    from ray_tpu.cluster.child_env import child_env

    out = subprocess.run(
        [sys.executable, "-c",
         "from ray_tpu._private.compile_cache import enable_compile_cache"
         "; print(enable_compile_cache())"],
        env=child_env(), cwd="/", capture_output=True, text=True,
        timeout=120, check=True)
    assert out.stdout.strip().splitlines()[-1] == want
    with open(os.path.join(repo, ".gitignore")) as f:
        assert compile_cache.CACHE_DIR_NAME + "/" in f.read().split()


def test_dryrun_multichip_raises_on_too_few_devices(monkeypatch):
    """No re-execution onto virtual devices: it runs on the devices
    there are, or says how many it found."""
    import jax

    import __graft_entry__ as graft

    def no_child(*args, **kwargs):
        raise AssertionError("dryrun_multichip started another process")

    monkeypatch.setattr(subprocess, "run", no_child)
    monkeypatch.setattr(os, "execve", no_child)
    n = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match=f"needs {n} devices"):
        graft.dryrun_multichip(n)
