"""A tiny benchmark in a temporary root: the same harness, drivers,
readers and reference, found by the same names, over tiny files."""

import json
import os

import jax

from benchmark import loader
from benchmark import run as harness

REPO = loader.ROOT
CELL = loader.Cell
TINY_MODEL = dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  num_hidden_layers=2, vocab_size=256)
# at this size bfloat16's rounding of a 3e-4 update reads 0.10-0.12 on the
# change; the int8 control reads 1.3e-2 and more on the first gradient
TINY_LIMITS = {"loss_gap": 1e-3, "first_grad_gap": 6e-3,
               "grad_share_gap": 6e-3, "change_gap": 0.3}


def write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_train_root(tmp_path, chips=1, mesh=None, dtype="bfloat16"):
    """BENCHMARK.json and the files of one training cell, cut to a size
    a test can hold; returns (root, cell name)."""
    root = str(tmp_path)
    spec = loader.benchmark_json(REPO)
    cell = "mistral7b_l4_train_s4096"
    entry = dict(loader.named(spec["workloads"], cell, "workload"),
                 chips=chips)
    spec["workloads"] = [entry]
    spec["configs"] = [dict(loader.named(spec["configs"], entry["config"],
                                         "config"),
                            file="benchmark/configs/tiny.json")]
    write(os.path.join(root, "BENCHMARK.json"), spec)
    config = loader.read_json(os.path.join(
        REPO, "benchmark/configs/mistral_7b_l4.json"))
    config.update(TINY_MODEL, mesh=mesh or {}, torch_dtype=dtype)
    write(os.path.join(root, "benchmark/configs/tiny.json"), config)
    mix = loader.read_json(os.path.join(
        REPO, "benchmark/workloads", cell + ".json"))
    mix.update(batch=4, seq=64, trace_seconds=1)
    mix["check"]["limits"] = dict(TINY_LIMITS)
    write(os.path.join(root, "benchmark/workloads", cell + ".json"), mix)
    for metric in spec["per_layer"]:
        name = metric["name"] + ".json"
        write(os.path.join(root, "benchmark/layer_metrics", name),
              loader.read_json(os.path.join(
                  REPO, "benchmark/layer_metrics", name)))
    return root, cell


def drive(monkeypatch, capsys, root, cell, seed=7, seconds=0.3, trace=0):
    """One run of the harness with the test standing in for its look for
    a chip; returns the result line as an object."""
    monkeypatch.setattr(loader, "Cell",
                        lambda name, root=root: CELL(name, root=root))
    monkeypatch.setattr(harness, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "place_compile_cache", lambda: "(none)")
    monkeypatch.setattr(
        loader, "peaks", lambda kind: loader.read_json(os.path.join(
            REPO, "benchmark/peaks.json"))["chips"]["TPU v5e"])
    capsys.readouterr()
    harness.main(["--workload", cell, "--seed", str(seed), "--seconds",
                  str(seconds), "--trace", str(trace)])
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out


def tiny_sched_root(tmp_path, burst_tasks=2000):
    """One scheduling cell over the full 256 x 32 cluster (fewer cells
    would not take the device solve at the default threshold), with small
    bursts; returns (root, cell name)."""
    root, cell = str(tmp_path), "sched256_burst10k"
    metrics = ["generator_share", "tick_ms", "device_solves_per_burst",
               "solve_device_ms", "solve_roofline", "device_idle_share.sched"]
    write(os.path.join(root, "BENCHMARK.json"), {
        "configs": [{"name": "ray_sched_256n",
                     "file": "benchmark/configs/ray_sched_256n.json"}],
        "workloads": [{"name": cell, "config": "ray_sched_256n",
                       "traffic": "burst10k", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "placements_per_s", "unit": "tasks/s"},
            {"name": "place_latency_p99_ms", "unit": "ms"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": m, "unit": "x", "layer": "x",
                       "moves": "placements_per_s"} for m in metrics]})
    write(os.path.join(root, "benchmark/configs/ray_sched_256n.json"),
          loader.read_json(os.path.join(
              REPO, "benchmark/configs/ray_sched_256n.json")))
    mix = loader.read_json(os.path.join(
        REPO, "benchmark/workloads", cell + ".json"))
    mix.update(burst_tasks=burst_tasks, warmup_tasks=500, trace_seconds=1)
    write(os.path.join(root, "benchmark/workloads", cell + ".json"), mix)
    for m in metrics:
        write(os.path.join(root, "benchmark/layer_metrics", m + ".json"),
              loader.read_json(os.path.join(
                  REPO, "benchmark/layer_metrics", m + ".json")))
    return root, cell
