"""flops.py against hand counts, trace.py on a recorded trace, the loader."""

import os
import sys
import textwrap

import pytest

from benchmark import flops, loader
from benchmark.tests import helpers
from benchmark.trace import Trace, self_times, union

MISTRAL_L4 = loader.read_json(os.path.join(
    loader.ROOT, "benchmark/configs/mistral_7b_l4.json"))


def test_dense_counts_by_hand():
    # a layer: q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    multiplied = 4 * layer + 4096 * 32000
    assert flops.dense_matmul_params(MISTRAL_L4) == multiplied
    # with the embedding and nine norm vectors
    assert flops.dense_params(MISTRAL_L4) == multiplied + 4096 * 32000 + 9 * 4096
    assert flops.dense_params(MISTRAL_L4) == 1_134_596_096
    # attention, causal: 6 products of 2*4096 operations a score, and a
    # token meets (4096 + 1) / 2 scores on average
    attention = 6 * 2 * 4096 * (4096 + 1) / 2
    assert flops.dense_train_flops_per_token(MISTRAL_L4, 4096) == \
        6 * multiplied + 4 * attention == 6_423_674_880


def test_flash_counts_by_hand():
    scores = 4096 * 4097 // 2               # causal, a head, a row
    one_product = 2 * 128 * scores * 32 * 4  # 32 heads, batch 4
    for kernel, products in (("flash_fwd", 2), ("flash_bwd_dq", 3),
                             ("flash_bwd_dkdv", 4)):
        ops, _ = flops.flash_call_cost(kernel, 4, 4096, 32, 8, 128)
        assert ops == products * one_product
    _, nbytes = flops.flash_call_cost("flash_fwd", 4, 4096, 32, 8, 128)
    q = 4 * 4096 * 32 * 128 * 2
    assert nbytes == 2 * q + 2 * q // 4 + 4 * 4096 * 32 * 4
    t, bound = flops.least_seconds(
        *flops.flash_call_cost("flash_fwd", 4, 4096, 32, 8, 128),
        loader.peaks("TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(2 * one_product / 197e12)


def test_sched_solve_counts_by_hand():
    ops, nbytes = flops.sched_solve_cost(256, 9, 32)
    assert ops == 32 * 256 * (6 * 9 + 8 + 2)
    assert nbytes == 4 * (2 * 256 * 9 + 256 + 32 * 9 + 32 + 32 * 256)
    assert flops.least_seconds(ops, nbytes, loader.peaks("TPU v5e"))[1] \
        == "memory"


def test_unknown_device_has_no_peak():
    with pytest.raises(SystemExit):
        loader.peaks("TPU v9")


def test_union_and_self_times():
    total, gaps = union([(0, 10), (5, 20), (30, 40)])
    assert total == 30 and gaps == [(20, 30)]
    # a loop of 100 with two ops of 30 and 50 inside, then a lone op
    own = dict(self_times([(0, 100, "while"), (10, 40, "a"), (45, 95, "b"),
                           (120, 130, "c")]))
    assert own == {"while": 20, "a": 30, "b": 50, "c": 10}


def test_recorded_tpu_trace():
    """Recorded on a v5e by benchmark/tools/record_trace.py: three steps
    of a tiny flash attention (forward and gradients) and a product, a
    2 ms pause after each, under the harness's window and spans."""
    trace = Trace(os.path.join(os.path.dirname(__file__), "data",
                               "tiny_tpu.xplane.pb"))
    assert list(trace.devices) == [0]
    assert 0.008 < trace.window_s < 0.05
    assert 0 < trace.busy_s() < 0.001 < trace.window_s
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv"):
        seconds, count = trace.matching(kernel)
        assert count == 3 and 1e-6 < seconds < 1e-4
    assert trace.matching("no_such_kernel") == (0.0, 0.0)
    names = [name for name, _ in trace.top_ops()]
    assert "jvp_flash_fwd_.1" in names and len(names) <= 10
    gaps = trace.idle_gaps()
    assert [name for name, _ in gaps[:3]] == ["pause"] * 3
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    """A later PR adds a configuration, a cell, a per-layer metric and a
    kind of reader as files and BENCHMARK.json entries, and edits no code."""
    root, cell = helpers.tiny_train_root(tmp_path)
    spec = loader.benchmark_json(root)
    spec["configs"].append({"name": "new_model", "source": "x",
                            "file": "benchmark/configs/new_model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "new_cell", "config": "new_model",
                              "traffic": "new", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new_metric", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "entry", "moves": "tokens_per_s",
                              "workloads": ["new_cell"]})
    spec["end_to_end"][0]["workloads"].append("new_cell")
    helpers.write(os.path.join(root, "BENCHMARK.json"), spec)
    helpers.write(os.path.join(root, "benchmark/configs/new_model.json"),
                  {"hidden_size": 8})
    helpers.write(os.path.join(root, "benchmark/workloads/new_cell.json"),
                  {"config": "new_model", "driver": "new_driver"})
    helpers.write(os.path.join(root, "benchmark/layer_metrics/new_metric.json"),
                  {"reader": "new_reader", "factor": 3})
    plugins = tmp_path / "plugins"
    for kind, body in (("readers", "def read(metric, run):\n"
                                   "    return metric['factor'] * run['x']\n"),
                       ("drivers", "def run(ctx):\n    return 'driven'\n")):
        (plugins / kind).mkdir(parents=True)
        (plugins / kind / f"new_{kind[:-1]}.py").write_text(body)
        package = sys.modules.get(f"benchmark.{kind}") or __import__(
            f"benchmark.{kind}", fromlist=["x"])
        monkeypatch.setattr(package, "__path__",
                            list(package.__path__) + [str(plugins / kind)])
    new = loader.Cell("new_cell", root=root)
    assert new.config == {"hidden_size": 8}
    assert new.driver().run(None) == "driven"
    assert [m["name"] for m in new.per_layer] == ["new_metric"]
    assert [m["name"] for m in new.end_to_end] == ["tokens_per_s", "setup_s"]
    metric, = new.per_layer
    assert loader.plugin("readers", metric["reader"]).read(
        metric, {"x": 2}) == 6
    # and the cell that was there is untouched by the additions
    old = loader.Cell(cell, root=root)
    assert [m["name"] for m in old.per_layer] == [
        m["name"] for m in spec["per_layer"] if cell in m["workloads"]]


def test_benchmark_json_points_at_files_that_exist():
    spec = loader.benchmark_json()
    for entry in spec["workloads"]:
        cell = loader.Cell(entry["name"])
        assert cell.driver().run
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for metric in cell.per_layer:
            assert loader.plugin("readers", metric["reader"]).read
        assert cell.per_layer, "every cell reports a per-layer metric"


def test_exposed_collective_time():
    from benchmark.trace import innermost, uncovered

    # a collective of 10..30; compute covers 0..15 and 25..40: 10 exposed
    assert uncovered([(10, 30)], [(0, 15), (25, 40)]) == 10
    assert uncovered([(10, 30)], []) == 20
    assert uncovered([], [(0, 5)]) == 0
    leaves = innermost([(0, 100, "while"), (10, 40, "a"), (45, 95, "b"),
                        (50, 60, "c"), (120, 130, "d")])
    assert sorted(name for _, _, name in leaves) == ["a", "c", "d"]
