"""The readers that join a trace with the program's registry of compiled
programs, on hand-made traces and tables."""

import sys

import pytest

from benchmark import loader
from benchmark.readers import (
    _registry,
    program_compiles,
    trace_scope_share as reader,
)
from benchmark.trace import union

BODY = "jit(train_step)/jvp(layers)/while/body/closed_call/"
BACK = "jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
TABLE = {
    "while.1": "jit(train_step)/jvp(layers)/while",
    "fusion.1": BODY + "attention/qkv_proj/bsh,hd->bsd/dot_general",
    "flash_fwd.2": BODY + "attention/flash/flash_fwd/pallas_call",
    "fusion.3": BODY + "mlp/...h,hm->...m/dot_general",
    "while.2": "jit(train_step)/transpose(jvp(layers))/while",
    "fusion.4": BACK + "rematted_computation/mlp/jit(silu)/mul",
    "fusion.5": BACK + "mlp/...m,mh->...h/dot_general",
    "all-reduce-start.1": BACK + "attention/out_proj/bsd,dh->bsh/dot_general",
    "fusion.6": "jit(train_step)/jvp(loss)/while/body/closed_call/unembed/"
                "bsh,hv->bsv/dot_general",
    "fusion.7": "jit(train_step)/optimizer/mul",
    "fusion.8": "jit(train_step)/attention/rope/slice",  # hoisted: no pass
    "param.1": "params['embed']",                        # not under the jit
}
# one device, nanoseconds: a forward loop of 100 holding 80 of operations,
# a backward loop of 100 holding 70, then loss, optimizer, a hoisted
# operation, an operation the table has under no jit, one it has not at all
EVENTS = [
    (0, 100, "while.1"), (0, 30, "fusion.1"), (30, 60, "flash_fwd.2"),
    (60, 80, "fusion.3"),
    (100, 200, "while.2"), (100, 140, "fusion.4"), (140, 170, "fusion.5"),
    (200, 240, "fusion.6"), (240, 250, "fusion.7"), (250, 260, "fusion.8"),
    (260, 270, "param.1"), (270, 300, "copy.9"),
]


class FakeTrace:
    def __init__(self, devices, async_ops=None):
        self.devices, self.async_ops = devices, async_ops or {}
        self.modules = {n: [(0, 300, "jit_train_step(123)")] for n in devices}
        every = [e for evs in devices.values() for e in evs]
        self.window = (min(e[0] for e in every), max(e[1] for e in every))

    def busy_s(self):
        busy = [union([(s, e) for s, e, _ in evs])[0]
                for evs in self.devices.values()]
        return sum(busy) / len(busy) / 1e9


class FakeCompiled:
    def __init__(self, table):
        self.table = table

    def as_text(self):
        return "HloModule jit_train_step\n" + "".join(
            f'  %{name} = f32[] add(), metadata={{op_name="{path}"}}\n'
            for name, path in self.table.items())


@pytest.fixture
def noted():
    from ray_tpu.observability import device_programs

    device_programs.clear()
    device_programs.note("train_step", FakeCompiled(TABLE))
    yield device_programs
    device_programs.clear()


def metric(name):
    return dict(loader.read_json(
        f"{loader.HERE}/layer_metrics/{name}.json"), name=name)


@pytest.mark.parametrize("name,want", [
    ("step_scope_coverage", 100 * 260 / 300),
    ("fwd_time_share", 100 * (20 + 30 + 30 + 20 + 40) / 300),
    ("remat_time_share", 100 * 40 / 300),
    ("bwd_time_share", 100 * (30 + 30) / 300),
    ("optimizer_time_share", 100 * 10 / 300),
    ("loss_time_share", 100 * 40 / 300),
    ("attention_block_time_share", 100 * (30 + 30 + 10) / 300),
    ("mlp_block_time_share", 100 * (20 + 40 + 30) / 300),
])
def test_shares_of_a_hand_made_trace(noted, capsys, name, want):
    run = {"trace": FakeTrace({0: EVENTS})}
    assert reader.read(metric(name), run) == pytest.approx(want)
    said = capsys.readouterr().out
    assert "scope table of train_step, 12 instructions" in said
    assert ("by pass" in said) == (name == "step_scope_coverage")


def test_passes_and_optimizer_partition_the_coverage(noted, capsys):
    run = {"trace": FakeTrace({0: EVENTS})}
    read = {n: reader.read(metric(n), run) for n in (
        "step_scope_coverage", "fwd_time_share", "remat_time_share",
        "bwd_time_share", "optimizer_time_share")}
    # what is left is the hoisted operation, which the commentary names
    left = read.pop("step_scope_coverage") - sum(read.values())
    assert left == pytest.approx(100 * 10 / 300)
    said = capsys.readouterr().out
    assert "other 6.67" in said and "attention/rope 3.33 (other 3.33)" in said
    assert "layers/mlp 30.00 (fwd 6.67, remat 13.33, bwd 10.00)" in said
    assert "copy 10.000" in said and "param 3.333" in said
    assert "exposed collective" not in said
    assert "XLA Modules line: jit_train_step x1\n" in said
    # the program's own reduction gives the same own times
    by_path = {}
    for op, seconds in run["_own_by_op"].items():
        path = TABLE.get(op, "")
        by_path[path] = by_path.get(path, 0.0) + seconds
    assert noted.anatomy(EVENTS, "train_step") == pytest.approx(by_path)


def test_devices_are_averaged_and_exposed_collectives_named(noted, capsys):
    # device 1 runs the same operations and an exposed all-reduce; an
    # async one on device 0 is half covered by the optimizer's operation
    second = EVENTS + [(300, 340, "all-reduce-start.1")]
    trace = FakeTrace({0: EVENTS, 1: second},
                      async_ops={0: [(245, 255, "all-reduce-start.1")]})
    run = {"trace": trace}
    value = reader.read(metric("attention_block_time_share"), run)
    assert value == pytest.approx(100 * (70 + 110) / 2 / 320)
    reader.read(metric("step_scope_coverage"), run)
    said = capsys.readouterr().out
    assert "layers/attention/out_proj bwd 0.0000" in said
    by = reader.exposed_collectives_by_chain(trace, TABLE)
    # device 0: nothing covers [250, 255)... but fusion.8 does; [245, 250)
    # is the optimizer's: all covered. device 1: 40 ns exposed
    assert by == {"layers/attention/out_proj bwd": pytest.approx(20e-9)}


@pytest.mark.parametrize("path,scopes,which", [
    (BACK + "rematted_computation/attention/flash/flash_fwd/pallas_call",
     ["layers", "attention", "flash", "flash_fwd"], "remat"),
    ("jit(train_step)/transpose(jvp(loss))/while/body/closed_call/checkpoint/"
     "softmax_xent/jit(log_softmax)/sub", ["loss", "softmax_xent"], "bwd"),
    ("jit(train_step)/jvp(embed)/jit(_take)/gather", ["embed"], "fwd"),
    ("jit(train_step)/jvp()/mul", [], "fwd"),
    ("jit(train_step)/optimizer/jit(_where)/select_n", ["optimizer"],
     "other"),
    ("jit(train_step)/jvp(layers)/while/body/closed_call/mlp/cond/"
     "branch_1_fun/moe/router/th,he->te/dot_general",
     ["layers", "mlp", "moe", "router"], "fwd"),
])
def test_scopes_and_pass_of_a_path(path, scopes, which):
    assert reader.scopes_of(path) == scopes
    assert reader.pass_of(path) == which


def test_nothing_without_a_noted_program_or_a_registry(noted, monkeypatch):
    run = {"trace": FakeTrace({0: EVENTS})}
    noted.clear()
    assert reader.read(metric("fwd_time_share"), run) is None
    # a program from before this registry: the import fails
    import ray_tpu.observability

    monkeypatch.setitem(sys.modules,
                        "ray_tpu.observability.device_programs", None)
    monkeypatch.delattr(ray_tpu.observability, "device_programs")
    assert _registry.device_programs() is None
    run = {"trace": FakeTrace({0: EVENTS}), "window": (0.0, 1e30)}
    assert reader.read(metric("step_scope_coverage"), run) is None
    assert program_compiles.read(metric("window_compiles"), run) is None


def test_window_compiles_counts_the_events_inside_the_window(noted, capsys):
    import time

    t0 = time.perf_counter()
    noted._on_duration(noted._BACKEND_COMPILE, 0.5, fun_name="jit(train_step)")
    t1 = time.perf_counter()
    m = metric("window_compiles")
    trace = FakeTrace({0: EVENTS})
    assert program_compiles.read(m, {"trace": trace, "window": (t0, t1)}) == 1
    assert "train_step compiled inside the window" in capsys.readouterr().out
    assert program_compiles.read(
        m, {"trace": trace, "window": (t1, t1 + 1)}) == 0
    # no device in the trace (a rehearsal on the CPU): like the trace's readers
    trace.devices = {}
    assert program_compiles.read(m, {"trace": trace,
                                     "window": (t0, t1)}) is None
