"""The two readers of set-up and of the step's memory, on a made-up run:
build events either side of the window's start, a program without the
record, a trace without devices."""

import types

import pytest

from benchmark import loader
from benchmark.readers import program_builds, program_memory
from ray_tpu.observability import device_programs
from ray_tpu.observability.device_programs import BuildEvent

WINDOW = (100.0, 120.0)
SETUP_S = 40.0  # set-up ran from 60 to 100 on the harness's clock
STEP_NESTED = tuple((f"f{i}", 1.0 / (i + 1), i + 1) for i in range(12))
EVENTS = [
    # (imported before set-up began: no part of setup_s, counted as built)
    BuildEvent("early", "compile", 59.0, 2.0, "miss"),
    BuildEvent("make", "trace", 62.0, 1.0, ""),
    BuildEvent("make", "lower", 62.5, 0.5, ""),
    BuildEvent("make", "compile", 66.0, 3.5, "hit"),
    BuildEvent("make", "cache_read", 66.0, 3.0, "hit"),
    # an eager operation compiled while the step was traced
    BuildEvent("iota", "lower", 75.0, 0.25, ""),
    BuildEvent("iota", "compile", 76.0, 1.0, "miss"),
    BuildEvent("train_step", "trace", 80.0, 8.0, "", STEP_NESTED,
               (("flash_fwd", 1.5, 6), ("rope_lanes", 0.25, 3))),
    BuildEvent("train_step", "lower", 82.0, 2.0, ""),
    BuildEvent("train_step", "compile", 92.0, 10.0, "off"),
    # the window's own (a recompile) and the reference's, after it
    BuildEvent("train_step", "compile", 110.0, 4.0, "miss"),
    BuildEvent("follow", "trace", 125.0, 3.0, "", (),
               (("flash_fwd", 9.0, 1),)),
    BuildEvent("follow", "compile", 130.0, 5.0, "hit"),
    BuildEvent("follow", "cache_read", 130.0, 4.5, "hit"),
]


def registry_of(events):
    return types.SimpleNamespace(builds=lambda since=0.0, until=float(
        "inf"): [e for e in events if since <= e.at <= until])


def run_of(devices=True, setup_s=SETUP_S):
    return {"trace": types.SimpleNamespace(devices={0: []} if devices
                                           else {}),
            "window": WINDOW, "end_to_end": {"setup_s": setup_s}}


def metric(name):
    return dict(loader.read_json(
        f"{loader.HERE}/layer_metrics/{name}.json"), name=name)


@pytest.fixture
def registry(monkeypatch):
    monkeypatch.setattr(program_builds, "device_programs",
                        lambda: registry_of(EVENTS))


@pytest.mark.parametrize("name,want", [
    ("setup_trace_s", 1.0 + 8.0),
    ("setup_lower_s", 0.5 + 0.25 + 2.0),
    ("setup_cache_read_s", 3.0),
    ("setup_compile_s", 2.0 + 1.0 + 10.0),  # missed, or not asked
    ("setup_kernel_trace_s", 1.5 + 0.25),
    ("setup_programs_built", 4.0),
    # 61-62.5, 62.5-66, 72-80 with the eager build inside it, 80-82, 82-92
    ("setup_build_share", 100 * (1.5 + 3.5 + 8.0 + 2.0 + 10.0) / 40.0),
])
def test_set_up_is_what_ended_before_the_window(registry, name, want):
    assert program_builds.read(metric(name), run_of()) == pytest.approx(want)


def test_the_parts_lie_inside_set_up(registry):
    read = {name: program_builds.read(metric(name), run_of()) for name in (
        "setup_trace_s", "setup_lower_s", "setup_compile_s",
        "setup_cache_read_s", "setup_kernel_trace_s")}
    assert read.pop("setup_kernel_trace_s") <= read["setup_trace_s"]
    assert sum(read.values()) <= SETUP_S


@pytest.mark.parametrize("setup_s,want", [
    (40.0, 62.5),           # the eager build inside the step's trace once
    (20.0, 100 * 12 / 20),  # set-up began as the step's trace ended
    (15.0, 100 * 7 / 15),   # ... inside the step's compile
    (5.0, 0.0),             # ... after the last build
])
def test_a_share_never_passes_100(registry, setup_s, want):
    """Overlapping builds, and builds that began before set-up did, are
    counted once and only for their part inside set-up."""
    share = program_builds.read(metric("setup_build_share"),
                                run_of(setup_s=setup_s))
    assert share == pytest.approx(want) and 0 <= share <= 100.0


def test_the_commentary_names_programs_phases_and_the_steps_largest(
        registry, capsys):
    program_builds.read(metric("setup_trace_s"), run_of())
    assert capsys.readouterr().out == ""
    program_builds.read(metric("setup_build_share"), run_of())
    said = capsys.readouterr().out.splitlines()
    assert all(line.startswith("[reader] setup_build_share: ")
               for line in said)
    assert said[:4] == [
        "[reader] setup_build_share: early: compile 2.000 s (cache miss)",
        "[reader] setup_build_share: make: trace 1.000 s, lower 0.500 s, "
        "compile 3.500 s (cache hit), cache_read 3.000 s",
        "[reader] setup_build_share: iota: lower 0.250 s, compile 1.000 s "
        "(cache miss)",
        "[reader] setup_build_share: train_step: trace 8.000 s, lower 2.000 "
        "s, compile 10.000 s (cache off)"]
    assert len(said) == 5
    assert not any("follow" in line for line in said)
    largest, = [line for line in said if "trace of train_step" in line]
    assert "f0 1.000 (1), f1 0.500 (2)" in largest and "f9 " in largest
    assert "f10" not in largest  # the ten largest
    assert "flash_fwd 1.500 (6), rope_lanes 0.250 (3)" in largest


@pytest.mark.parametrize("reader", [program_builds, program_memory])
@pytest.mark.parametrize("registry_is", ["absent", "the parent's"])
def test_nothing_without_the_programs_record(monkeypatch, reader,
                                             registry_is):
    # the parent's registry knows compiles and scope tables alone
    parents = types.SimpleNamespace(compiles=lambda *a: [],
                                    scope_table_of=lambda name: None)
    monkeypatch.setattr(reader, "device_programs", lambda: (
        None if registry_is == "absent" else parents))
    for name in ("setup_trace_s", "setup_build_share", "step_memory_share"):
        if metric(name)["reader"] == reader.__name__.rsplit(".", 1)[1]:
            assert reader.read(metric(name), run_of()) is None


def test_nothing_where_the_trace_shows_no_device(registry, monkeypatch):
    monkeypatch.setattr(program_memory, "device_programs",
                        lambda: device_programs)
    for name in ("setup_trace_s", "setup_build_share",
                 "setup_programs_built"):
        assert program_builds.read(metric(name), run_of(False)) is None
    assert program_memory.read(metric("step_memory_share"),
                               run_of(False)) is None


class FakeAnalysis:
    argument_size_in_bytes = 7_000
    output_size_in_bytes = 6_900
    alias_size_in_bytes = 6_800
    temp_size_in_bytes = 8_000
    generated_code_size_in_bytes = 100
    peak_memory_in_bytes = 13_000


class FakeCompiled:
    def as_text(self):
        return "HloModule jit_train_step\n"

    def memory_analysis(self):
        return FakeAnalysis()


def test_the_steps_memory_over_the_fullest_devices_limit(monkeypatch,
                                                         capsys):
    import jax

    def device(peak, limit):
        return types.SimpleNamespace(memory_stats=lambda: {
            "peak_bytes_in_use": peak, "bytes_limit": limit})

    monkeypatch.setattr(jax, "local_devices", lambda: [
        device(5_000, 32_000), device(7_000, 16_000), device(6_000, 8_000)])
    device_programs.clear()
    try:
        # no step noted yet: nothing
        assert program_memory.read(metric("step_memory_share"),
                                   run_of()) is None
        device_programs.note("train_step", FakeCompiled())
        share = program_memory.read(metric("step_memory_share"), run_of())
    finally:
        device_programs.clear()
    needed = 7_000 + 6_900 - 6_800 + 8_000 + 100
    assert share == pytest.approx(100 * needed / 16_000) and share <= 100
    said = capsys.readouterr().out
    assert f"train_step needs {needed} B of a device's 16000" in said
    assert "temp 8000" in said and "peak 13000" in said
    # a backend that reports no limit (the CPU's): nothing
    monkeypatch.setattr(jax, "local_devices", lambda: [
        types.SimpleNamespace(memory_stats=lambda: None)])
    device_programs.note("train_step", FakeCompiled())
    try:
        assert program_memory.read(metric("step_memory_share"),
                                   run_of()) is None
    finally:
        device_programs.clear()
