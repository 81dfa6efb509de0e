"""The registry of compiled device programs: stable program names and
named scopes in the train step, compile events by name, scope tables read
from a Compiled only when asked, and the join of a trace with them."""

import re
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.training import (
    build_forward,
    build_pipeline_train_step,
    build_train_step,
)
from ray_tpu.observability import device_programs as dp
from ray_tpu.observability.metrics import (
    device_program_build_seconds,
    device_program_compiles,
    device_program_kernel_trace_seconds,
    device_program_memory_bytes,
)
from ray_tpu.ops import attention
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.util import tracing

SCOPES = ("embed", "layers", "attention", "qkv_proj", "rope", "flash",
          "out_proj", "mlp", "final_norm", "loss", "unembed",
          "softmax_xent", "optimizer", "grad_norm")


@pytest.fixture(autouse=True)
def _empty_registry():
    dp.clear()
    yield
    dp.clear()


def _lowered_tiny_step(builder=build_train_step, spec=MeshSpec(), **kw):
    cfg = tfm.ModelConfig.debug(logits_chunk=16, tie_embeddings=False)
    mesh = build_mesh(spec)
    step, init_fn = builder(cfg, mesh, **kw)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    tokens = jnp.zeros((4, 65), jnp.int32)
    return step, step.lower(params, opt_state, tokens), (
        params, opt_state, tokens)


@pytest.fixture(scope="module")
def tiny_step_text():
    _, lowered, _ = _lowered_tiny_step()
    return lowered.compile().as_text()


def _segment(path, name):
    """``name`` is a whole segment of the path, transforms taken off."""
    return re.search(rf"(?:^|[/(]){name}(?:[/)]|$)", path) is not None


def test_train_step_compiles_to_a_module_of_its_name(tiny_step_text):
    assert tiny_step_text.startswith("HloModule jit_train_step,")


@pytest.mark.parametrize("which,want", [
    ("forward", lambda p: "jvp(" in p and "transpose(" not in p
     and "rematted_computation" not in p),
    ("recompute", lambda p: "rematted_computation" in p),
    ("backward", lambda p: "transpose(jvp(" in p
     and "rematted_computation" not in p),
    ("optimizer", lambda p: _segment(p, "optimizer")),
    ("loss", lambda p: _segment(p, "loss")),
    ("attention", lambda p: _segment(p, "attention")),
    ("mlp", lambda p: _segment(p, "mlp")),
    ("embed", lambda p: _segment(p, "embed")),
    ("final_norm", lambda p: _segment(p, "final_norm")),
])
def test_scope_table_has_every_pass_and_scope(tiny_step_text, which, want):
    # (no instruction need carry ``grad_norm``: the compiler merges the
    # reported norm with the clip's, which keeps the optimizer's name)
    paths = [p for p in dp.scope_table(tiny_step_text).values()
             if p.startswith("jit(train_step)/")]
    assert any(want(p) for p in paths), which


def test_no_dot_of_the_step_is_outside_a_scope(tiny_step_text):
    table = dp.scope_table(tiny_step_text)
    dots = re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \S+ dot\(",
                      tiny_step_text, re.MULTILINE)
    assert len(dots) >= 20
    for name in dots:
        path = table[name]
        assert any(_segment(path, s) for s in SCOPES), (name, path)
    # each inner scope sits in its block: the vocabulary is flat, the
    # nesting is the code's
    inner = {"qkv_proj": "attention", "rope": "attention",
             "flash": "attention", "out_proj": "attention",
             "unembed": "loss", "softmax_xent": "loss"}
    for path in table.values():
        for scope, block in inner.items():
            if _segment(path, scope) and path.startswith("jit(train_step)"):
                assert _segment(path, block), path


def test_pipeline_step_and_the_other_programs_have_their_names():
    _, lowered, _ = _lowered_tiny_step(
        build_pipeline_train_step, MeshSpec(pp=2), num_microbatches=2)
    text = lowered.compile().as_text()
    assert text.startswith("HloModule jit_pipeline_train_step,")
    table = dp.scope_table_of("pipeline_train_step")
    assert dp.scope_table_of("train_step") is None
    paths = list(table.values())
    for scope in ("layers", "attention", "mlp", "loss", "optimizer"):
        assert any(_segment(p, scope) for p in paths), scope
    # init and forward: named, not noted
    cfg = tfm.ModelConfig.debug()
    fwd = build_forward(cfg)
    _, init_fn = build_train_step(cfg, build_mesh(MeshSpec()))
    assert (fwd.__name__, init_fn.__name__) == ("forward", "train_init")
    key = jax.random.PRNGKey(0)
    assert init_fn.lower(key).compile().as_text().startswith(
        "HloModule jit_train_init,")
    assert sorted(dp._noted) == ["pipeline_train_step"]


def test_lowering_notes_and_a_direct_call_does_not():
    step, lowered, args = _lowered_tiny_step()
    assert dp.scope_table_of("train_step") is None  # lowered, not compiled
    out = step(*args)  # compiles inside JAX
    jax.block_until_ready(out)
    assert dp.scope_table_of("train_step") is None
    compiled = lowered.compile()
    assert dp._noted["train_step"] is compiled
    # the wrapper forwards what the jitted function has
    assert step.__name__ == "train_step"
    assert callable(step.trace) and callable(step.eval_shape)
    # what was lowered keeps its own attributes too
    assert "module" in lowered.as_text()


class _SpyCompiled:
    def __init__(self, text):
        self.text, self.asked = text, 0

    def as_text(self):
        self.asked += 1
        return self.text


HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(train_step)/jvp(layers)/while/body/closed_call/mlp/mul" source_file="x.py" source_line=3}
}

%body (c: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c = (s32[], f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%c), index=1
  %fusion.7 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(layers)/while/body/closed_call/mlp/mul"}
  %copy.2 = f32[8]{0} copy(%fusion.7)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte, %copy.2)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="params"}
  %while.1 = (s32[], f32[8]{0}) while(%a), condition=%cond, body=%body, metadata={op_name="jit(train_step)/jvp(layers)/while" stack_frame_id=4}
  ROOT %all-reduce-start.2 = f32[8]{0} all-reduce-start(%a), metadata={op_name="jit(train_step)/optimizer/add"}
}
"""


def test_scope_table_is_a_pure_function_of_the_text():
    assert dp.scope_table(HLO) == {
        "mul.3": "jit(train_step)/jvp(layers)/while/body/closed_call/mlp/mul",
        "fusion.7":
            "jit(train_step)/jvp(layers)/while/body/closed_call/mlp/mul",
        "a": "params",
        "while.1": "jit(train_step)/jvp(layers)/while",
        "all-reduce-start.2": "jit(train_step)/optimizer/add",
    }


def test_note_parses_nothing_until_asked_and_clear_empties():
    first, second = _SpyCompiled(HLO), _SpyCompiled(HLO.replace(
        "optimizer", "grad_norm"))
    dp.note("train_step", first)
    assert first.asked == 0
    table = dp.scope_table_of("train_step")
    assert table["all-reduce-start.2"].endswith("optimizer/add")
    assert dp.scope_table_of("train_step") is table and first.asked == 1
    # a new compile of the same name replaces the old entry
    dp.note("train_step", second)
    assert second.asked == 0
    assert dp.scope_table_of("train_step")["all-reduce-start.2"].endswith(
        "grad_norm/add")
    assert (first.asked, second.asked) == (1, 1)
    dp.clear()
    assert dp.scope_table_of("train_step") is None
    assert dp.compiles() == []


def test_anatomy_counts_a_loops_body_once():
    dp.note("train_step", _SpyCompiled(HLO))
    mlp = "jit(train_step)/jvp(layers)/while/body/closed_call/mlp/mul"
    events = [
        (0, 100_000, "while.1"),          # the loop holds its body
        (10_000, 40_000, "fusion.7"),
        (45_000, 50_000, "copy.2"),       # no op_name
        (50_000, 90_000, "fusion.7"),
        (120_000, 130_000, "all-reduce-start.2"),
    ]
    own = dp.anatomy(events, "train_step")
    assert own == pytest.approx({
        "jit(train_step)/jvp(layers)/while": 25e-6,
        mlp: 70e-6,
        "": 5e-6,
        "jit(train_step)/optimizer/add": 10e-6,
    })
    assert sum(own.values()) == pytest.approx(110e-6)  # the busy time
    assert dp.anatomy(events, "no_such_program") == {"": pytest.approx(110e-6)}


def test_compiles_sees_a_recompile_by_name_and_none_on_a_second_call():
    def scaled(x):
        return x * 3 + 1

    f = dp.named_jit(scaled, "scaled_for_test")
    t0 = time.perf_counter()
    f(jnp.ones(3))
    t1 = time.perf_counter()
    mine = [e for e in dp.compiles(t0, t1) if e.program == "scaled_for_test"]
    assert len(mine) == 1 and mine[0].seconds > 0
    assert t0 <= mine[0].at <= t1
    f(jnp.ones(3))  # the same shape: nothing compiles
    t2 = time.perf_counter()
    assert [e for e in dp.compiles(t1, t2)
            if e.program == "scaled_for_test"] == []
    f(jnp.ones(4))  # a new shape: a recompile, by name
    t3 = time.perf_counter()
    again = [e.program for e in dp.compiles(t2, t3)]
    assert again.count("scaled_for_test") == 1
    assert dp.compiles(t3, t3 + 1) == []
    series = device_program_compiles.series()
    assert sum(v for (program, _), v in series.items()
               if program == "scaled_for_test") == 2


@pytest.mark.parametrize("cache_event,read,want", [
    ("/jax/compilation_cache/cache_hits", 0.125, "hit"),
    ("/jax/compilation_cache/cache_hits", None, "hit"),
    ("/jax/compilation_cache/cache_misses", None, "miss"),
    # (a read reported and then a miss: nothing was read in the end)
    ("/jax/compilation_cache/cache_misses", 0.125, "miss"),
    (None, None, "off"),
])
def test_a_compile_event_is_paired_with_the_caches_answer(cache_event, read,
                                                          want):
    before = device_program_compiles.series().get(("paired", want), 0)
    if cache_event:
        dp._on_event("/jax/compilation_cache/compile_requests_use_cache")
        dp._on_event(cache_event)
    if read is not None:
        dp._on_duration(dp._CACHE_READ, read)
    dp._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.5,
                    fun_name="paired")  # not a backend compile
    dp._on_duration(dp._BACKEND_COMPILE, 0.25, fun_name="jit(paired)")
    dp._on_duration(dp._BACKEND_COMPILE, 0.25, fun_name="jit(unpaired)")
    events = dp.compiles()
    assert [(e.program, e.cache, e.seconds) for e in events] == [
        ("paired", want, 0.25), ("unpaired", "off", 0.25)]
    assert device_program_compiles.series()[("paired", want)] == before + 1
    # the read's seconds ride on the compile that hit, and on no other
    reads = [(e.program, e.seconds, e.cache) for e in dp.builds()
             if e.phase == "cache_read"]
    assert reads == ([("paired", read, "hit")]
                     if (want, read) == ("hit", 0.125) else [])
    assert [(e.program, e.phase) for e in dp.builds()
            if e.phase != "cache_read"] == [
        ("paired", "trace"), ("paired", "compile"), ("unpaired", "compile")]


def test_a_tiny_step_leaves_a_trace_a_lowering_and_a_compile():
    before = device_program_build_seconds.series()
    t0 = time.perf_counter()
    _, lowered, _ = _lowered_tiny_step()
    t1 = time.perf_counter()
    mine = [e for e in dp.builds(t0, t1) if e.program == "train_step"]
    assert [e.phase for e in mine] == ["trace", "lower"]
    lowered.compile()
    mine = [e for e in dp.builds(t0) if e.program == "train_step"]
    assert [e.phase for e in mine] == ["trace", "lower", "compile"]
    trace, lower, compile_ = mine
    assert all(e.seconds > 0 and t0 <= e.at for e in mine)
    assert trace.at <= lower.at <= compile_.at
    assert (trace.cache, lower.cache) == ("", "")
    assert compile_.cache in ("hit", "miss", "off")
    assert [(e.program, e.seconds, e.cache) for e in dp.compiles(t0)
            if e.program == "train_step"] == [
        ("train_step", compile_.seconds, compile_.cache)]
    # what the step traced is in its event, not in the ring: jnp's own
    # jitted functions by name, own times that the trace's seconds hold
    functions = {name: (own, times) for name, own, times in trace.nested}
    assert "_einsum" in functions and functions["_einsum"][1] > 1
    assert 0 < sum(own for own, _ in functions.values()) <= trace.seconds
    assert not [e for e in dp.builds(t0, t1) if e.program == "_einsum"]
    after = device_program_build_seconds.series()
    for e in mine:
        key = ("train_step", e.phase)
        assert after[key] - before.get(key, 0.0) == pytest.approx(e.seconds)


def _traced(name, seconds, inside=()):
    """What JAX reports of one trace: its start, what is traced inside
    it, its end."""
    dp._on_scalar(dp._TRACE, 0.0, fun_name=name)
    for args in inside:
        _traced(*args)
    dp._on_duration(dp._TRACE, seconds, fun_name=name)


def test_a_thousand_nested_functions_add_one_event_and_a_bounded_table():
    dp._on_duration(dp._BACKEND_COMPILE, 0.25, fun_name="jit(earlier)")
    # f0 .. f999 take 1 .. 1000 ms; each is traced twice more from JAX's
    # cache, g inside every one of them
    inside = [(f"f{i}", (i + 1) * 1e-3, [("g", 1e-4)]) for i in range(1000)]
    again = [(f"f{i}", 0.0) for i in range(1000)] * 2
    _traced("step", 600.0, inside + again)
    events = dp.builds()
    assert [(e.program, e.phase) for e in events] == [
        ("earlier", "compile"), ("step", "trace")]
    step = events[-1]
    assert step.seconds == 600.0 and step.kernels == ()
    assert len(step.nested) == dp._MAX_NESTED + 1
    named = step.nested[:-1]
    assert [name for name, _, _ in named[:3]] == ["f999", "f998", "f997"]
    assert named[0][1] == pytest.approx(1.0 - 1e-4) and named[0][2] == 3
    assert all(a[1] >= b[1] for a, b in zip(named, named[1:]))
    # the rest is summed, so the table still holds all that was nested
    assert step.nested[-1][0] == "(others)"
    assert sum(own for _, own, _ in step.nested) == pytest.approx(500.5)
    assert sum(times for _, _, times in step.nested) == 4000
    assert len(dp.compiles()) == 1


def test_a_program_built_inside_a_trace_is_an_event_of_its_own():
    """An eager operation on a constant while a step is traced: its
    trace is folded into the step's, its lowering and compile are kept
    and taken off the tracing function's own time."""
    dp._on_scalar(dp._TRACE, 0.0, fun_name="step")
    dp._on_scalar(dp._TRACE, 0.0, fun_name="layer")
    _traced("iota", 0.25)
    dp._on_scalar(dp._LOWER, 0.0, fun_name="jit(iota)")
    _traced("lowering_rule", 0.125)  # a rule written as a jnp function
    dp._on_duration(dp._LOWER, 0.5, fun_name="jit(iota)")
    dp._on_duration(dp._BACKEND_COMPILE, 1.0, fun_name="jit(iota)")
    dp._on_duration(dp._TRACE, 2.5, fun_name="layer")
    dp._on_duration(dp._TRACE, 3.0, fun_name="step")
    assert [(e.program, e.phase, e.seconds, e.nested) for e in dp.builds()
            ] == [
        ("iota", "lower", 0.5, (("lowering_rule", 0.125, 1),)),
        ("iota", "compile", 1.0, ()),
        ("step", "trace", 3.0, (("jit(iota)", 1.5, 2), ("layer", 0.75, 1),
                                ("iota", 0.25, 1))),
    ]


def test_kernel_trace_counts_a_traced_kernel_and_not_a_compiled_call(
        monkeypatch):
    monkeypatch.setattr(attention, "_FORCE_INTERPRET", True)
    q = jnp.ones((1, 256, 2, 128), jnp.bfloat16)

    def seconds():
        return device_program_kernel_trace_seconds.series().get(
            ("flash_fwd",), 0.0)

    before = seconds()
    attend = dp.named_jit(
        lambda q: attention._pallas_fwd(q, q, q, True, 1.0)[0], "attend")
    jax.block_until_ready(attend(q))
    traced = seconds() - before
    assert traced > 0
    trace, = [e for e in dp.builds()
              if (e.program, e.phase) == ("attend", "trace")]
    assert trace.kernels == (("flash_fwd", pytest.approx(traced), 1),)
    # what the kernel's body traced is the kernel's, not the program's
    assert sum(own for _, own, _ in trace.nested) + traced < trace.seconds
    # the compiled program runs no Python: nothing is counted again
    n = len(dp.builds())
    jax.block_until_ready(attend(q))
    assert seconds() - before == traced and len(dp.builds()) == n
    # outside any trace the counter alone takes it
    with dp.kernel_trace("flash_fwd"):
        pass
    assert seconds() - before > traced and len(dp.builds()) == n


def test_memory_of_a_noted_step_and_none_before():
    assert dp.memory_of("train_step") is None
    _, lowered, (params, _, _) = _lowered_tiny_step()
    assert dp.memory_of("train_step") is None  # lowered, not compiled
    compiled = lowered.compile()
    memory = dp.memory_of("train_step")
    analysis = compiled.memory_analysis()
    assert set(memory) >= {"argument", "output", "alias", "temp",
                           "generated_code"}
    assert memory["argument"] == analysis.argument_size_in_bytes
    assert memory["temp"] == analysis.temp_size_in_bytes > 0
    held = sum(p.nbytes for p in jax.tree.leaves(params))
    assert memory["argument"] > held and memory["alias"] >= held  # donated
    series = device_program_memory_bytes.series()
    assert {kind: series[("train_step", kind)] for kind in memory} == memory
    # what is not an executable has no memory to analyse
    dp.note("train_step", _SpyCompiled(HLO))
    assert dp.memory_of("train_step") is None
    assert dp.memory_of("no_such_program") is None


@pytest.mark.parametrize("on", [True, False])
def test_a_build_shows_in_the_timeline_where_tracing_is_on(on):
    from ray_tpu.observability.profiling import timeline

    tracing.shutdown_tracing()
    if on:
        tracing.setup_tracing()
    try:
        f = dp.named_jit(lambda x: x * 5 - 2, "timed_for_test")
        wall0 = time.time()
        f(jnp.ones(3))
        wall1 = time.time()
        mine = [e for e in timeline()
                if e["args"].get("program") == "timed_for_test"]
    finally:
        tracing.shutdown_tracing()
    if not on:
        assert mine == []
        return
    assert [e["name"] for e in mine] == [
        "device_program.trace", "device_program.lower",
        "device_program.compile"]
    assert mine[-1]["args"]["cache"] in ("hit", "miss", "off")
    built = {e.phase: e.seconds for e in dp.builds()
             if e.program == "timed_for_test"}
    for e in mine:
        # JAX's wall start, and an end within a millisecond of JAX's
        assert wall0 * 1e6 <= e["ts"] <= e["ts"] + e["dur"] <= wall1 * 1e6
        assert e["dur"] / 1e6 == pytest.approx(
            built[e["name"].split(".")[1]], abs=2e-3)


def test_the_event_ring_is_bounded():
    for i in range(dp._MAX_EVENTS + 10):
        dp._on_duration(dp._BACKEND_COMPILE, 0.0, fun_name=f"jit(p{i})")
    events = dp.compiles()
    assert len(events) == dp._MAX_EVENTS
    assert events[-1].program == f"p{dp._MAX_EVENTS + 9}"


def test_shutdown_keeps_the_registry():
    import ray_tpu

    dp.note("train_step", _SpyCompiled(HLO))
    ray_tpu.init(num_cpus=1)
    ray_tpu.shutdown()
    assert dp.scope_table_of("train_step") is not None
