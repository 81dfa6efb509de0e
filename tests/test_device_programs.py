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
from ray_tpu.observability.metrics import device_program_compiles
from ray_tpu.parallel.mesh import MeshSpec, build_mesh

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


@pytest.mark.parametrize("cache_event,want", [
    ("/jax/compilation_cache/cache_hits", "hit"),
    ("/jax/compilation_cache/cache_misses", "miss"),
    (None, "off"),
])
def test_a_compile_event_is_paired_with_the_caches_answer(cache_event, want):
    before = device_program_compiles.series().get(("paired", want), 0)
    if cache_event:
        dp._on_event("/jax/compilation_cache/compile_requests_use_cache")
        dp._on_event(cache_event)
    dp._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.5,
                    fun_name="paired")  # not a backend compile
    dp._on_duration(dp._BACKEND_COMPILE, 0.25, fun_name="jit(paired)")
    dp._on_duration(dp._BACKEND_COMPILE, 0.25, fun_name="jit(unpaired)")
    events = dp.compiles()
    assert [(e.program, e.cache, e.seconds) for e in events] == [
        ("paired", want, 0.25), ("unpaired", "off", 0.25)]
    assert device_program_compiles.series()[("paired", want)] == before + 1


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
