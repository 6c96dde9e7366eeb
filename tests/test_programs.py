"""``telemetry/programs.py``: the registry of compiled programs, the
placement rule, and the wrapper ``optax_f32_step`` returns.  Each model's
tiny step is compiled once a process (``tests/_models.py``)."""

import re

import pytest

import jax
import jax.numpy as jnp
import optax

import _models
from distributedarrays_tpu import telemetry as tm
from distributedarrays_tpu.telemetry import programs
from distributedarrays_tpu.models import mla_moe as M
from distributedarrays_tpu.models import sambay as S
from distributedarrays_tpu.models import transformer as T


# ---------------------------------------------------------------------------
# the placement rule, on hand-written op_names
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name,scopes,want", [
    ("jit(step)/jvp(block)/attn/jit(wrapped)/flash_fwd/pallas_call",
     T.SCOPES, ("block/attn", "forward")),
    ("jit(step)/transpose(jvp(block))/mlp/dot_general",
     T.SCOPES, ("block/mlp", "backward")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "block/mamba/selective_scan_fwd/while/body/mul",
     S.SCOPES, ("block/mamba", "recompute")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/block/mamba/mul",
     S.SCOPES, ("block/mamba", "backward")),
    ("jit(step)/jvp(mtp)/block/mla/custom_vjp_call/dot_general",
     M.SCOPES, ("block/mla", "forward")),
    ("jit(step)/transpose(jvp(mtp))/head_loss/while/body/closed_call/exp",
     M.SCOPES, ("head_loss", "backward")),
    ("jit(step)/jvp(mtp)/dot_general", M.SCOPES, ("mtp", "forward")),
    ("jit(step)/jvp(block)/moe/experts/jit(silu)/mul",
     M.SCOPES, ("block/moe/experts", "forward")),
    ("jit(step)/jvp(block)/moe/add", M.SCOPES, (None, "forward")),
    ("jit(step)/optimizer/jit(_where)/select_n",
     T.SCOPES, ("optimizer", "forward")),
    ("jit(step)/jvp()/reduce_sum", T.SCOPES, (None, "forward")),
    ("jit(embed)/gather", T.SCOPES, (None, "forward")),
    ("jit(step)/jvp(block)/cross/transpose;jit(step)/jvp(block)/cross/mul",
     S.SCOPES, ("block/cross", "forward")),
    ("", T.SCOPES, (None, "forward")),
])
def test_place_peels_the_wrappers_and_takes_the_innermost_scope(
        op_name, scopes, want):
    assert programs.place(op_name, scopes) == want


def test_parse_hlo_reads_heads_tuples_and_root():
    text = "\n".join([
        "HloModule jit_step, is_scheduled=true",
        "%fused_computation.1 (p: f32[8]) -> (f32[8], (f32[2], s32[])) {",
        "  %p = f32[8]{0} parameter(0)",
        "  ROOT %t.1 = (f32[8]{0}, (f32[2]{0}, s32[])) tuple(%p, %p), "
        'metadata={op_name="jit(step)/jvp(block)/attn/mul"}',
        "}",
        "ENTRY %main.2 (a: f32[8]) -> f32[8] {",
        "  %fusion.1 = (f32[8]{0:T(256)}, (f32[2]{0}, s32[])) "
        "fusion(%a), kind=kLoop, calls=%fused_computation.1, "
        'metadata={op_name="jit(step)/transpose(jvp(block))/mlp/dot"}',
        "  %slice-done.3 = f32[8]{0} slice-done(%slice-start.3)",
        "}"])
    got = programs.parse_hlo(text, T.SCOPES)
    assert got["fusion.1"] == (
        "%fusion.1 = (f32[8]{0:T(256)}, (f32[2]{0}, s32[])) fusion",
        "block/mlp", "backward")
    assert got["t.1"] == (
        "%t.1 = (f32[8]{0}, (f32[2]{0}, s32[])) tuple", "block/attn",
        "forward")
    assert got["p"] == ("%p = f32[8]{0} parameter", None, "forward")
    assert got["slice-done.3"][0] == "%slice-done.3 = f32[8]{0} slice-done"
    assert set(got) == {"p", "t.1", "fusion.1", "slice-done.3"}


# ---------------------------------------------------------------------------
# each model's map
# ---------------------------------------------------------------------------

_MODELS = ["transformer", "sambay", "mla_moe"]


@pytest.fixture
def model(request):
    """(program, scopes, its map, its compiled text), compiled once."""
    return _models.step_program(request.param)


_EACH = pytest.mark.parametrize("model", _MODELS, indirect=True)


@_EACH
def test_map_places_every_instruction_that_has_an_op_name(model):
    step, scopes, pmap, text = model
    named = [m.group(1) for m in re.finditer(
        r"^\s+(?:ROOT )?%?(\S+) = .*op_name=", text, re.M)]
    assert len(named) > 500
    assert not [n for n in named if n not in pmap]
    # and by the rule: spot-check every one against its own op_name
    for line in text.splitlines():
        m = re.match(r'\s+(?:ROOT )?%?(\S+) = .*op_name="([^"]*)"', line)
        if m:
            assert pmap[m.group(1)][1:] == programs.place(m.group(2), scopes)
    # most named instructions lie under a declared scope
    placed = [n for n in named if pmap[n][1] is not None]
    assert len(placed) > 0.8 * len(named)


@_EACH
def test_every_declared_scope_is_hit(model):
    step, scopes, pmap, _ = model
    assert step.scopes == scopes
    assert {phase for _, phase, _ in pmap.values()} - {None} == set(scopes)


@_EACH
def test_recompute_shows_where_the_model_checkpoints(model, request):
    _, _, pmap, _ = model
    again = {phase for _, phase, which in pmap.values()
             if which == "recompute"}
    name = request.node.callspec.params["model"]
    if name == "transformer":
        assert not again
    elif name == "sambay":
        assert {f"block/{k}" for k in S.KINDS} | {"block/mlp"} <= again
    else:
        # the FFN half is computed again, latent attention is not
        assert {"block/mlp", "block/moe/experts", "block/moe/shared"} <= again
        assert "block/mla" not in again


@_EACH
def test_forward_and_backward_both_show(model):
    _, _, pmap, _ = model
    passes = {(phase, which) for _, phase, which in pmap.values()}
    for phase in ("embed", "head_loss", "block/mlp"):
        assert {(phase, "forward"), (phase, "backward")} <= passes
    assert ("optimizer", "backward") not in passes


# ---------------------------------------------------------------------------
# the wrapper
# ---------------------------------------------------------------------------

@pytest.fixture
def tiny():
    cfg = T.Config(vocab=64, dim=32, heads=2, layers=1, max_seq=16,
                   dtype=jnp.bfloat16)
    step, init = T.make_optax_train_step(cfg, optax.adamw(1e-3))
    params = T.init_params(jax.random.key(0), cfg)
    return step, params, init(params), jnp.zeros((2, 17), jnp.int32)


def _new_spans(seen):
    return [s for s in tm.spans() if s["span_id"] not in seen]


def test_wrapper_passes_the_jitted_function_through(tiny):
    step, params, state, tokens = tiny
    assert isinstance(step, programs.Program)
    assert step.name == "train.optax_step" and step.scopes == T.SCOPES
    lowered = step.lower(params, state, tokens)
    assert "optimizer" in lowered.as_text(debug_info=True)
    assert step.trace(params, state, tokens).jaxpr is not None
    assert step.abstract is None and step not in programs.live()
    with pytest.raises(AttributeError):
        step.no_such_attribute
    import copy
    twin = copy.copy(step)           # looks attributes up before _fn is set
    assert twin.name == step.name and twin.lower == step.lower


def test_wrapper_donates_as_the_bare_step_does(tiny):
    step, params, state, tokens = tiny
    leaf = jax.tree_util.tree_leaves(params)[0]
    moment = jax.tree_util.tree_leaves(state)[-1]
    new, new_state, loss = step(params, state, tokens)
    assert leaf.is_deleted() and moment.is_deleted()
    assert not tokens.is_deleted()
    assert jnp.isfinite(loss)
    assert not jax.tree_util.tree_leaves(new)[0].is_deleted()


def test_wrapper_notes_its_arguments_once(tiny):
    step, params, state, tokens = tiny
    params, state, _ = step(params, state, tokens)
    noted = step.abstract
    assert step in programs.live("train.optax_step")
    args, kwargs = noted
    assert not kwargs
    flat = jax.tree_util.tree_leaves(args)
    live = jax.tree_util.tree_leaves((params, state, tokens))
    assert [(a.shape, a.dtype) for a in flat] == [
        (x.shape, x.dtype) for x in live]
    step(params, state, tokens)
    assert step.abstract is noted


def test_one_root_span_a_bare_call_and_a_child_under_an_open_span(tiny):
    step, params, state, tokens = tiny
    params, state, _ = step(params, state, tokens)      # compiled outside
    seen = {s["span_id"] for s in tm.spans()}
    params, state, _ = step(params, state, tokens)
    (root,) = [s for s in _new_spans(seen) if s["name"] == "train.optax_step"]
    assert root["parent_id"] is None
    seen = {s["span_id"] for s in tm.spans()}
    with tm.span("train.step") as outer:
        step(params, state, tokens)
    (child,) = [s for s in _new_spans(seen)
                if s["name"] == "train.optax_step"]
    assert child["parent_id"] == outer.span_id


def test_disabled_telemetry_makes_neither_span_nor_registry_entry(tiny):
    step, params, state, tokens = tiny
    before = tm.span_stats().get("train.optax_step", {"count": 0})["count"]
    tm.disable()
    try:
        params, state, loss = step(params, state, tokens)
    finally:
        tm.enable()
    assert jnp.isfinite(loss)
    assert step.abstract is None and step not in programs.live()
    after = tm.span_stats().get("train.optax_step", {"count": 0})["count"]
    assert after == before
    with pytest.raises(ValueError, match="noted no arguments"):
        programs.compiled(step)


def test_a_dropped_program_leaves_the_registry():
    import gc
    bump = jax.jit(lambda x: x + 1)
    one = programs.register("probe.dropped", bump)
    two = programs.register("probe.dropped", bump)     # a name is no key
    one(jnp.zeros(3))
    two(jnp.zeros(4))
    assert set(programs.live("probe.dropped")) == {one, two}
    assert one.abstract != two.abstract
    del one
    gc.collect()
    assert programs.live("probe.dropped") == [two]


def test_memory_sums_as_stated(tiny):
    step, params, state, tokens = tiny
    step.note(params, state, tokens)
    mem = programs.memory(step)
    stats = programs.compiled(step).memory_analysis()
    assert mem["argument"] == stats.argument_size_in_bytes > 0
    assert mem["temp"] == stats.temp_size_in_bytes
    assert mem["total"] == (mem["argument"] + mem["output"] - mem["alias"]
                            + mem["temp"] + mem["generated_code"])
    # parameters and state are donated: the outputs alias them, and what
    # is left of the outputs is the loss
    assert mem["alias"] > 0.9 * mem["argument"]
    assert programs.compiled(step) is programs.compiled(step)


def test_telemetry_package_exports_the_module_without_loading_jax():
    import subprocess
    import sys
    code = ("import sys, types\n"
            "import importlib.util as u\n"
            "from pathlib import Path\n"
            f"root = Path({str(T.__file__)!r}).parents[1]\n"
            "pkg = types.ModuleType('distributedarrays_tpu')\n"
            "pkg.__path__ = [str(root)]\n"
            "sys.modules['distributedarrays_tpu'] = pkg\n"
            "from distributedarrays_tpu import telemetry\n"
            "assert telemetry.programs.register and 'programs' in "
            "telemetry.__all__\n"
            "assert 'jax' not in sys.modules, 'telemetry pulled jax in'\n"
            "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
