"""The readers PR 37 adds, on made-up runs: the join of the traced window's
events with the programs' phase maps (a name two programs hold, an event
that joins nothing, the refusal under 98%, the partition, recomputation
cutting across), the program's memory, and the host time inside the
``train.optax_step`` spans."""

import importlib
import threading
from types import SimpleNamespace

import pytest

from layer_metrics import phases

MAIN = threading.main_thread().ident
PHASE_METRICS = ("phase_attn_ms", "phase_ssm_ms", "phase_mlp_ms",
                 "phase_moe_ms", "phase_head_loss_ms", "phase_optimizer_ms",
                 "phase_unscoped_ms")


def read(name, run):
    return importlib.import_module(f"layer_metrics.{name}").read(run)


def _event(name, shape, opcode, operands="%p.1"):
    """An event's name as the trace gives it: layouts and operand types."""
    return (f"%{name} = {shape}{{1,0:T(8,128)(2,1)S(1)}} "
            f"{opcode}({shape}{{1,0:T(8,128)}} {operands}), kind=kLoop")


def _entry(name, shape, opcode, phase, which="forward", root=False):
    """A map's entry as the program gives it: its text has other layouts."""
    return name, (f"{'ROOT ' if root else ''}%{name} = {shape}{{1,0}} "
                  f"{opcode}", phase, which)


STEP = dict([
    _entry("fusion.1", "bf16[8,128]", "fusion", "block/attn"),
    _entry("flash_fwd.2", "bf16[8,128]", "custom-call", "block/attn",
           "recompute"),
    _entry("fusion.3", "bf16[8,128]", "fusion", "block/mamba", "backward"),
    _entry("fusion.4", "bf16[8,128]", "fusion", "block/mlp", "recompute"),
    _entry("fusion.5", "bf16[8,128]", "fusion", "block/moe/experts",
           "backward"),
    _entry("fusion.6", "f32[96]", "fusion", "head_loss", root=True),
    _entry("fusion.7", "f32[96]", "fusion", "mtp"),
    _entry("fusion.8", "f32[96]", "fusion", "optimizer"),
    _entry("copy.9", "bf16[8,128]", "copy", None),
    _entry("fusion.10", "f32[96]", "fusion", "a/scope/no/group/lists"),
    _entry("slice-done.11", "bf16[8,128]", "slice-done", "embed"),
])
# another live program holds a %fusion.1 too, of another shape, and a
# %copy.9 of the same head but another phase
OTHER = dict([
    _entry("fusion.1", "f32[4]", "fusion", "block/mlp"),
    _entry("copy.9", "bf16[8,128]", "copy", "block/mlp"),
])

OPS = {
    _event("fusion.1", "bf16[8,128]", "fusion"): 0.010,
    _event("flash_fwd.2", "bf16[8,128]", "custom-call"): 0.020,
    _event("fusion.3", "bf16[8,128]", "fusion"): 0.030,
    _event("fusion.4", "bf16[8,128]", "fusion"): 0.040,
    _event("fusion.5", "bf16[8,128]", "fusion"): 0.050,
    _event("fusion.6", "f32[96]", "fusion"): 0.060,
    _event("fusion.7", "f32[96]", "fusion"): 0.005,
    _event("fusion.8", "f32[96]", "fusion"): 0.070,
    _event("copy.9", "bf16[8,128]", "copy"): 0.003,
    _event("fusion.10", "f32[96]", "fusion"): 0.002,
    # the trace spells an asynchronous operation by its kind
    _event("slice-done.11", "bf16[8,128]", "async-done"): 0.004,
    # in no map
    _event("fusion.99", "bf16[8,128]", "fusion"): 0.001,
}


def _run(ops=OPS, steps=10, **kw):
    busy = sum(ops.values())
    base = dict(trace={"steps": steps, "window_s": 2.0, "reduced": {
        "steps": steps, "ops_fullest": dict(ops), "busy_s_fullest": busy}},
        notes=[])
    base.update(kw)
    return SimpleNamespace(**base)


@pytest.fixture
def registry(monkeypatch):
    """Live programs made of maps and memory alone."""
    from distributedarrays_tpu.telemetry import programs
    box = {"maps": [STEP], "memory": [{"total": 11_300_000_000}]}
    monkeypatch.setattr(programs, "live",
                        lambda: list(range(len(box["maps"]))))
    monkeypatch.setattr(programs, "phase_map", lambda p: box["maps"][p])
    monkeypatch.setattr(programs, "memory", lambda p: box["memory"][p])
    return box


def test_head_key_leaves_layouts_root_and_operands_out():
    event = ("%fusion.489 = (bf16[1024]{0:T(1024)(128)(2,1)S(1)}, "
             "(f32[8,128]{1,0:T(8,128)}, s32[]{:S(2)})) fusion(bf16[1024]"
             "{0:T(1024)} %p.1, f32[8,128]{1,0} %p.2), kind=kLoop, "
             "calls=%fused_computation.489")
    head = "ROOT %fusion.489 = (bf16[1024]{0}, (f32[8,128]{1,0}, s32[])) fusion"
    assert phases.head_key(event) == phases.head_key(head) == (
        "fusion.489", "(bf16[1024],(f32[8,128],s32[]))", "fusion")
    long = ("%t = (f32[1]{0}, f32[2]{0}, f32[3]{0}, f32[4]{0}, f32[5]{0}, "
            "/*index=5*/f32[6]{0}) tuple(%a)")
    assert phases.head_key(long)[1] == (
        "(f32[1],f32[2],f32[3],f32[4],f32[5],f32[6])")
    assert phases.head_key("%slice-done.3 = f32[8]{0} slice-done") == \
        phases.head_key("%slice-done.3 = f32[8]{0:T(256)} async-done(%x)")
    assert phases.head_key("%copy-start.3 = f32[8]{0} copy-start")[2] == \
        "async-start"
    assert phases.head_key("bench.step") is None
    assert phases.head_key("%dangling = ") is None


def test_the_seven_rows_partition_the_busy_time(registry):
    run = _run()
    got = {m: read(m, run) for m in PHASE_METRICS}
    assert got["phase_attn_ms"] == pytest.approx(3.0)      # 0.010 + 0.020
    assert got["phase_ssm_ms"] == pytest.approx(3.0)
    assert got["phase_mlp_ms"] == pytest.approx(4.0)
    assert got["phase_moe_ms"] == pytest.approx(5.0)
    # head_loss, mtp where it is the innermost scope, and embed
    assert got["phase_head_loss_ms"] == pytest.approx(6.9)
    assert got["phase_optimizer_ms"] == pytest.approx(7.0)
    # no scope, a phase no group lists, and the event in no map
    assert got["phase_unscoped_ms"] == pytest.approx(0.6)
    busy_ms = 1e3 * sum(OPS.values()) / 10
    assert sum(got.values()) == pytest.approx(busy_ms)
    # the partition's sum is said beside the busy time, once a run
    sums = [n for n in run.notes if "the seven rows sum to" in n]
    assert len(sums) == 1 and f"{busy_ms:.3f}" in sums[0]
    assert sum("built in" in n for n in run.notes) == 1


def test_recompute_cuts_across_the_rows(registry):
    run = _run()
    # attention's recomputed kernel and the MLP's recomputed fusion
    assert read("phase_recompute_ms", run) == pytest.approx(6.0)
    assert read("phase_attn_ms", run) == pytest.approx(3.0)


def test_a_name_two_programs_hold_joins_by_its_head(registry):
    registry["maps"] = [STEP, OTHER]
    run = _run()
    # %fusion.1: the other program's is of another shape, so the step's
    # entry is singled out; %copy.9: one head, two phases: it joins neither
    assert read("phase_attn_ms", run) == pytest.approx(3.0)
    assert read("phase_mlp_ms", run) == pytest.approx(4.0)
    assert read("phase_unscoped_ms", run) == pytest.approx(0.6)
    assert any("98.64% of busy time joined" in n for n in run.notes)
    alone = _run()
    registry["maps"] = [STEP]
    read("phase_attn_ms", alone)
    assert any("99.66% of busy time joined" in n for n in alone.notes)


def test_under_98_percent_joined_nothing_is_reported(registry):
    ops = dict(OPS)
    ops[_event("fusion.77", "bf16[8,128]", "fusion")] = 0.02
    run = _run(ops)
    assert all(read(m, run) is None for m in PHASE_METRICS)
    assert read("phase_recompute_ms", run) is None
    refused = [n for n in run.notes if "no phase_* metric" in n]
    assert len(refused) == 1 and "(93.33%)" in refused[0]
    # a foreign map: every name is there, no head agrees
    registry["maps"] = [{k: (h.replace("[", "[7,"), p, w)
                         for k, (h, p, w) in STEP.items()}]
    run = _run()
    assert read("phase_attn_ms", run) is None
    assert any("(0.00%)" in n for n in run.notes)


def test_nothing_to_read_gives_nothing(registry):
    assert read("phase_attn_ms", _run(trace=None)) is None
    assert read("phase_attn_ms", _run(trace={"reduced": None})) is None
    registry["maps"] = []
    run = _run()
    assert read("phase_attn_ms", run) is None and run.notes == []
    assert read("program_hbm_gb", run) is None


def test_a_program_without_the_registry_gives_nothing(monkeypatch):
    # this PR's files laid over a parent commit
    import sys
    monkeypatch.setitem(
        sys.modules, "distributedarrays_tpu.telemetry.programs", None)
    from distributedarrays_tpu import telemetry
    monkeypatch.delattr(telemetry, "programs", raising=False)
    run = _run()
    assert all(read(m, run) is None for m in PHASE_METRICS)
    assert read("phase_recompute_ms", run) is None
    assert read("program_hbm_gb", run) is None
    assert run.notes == []


def test_program_hbm_gb_is_the_largest_live_program(registry):
    registry["maps"] = [STEP, OTHER]
    registry["memory"] = [{"total": 11_300_000_000}, {"total": 2_000_000}]
    assert read("program_hbm_gb", _run()) == pytest.approx(11.3)


def test_the_real_registry_joins_a_compiled_step():
    # a tiny step through the registry itself: every instruction's head
    # parses, and events made from the program's own text join whole
    import jax
    import jax.numpy as jnp
    import optax
    from distributedarrays_tpu.models import transformer as T
    from distributedarrays_tpu.telemetry import programs
    cfg = T.Config(vocab=64, dim=32, heads=2, layers=1, max_seq=16,
                   dtype=jnp.float32)
    step, init = T.make_optax_train_step(cfg, optax.adamw(1e-3))
    p = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    step.note(p, jax.eval_shape(init, p),
              jax.ShapeDtypeStruct((2, 17), jnp.int32))
    pmap = programs.phase_map(step)
    assert all(phases.head_key(h) is not None for h, _, _ in pmap.values())
    ops = {f"{head}(f32[1]{{0}} %x), kind=kLoop": 1e-3
           for head, _, _ in pmap.values()}
    assert step in programs.live()
    mine = [q for q in programs.live() if q is step]
    by_group, by_pass, _, joined = phases.join(
        ops, [programs.phase_map(q) for q in mine])
    assert joined == pytest.approx(sum(ops.values()))
    assert by_group["attn"] > 0 and by_group["optimizer"] > 0
    assert set(by_pass) == {"forward", "backward"}
    run = _run()
    assert read("program_hbm_gb", run) >= programs.memory(step)["total"] / 1e9


# ---------------------------------------------------------------------------
# train_host_ms, on a made-up span buffer
# ---------------------------------------------------------------------------

def _span(name, start, dur, parent=None, tid=MAIN):
    return {"name": name, "start": start, "dur": dur, "parent_id": parent,
            "tid": tid, "span_id": 0}


def _train_spans(steps=50, step_s=0.15, w0=50.0, profiler_up_s=0.2,
                 first_s=6.0):
    """Three first steps (the first compiles), the readings, the measured
    window, and the traced window close behind it."""
    spans = [_span("train.optax_step", w0 - first_s - 3.0, first_s)]
    spans += [_span("train.optax_step", w0 - 2.9 + i * step_s, 2e-3)
              for i in (0, 1)]
    for i in range(steps):
        t = w0 + i * step_s + 2e-5
        spans.append(_span("train.optax_step", t, 2e-3))
        # what another thread, or a span under the step, adds is not it
        spans.append(_span("train.optax_step", t, 5e-3, tid=MAIN + 1))
        spans.append(_span("put_global", t + 1e-4, 1e-4, parent=3))
    hi = w0 + steps * step_s
    spans += [_span("train.optax_step", hi + profiler_up_s + i * step_s,
                    6e-3) for i in range(10)]
    return spans


def test_train_host_ms_reads_the_steps_spans_of_the_measured_window(
        monkeypatch):
    from distributedarrays_tpu import telemetry as tm
    steps, step_s = 50, 0.15
    run = SimpleNamespace(
        steps=steps, window_s=steps * step_s, step_s=[step_s] * steps,
        dispatch_s=[0.0023] * steps, notes=[],
        trace={"steps": 10, "window_s": 10 * step_s}, driver=object())
    monkeypatch.setattr(tm, "spans", lambda: _train_spans())
    assert read("train_host_ms", run) == pytest.approx(2.0, rel=1e-6)
    assert run.notes == []
    # a cold compile cache: the first call's span holds half a minute of
    # compilation, longer than the whole window, and is left out
    monkeypatch.setattr(tm, "spans", lambda: _train_spans(first_s=34.0))
    assert read("train_host_ms", run) == pytest.approx(2.0, rel=1e-6)
    assert run.notes == []
    # a profiler that took longer to come up: the traced window stands off
    monkeypatch.setattr(tm, "spans",
                        lambda: _train_spans(profiler_up_s=0.9))
    assert read("train_host_ms", run) == pytest.approx(2.0, rel=1e-6)
    # under the harness's dispatch_ms, by its glue
    assert read("train_host_ms", run) < 1e3 * run.dispatch_s[0]
    # a program that opens no such span (the parent): nothing
    monkeypatch.setattr(tm, "spans", lambda: [])
    assert read("train_host_ms", run) is None
