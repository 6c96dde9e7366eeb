"""The readers PR 25 adds, on a made-up run: the two that read the
program's span buffer find the measured window between warm-up and the
traced window, the two that read the flash kernels by name split what
``flash_attn_roofline`` sums, and the accepted patterns go on matching
the events the named kernels give."""

import ast
import importlib
import threading
from pathlib import Path
from types import SimpleNamespace

import pytest

import trace_reduce
from layer_metrics import flash_attn_roofline

PACKAGE = Path(__file__).resolve().parents[2] / "distributedarrays_tpu"
MAIN = threading.main_thread().ident


def read(name, run):
    return importlib.import_module(f"layer_metrics.{name}").read(run)


def _span(name, start, dur, parent=None, tid=MAIN):
    return {"name": name, "start": start, "dur": dur, "parent_id": parent,
            "tid": tid, "span_id": 0}


def _made_up_spans(steps=50, step_s=0.1, w0=10.0, profiler_up_s=1.0):
    """Set-up and two warm-up steps (the first holds seconds of
    compilation) run straight into the measured window; the traced window
    stands ``profiler_up_s`` away, the audit step seconds."""
    spans = [_span("distribute", 1.0, 0.3), _span("distribute", 1.4, 0.3)]

    def step(t, slow=1.0):
        t += 2e-5                      # the harness enters the step first
        return [_span("distribute", t, 1e-3 * slow),
                _span("reshard", t + 2e-4, 6e-4 * slow, parent=7),
                _span("mapreduce", t + 1.2e-3 * slow, 5e-4 * slow),
                # another thread's root span is not the caller's time
                _span("serve.dispatch", t, 5e-3, tid=MAIN + 1)]

    spans += step(w0 - 2.1, slow=1000.0)          # warm-up 1: compiles
    spans += step(w0 - step_s)                    # warm-up 2
    for i in range(steps):
        spans += step(w0 + i * step_s)
    hi = w0 + steps * step_s
    for i in range(10):                           # traced: slower entries
        spans += step(hi + profiler_up_s + i * step_s, slow=3.0)
    spans += step(hi + profiler_up_s + 4.0)       # the audit step
    return spans


def _run(steps=50, step_s=0.1, **kw):
    base = dict(steps=steps, window_s=steps * step_s,
                step_s=[step_s] * steps, dispatch_s=[0.00172] * steps,
                notes=[], trace={"steps": 10, "window_s": 10 * step_s},
                driver=object())
    base.update(kw)
    return SimpleNamespace(**base)


@pytest.fixture
def spans(monkeypatch):
    from distributedarrays_tpu import telemetry as tm
    box = {"spans": _made_up_spans()}
    monkeypatch.setattr(tm, "spans", lambda: box["spans"])
    return box


def test_span_readers_find_the_measured_window(spans):
    run = _run()
    assert read("entry_host_ms", run) == pytest.approx(1.5, rel=1e-6)
    assert read("reshard_host_ms", run) == pytest.approx(0.6, rel=1e-6)
    assert run.notes == []                 # a whole number of spans a step


def test_span_readers_cut_off_a_traced_window_that_follows_at_once(spans):
    # a profiler that is up in 30 ms leaves no gap to find the window by
    spans["spans"] = _made_up_spans(profiler_up_s=0.03)
    run = _run()
    assert read("entry_host_ms", run) == pytest.approx(1.5, rel=1e-6)
    assert read("reshard_host_ms", run) == pytest.approx(0.6, rel=1e-6)
    assert run.notes == []


def test_span_readers_scale_a_buffer_that_lost_its_head(spans):
    # the buffer begins 2 s into the window: 30 of 50 steps are covered
    spans["spans"] = [s for s in spans["spans"] if s["start"] >= 12.0]
    run = _run()
    assert read("entry_host_ms", run) == pytest.approx(1.5, rel=1e-3)
    assert any("not a whole number" in n for n in run.notes)


def test_span_readers_give_nothing_without_spans(spans):
    spans["spans"] = []
    assert read("entry_host_ms", _run()) is None
    assert read("reshard_host_ms", _run()) is None
    spans["spans"] = [s for s in _made_up_spans() if s["name"] != "reshard"]
    assert read("reshard_host_ms", _run()) is None
    assert read("entry_host_ms", _run()) == pytest.approx(1.5, rel=1e-6)


def _event(kernel, n=3):
    return (f"%{kernel}.{n} = (bf16[128,1024,64]{{2,1,0:T(8,128)(2,1)}}) "
            f"custom-call(bf16[128,1024,64] %p.{n}), "
            f'custom_call_target="tpu_custom_call"')


def test_flash_readers_split_what_the_roofline_sums():
    ops = {_event("flash_fwd"): 2.0, _event("flash_bwd_dq"): 1.25,
           _event("flash_bwd_dkv", 9): 1.75, "%fusion.1 = fusion(...)": 9.0}
    reduced = {"steps": 100, "window_s": 20.0, "ops_fullest": ops}
    run = _run(trace={"reduced": reduced})
    assert read("flash_fwd_ms", run) == pytest.approx(20.0)
    assert read("flash_bwd_ms", run) == pytest.approx(30.0)
    flash_s = sum(v for k, v in ops.items()
                  if flash_attn_roofline.PATTERN.search(k))
    assert (read("flash_fwd_ms", run) + read("flash_bwd_ms", run)
            == pytest.approx(1e3 * flash_s / 100))


def test_flash_readers_give_nothing_without_the_named_kernels():
    old = {"%jvp_jit_wrapped__.3 = bf16[1] custom-call(...)": 2.0,
           "%fusion.1 = fusion(...)": 9.0}
    run = _run(trace={"reduced": {"steps": 100, "ops_fullest": old}})
    assert read("flash_fwd_ms", run) is None
    assert read("flash_bwd_ms", run) is None
    assert read("flash_fwd_ms", _run(trace=None)) is None


def _kernel_names():
    """The ``name=`` of every ``pallas_call`` in the package."""
    names = []
    for path in PACKAGE.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "attr", "") == "pallas_call"):
                names += [kw.value.value for kw in node.keywords
                          if kw.arg == "name"]
    return names


def test_accepted_patterns_match_the_named_kernels():
    names = _kernel_names()
    assert len(names) == 15 and len(set(names)) == 15
    flash = {n for n in names
             if flash_attn_roofline.PATTERN.search(_event(n))}
    assert flash == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                     "flash_carry"}
    ring = {n for n in names if trace_reduce.COLLECTIVE.search(_event(n))}
    # the fused GEMMs and the attention hop compute while they move data:
    # a time in which one runs alone is no exposed collective
    assert ring == {"ring_all_gather", "ring_all_to_all",
                    "ring_reduce_scatter"}
    assert "attn_ring_hop" in names and "matmul_ring_ag" in names
