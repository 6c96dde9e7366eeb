"""What a profiler trace can rely on: a stable ``name=`` on every
``pallas_call`` (held to the table of ``docs/telemetry.md``), one
``dat.<span>`` annotation for every telemetry span on the profiler's own
clock, and one journaled root span for each public entry point."""

import ast
import re
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _models
import distributedarrays_tpu as dat
from distributedarrays_tpu import telemetry as tm
from distributedarrays_tpu.telemetry import tracing

REPO = Path(__file__).resolve().parent.parent
PACKAGE = REPO / "distributedarrays_tpu"


# ---------------------------------------------------------------------------
# kernel names
# ---------------------------------------------------------------------------


def _pallas_calls():
    """(file:line, name or None) of every ``pallas_call(...)`` call."""
    out = []
    for path in sorted(PACKAGE.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "attr", "") == "pallas_call"):
                name = next((kw.value for kw in node.keywords
                             if kw.arg == "name"), None)
                out.append((f"{path.relative_to(REPO)}:{node.lineno}",
                            name.value if isinstance(name, ast.Constant)
                            else None))
    return out


def _documented_kernels():
    doc = (REPO / "docs" / "telemetry.md").read_text()
    table = doc.split("| Kernel name | Site |", 1)[1].split("\n\n", 1)[0]
    return re.findall(r"^\| `(\w+)` \|", table, re.M)


def test_every_pallas_call_has_a_stable_documented_name():
    calls = _pallas_calls()
    assert len(calls) >= 15
    unnamed = [site for site, name in calls if not name]
    assert not unnamed, f"pallas_call without a constant name=: {unnamed}"
    names = [name for _, name in calls]
    # one name a kernel, but for the delta rule's forward: its solve and
    # its recurrence are both gdn_fwd, so that gdn_scan_ms reads both
    shared = sorted(n for n in set(names) if names.count(n) > 1)
    assert shared == ["gdn_fwd"] and names.count("gdn_fwd") == 2, \
        f"kernels that share a name: {shared}"
    assert sorted(set(names)) == sorted(_documented_kernels())
    # kind only, no shapes: the event carries those
    assert all(re.fullmatch(r"[a-z][a-z0-9_]*", n) for n in names)


def test_spelling_the_benchmark_readers_rely_on():
    names = {name for _, name in _pallas_calls()}
    assert {n for n in names if "flash" in n} == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_carry"}
    assert {n for n in names if n.startswith("ring_")} == {
        "ring_all_gather", "ring_all_to_all", "ring_reduce_scatter"}
    assert {n for n in names if n.startswith("selective_scan_")} == {
        "selective_scan_fwd", "selective_scan_bwd"}
    assert {n for n in names if n.startswith("ssd_")} == {"ssd_fwd",
                                                          "ssd_bwd"}


def test_spelling_of_the_gated_delta_kernels():
    # gdn_scan_ms reads ``^%?gdn_(fwd|bwd)``
    names = {name for _, name in _pallas_calls()}
    assert {n for n in names if n.startswith("gdn_")} == {"gdn_fwd",
                                                          "gdn_bwd"}


@pytest.mark.parametrize("scope", [
    "embed", "mamba", "window", "full", "gmu", "cross", "mlp", "head_loss",
    "optimizer", "selective_scan_fwd", "selective_scan_bwd", "flash_fwd",
    "flash_bwd_dkv"])
def test_sambay_step_carries_its_scopes_and_kernel_names(scope):
    # the scopes docs/telemetry.md lists for models/sambay.py reach the
    # step program's op names: forward, recomputed and backward
    from distributedarrays_tpu.models import sambay as S
    names = set(re.findall(r'loc\("([^"]*)"', _models.step_text("sambay")))
    if scope in S.KINDS or scope == "mlp":
        hits = [n for n in names if f"block/{scope}/" in n
                or f"jvp(block)/{scope}/" in n]
        # a layer is computed forward, again in the backward, and backward
        assert any("rematted_computation" in n for n in hits)
        assert any(n.startswith("jit(step)/jvp(") for n in hits)
    else:
        hits = [n for n in names if scope in n]
    assert hits, scope


@pytest.mark.parametrize("scope", [
    "embed", "block/mla", "block/mlp", "block/moe/route",
    "block/moe/experts", "block/moe/shared", "mtp", "head_loss", "optimizer",
    "ragged_dot", "flash_fwd", "flash_bwd_dkv"])
def test_mla_moe_step_carries_its_scopes_and_kernel_names(scope):
    # the scopes docs/telemetry.md lists for models/mla_moe.py reach the
    # step program's op names; the FFN half forward, recomputed and backward
    names = set(re.findall(r'loc\("([^"]*)"', _models.step_text("mla_moe")))
    hits = [n for n in names if scope.replace("block/", "block)/") in n
            or scope in n]
    assert hits, scope
    if scope.startswith(("block/mlp", "block/moe")):
        assert any("rematted_computation" in n for n in hits)
        assert any(n.startswith("jit(step)/jvp(") for n in hits)
    if scope == "mtp":
        assert any("mtp/block" in n or "mtp)/block" in n for n in hits)
        assert any("head_loss" in n for n in hits)



# ---------------------------------------------------------------------------
# the registered step: its span, and each model's declared scopes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("module", ["transformer", "sambay", "mla_moe",
                                    "mamba2_hybrid"])
def test_declared_scopes_are_held_to_the_docs(module):
    import importlib
    mod = importlib.import_module(f"distributedarrays_tpu.models.{module}")
    doc = (REPO / "docs" / "telemetry.md").read_text()
    section = doc.split(
        "### Compiled programs and device time by phase", 1)[1].split(
            "\n### ", 1)[0]
    listed = section.split(f"`{module}`", 1)[1].split(";", 1)[0]
    assert re.findall(r"`([a-z_/]+)`", listed) == list(mod.SCOPES)
    # the scopes are the ones the module's own named_scopes nest to
    source = Path(mod.__file__).read_text()
    for leaf in {s.rsplit("/", 1)[-1] for s in mod.SCOPES} - {
            "route", "experts", "optimizer", *getattr(mod, "KINDS", ())}:
        assert f'named_scope("{leaf}")' in source, leaf


def test_optax_step_opens_the_documented_span(monkeypatch):
    import optax
    from distributedarrays_tpu.models import transformer as T
    made = []

    class Spy:
        is_enabled = staticmethod(lambda: True)

        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    cfg = T.Config(vocab=64, dim=32, heads=2, layers=1, max_seq=16)
    step, init = T.make_optax_train_step(cfg, optax.adamw(1e-3))
    params = T.init_params(jax.random.key(0), cfg)
    state, tokens = init(params), jnp.zeros((2, 17), jnp.int32)
    params, state, _ = step(params, state, tokens)     # traced outside
    monkeypatch.setattr(tracing, "_TraceAnnotation", Spy)
    step(params, state, tokens)
    assert made == [tracing.ANNOTATION_PREFIX + step.name]
    doc = (REPO / "docs" / "telemetry.md").read_text()
    table = doc.split("| Span in the trace | Opened by |", 1)[1].split(
        "\n\n", 1)[0]
    assert f"| `{made[0]}` |" in table
    assert made[0] == "dat.train.optax_step"


# ---------------------------------------------------------------------------
# one journaled root span for each public entry
# ---------------------------------------------------------------------------


def _entries():
    def djit(d):
        return dat.djit(lambda a: jnp.sin(a) + 1.0)(d)

    def relayout(d):
        return dat.distribute(d, procs=range(8), dist=(2, 4))

    return {
        "djit": (djit, "djit"),
        "dmapreduce": (lambda d: dat.dmapreduce(jnp.square, "sum", d),
                       "mapreduce"),
        "dsum": (dat.dsum, "mapreduce"),
        "dmean": (dat.dmean, "mapreduce"),
        "dstd": (dat.dstd, "mapreduce"),
        "dvar": (dat.dvar, "mapreduce"),
        "matmul": (lambda d: dat.matmul(d, d), "matmul"),
        "distribute": (relayout, "distribute"),
        "gather": (dat.gather, "gather"),
    }


@pytest.mark.parametrize("entry", list(_entries()))
def test_public_entry_leaves_exactly_one_root_span(entry):
    call, span_name = _entries()[entry]
    d = dat.drand((64, 64), procs=range(8), dist=(4, 2))
    call(d)                                   # compiled once, outside
    seen = {s["span_id"] for s in tm.spans()}
    call(d)
    new = [s for s in tm.spans() if s["span_id"] not in seen]
    roots = [s for s in new if s["parent_id"] is None]
    assert [s["name"] for s in roots] == [span_name]
    # the rest hangs under it: the journal links each span to its nearest
    # journaled ancestor (the buffer keeps the direct parent, which may be
    # an aggregate-only span such as put_global)
    ids = {s["span_id"] for s in new}
    linked = [e for e in tm.events("span") if e["span_id"] in ids
              and e["span_id"] != roots[0]["span_id"]]
    assert len(linked) == len(new) - 1
    assert all(e["parent_id"] in ids for e in linked)
    if entry == "djit":
        assert roots[0]["labels"]["fn"] == "<lambda>"
    if entry == "distribute":
        # the layout change and its four host phases nest under the root
        assert [s["name"] for s in new if s is not roots[0]] == ["reshard"]


def test_reshard_host_phases_are_counted_once_a_leg():
    d = dat.drand((64, 64), procs=range(8), dist=(4, 2))
    before = tm.span_stats()
    ran = tm.counter_value("reshard.chain_steps", kind="exchange")
    dat.distribute(d, procs=range(8), dist=(2, 4))
    after = tm.span_stats()
    for phase in ("reshard.plan", "reshard.program", "reshard.dispatch",
                  "distribute.wrap", "reshard", "distribute"):
        assert (after[phase]["count"]
                - before.get(phase, {"count": 0})["count"]) == 1, phase
    # aggregate-only: counted, never buffered
    assert not tm.spans("reshard.dispatch")
    # (4,2)->(2,4) is one exchange step, counted once when it ran
    assert tm.counter_value("reshard.chain_steps",
                            kind="exchange") == ran + 1


@pytest.mark.parametrize("grids,scope", [
    (((1, 4), (2, 2)), "reshard.chain/step0.exchange"),
    (((4, 1), (2, 2)), "reshard.chain/step0.a2a"),
    (((4, 1), (1, 4)), "reshard.all_to_all"),
])
def test_reshard_programs_carry_their_scopes(grids, scope):
    # the scopes docs/telemetry.md lists reach the lowered program's
    # op names (the compiled HLO keeps them as op_name)
    from distributedarrays_tpu import layout as L
    from distributedarrays_tpu.parallel import reshard as R
    shape = (32, 64)
    src, dst = (L.sharding_for(list(range(4)), g, shape) for g in grids)
    x = jax.device_put(np.zeros(shape, np.float32), src)
    plan = R.plan_reshard(x, dst)
    if plan.steps:
        fn = R._chain_jit(L.mesh_for(list(plan.ranks), plan.mesh_shape), 2,
                          plan.src_comp, plan.dst_comp, plan.steps, None)
    else:
        fn = R._collective_jit(L.mesh_for(list(plan.ranks), (plan.nparts,)),
                               plan.strategy, 2, plan.src_dim, plan.dst_dim,
                               plan.nparts, plan.chunk_axis, plan.nchunks,
                               None)
    assert scope in fn.lower(x).as_text(debug_info=True)


# ---------------------------------------------------------------------------
# every span is an annotation on the profiler's clock
# ---------------------------------------------------------------------------


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = max(Path(trace_dir).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            events += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events
                       if ev.name.startswith(tracing.ANNOTATION_PREFIX)]
    return sorted(events, key=lambda e: e[1])


def test_profiler_trace_holds_one_annotation_for_each_span(tmp_path):
    d = dat.drand((64, 64), procs=range(8), dist=(4, 2))
    chain = dat.djit(lambda a: jnp.sin(a) * 2.0)

    def calls():
        dat.matmul(d, d)
        chain(d)
        dat.dmean(d)
        dat.distribute(d, procs=range(8), dist=(2, 4))

    calls()                                   # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    seen = {s["span_id"] for s in tm.spans()}
    stats0 = tm.span_stats()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        calls()
    finally:
        jax.profiler.stop_trace()
    stats1 = tm.span_stats()
    events = _host_events(tmp_path)
    by_name = {}
    for name, lo, hi in events:
        by_name.setdefault(name, []).append((lo, hi))

    # one event for every span that finished in the window, aggregate-only
    # ones included
    counted = {k: v["count"] - stats0.get(k, {"count": 0})["count"]
               for k, v in stats1.items()}
    counted = {k: n for k, n in counted.items() if n}
    assert {"dat." + k: n for k, n in counted.items()} == {
        k: len(v) for k, v in by_name.items()}
    for root in ("matmul", "djit", "mapreduce", "distribute"):
        assert counted[root] >= 1
    assert counted["djit"] == 1 and counted["distribute.wrap"] == 1

    # nested as the spans nest: the k-th buffered span of a name is the
    # k-th event of that name, and lies inside its parent's event
    # (the journal links a span to its nearest journaled ancestor)
    new = [e for e in tm.events("span") if e["span_id"] not in seen]
    nth, where = {}, {}
    for s in sorted(new, key=lambda s: s["span_id"]):
        k = nth.get(s["name"], 0)
        nth[s["name"]] = k + 1
        where[s["span_id"]] = by_name["dat." + s["name"]][k]
    nested = [s for s in new if s["parent_id"] is not None]
    assert any(s["name"] == "reshard" for s in nested)
    for s in nested:
        lo, hi = where[s["span_id"]]
        plo, phi = where[s["parent_id"]]
        assert plo <= lo and hi <= phi, s["name"]
    # the aggregate-only phases lie inside the root that caused them
    (dlo, dhi), = by_name["dat.distribute"][-1:]
    for phase in ("reshard.plan", "reshard.program", "reshard.dispatch",
                  "distribute.wrap"):
        lo, hi = by_name["dat." + phase][-1]
        assert dlo <= lo and hi <= dhi, phase


def test_disabled_telemetry_makes_neither_span_nor_annotation(tmp_path,
                                                               monkeypatch):
    made = []

    class Spy:
        is_enabled = staticmethod(lambda: True)     # a session "runs"

        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    d = dat.drand((32, 32), procs=range(8), dist=(4, 2))
    dat.dsum(d)                               # looks jax.profiler up
    monkeypatch.setattr(tracing, "_TraceAnnotation", Spy)
    with tm.span("probe"):
        pass
    assert made == ["dat.probe"]              # the spy sees annotations
    del made[:]
    finished = tm.report()["spans"]["finished"]
    tm.disable()
    try:
        with tm.span("probe") as sp:
            assert sp is None
            dat.dsum(d)
            dat.distribute(d, procs=range(8), dist=(2, 4))
    finally:
        tm.enable()
    assert made == []
    assert tm.report()["spans"]["finished"] == finished
    assert not tracing.open_spans()


def test_tracing_imports_and_spans_without_jax():
    """``telemetry/tracing.py`` is stdlib-only at import, and a span in a
    process that never loaded JAX does not load it either."""
    code = textwrap.dedent(f"""
        import sys, types
        for name, sub in (("distributedarrays_tpu", ""),
                          ("distributedarrays_tpu.telemetry", "telemetry")):
            pkg = types.ModuleType(name)
            pkg.__path__ = [{str(PACKAGE)!r} + "/" + sub]
            sys.modules[name] = pkg
        from distributedarrays_tpu.telemetry import tracing
        assert "jax" not in sys.modules, "import pulled jax in"
        with tracing.span("outer"):
            with tracing.span("inner", _journal=False):
                pass
        assert "jax" not in sys.modules, "a span pulled jax in"
        (sp,) = tracing.spans("outer")
        assert sp["parent_id"] is None and sp["tname"] == "MainThread"
        print("ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_span_thread_name_is_left_to_to_dict():
    import threading
    got = {}

    def worker():
        with tm.span("probe.thread") as sp:
            got["sp"] = sp
            got["open"] = [s for s in tracing.open_spans()
                           if s["name"] == "probe.thread"]

    t = threading.Thread(target=worker, name="probe-worker")
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert got["sp"].tid == t.ident
    assert got["open"][0]["tname"] == "probe-worker"
    assert tm.spans("probe.thread")[-1]["tname"] == "probe-worker"


def test_event_pid_is_taken_once_and_again_after_fork():
    import os
    from distributedarrays_tpu.telemetry import core
    assert core._PID == os.getpid()
    if not hasattr(os, "fork"):
        pytest.skip("no fork on this platform")
    r, w = os.pipe()
    with warnings.catch_warnings():
        # the child touches neither JAX nor a lock: it writes and leaves
        warnings.simplefilter("ignore")
        pid = os.fork()
    if pid == 0:                              # the child: report and leave
        try:
            os.write(w, f"{core._PID} {os.getpid()}".encode())
        finally:
            os._exit(0)
    os.close(w)
    with os.fdopen(r) as f:
        cached, real = f.read().split()
    os.waitpid(pid, 0)
    assert cached == real != str(os.getpid())
