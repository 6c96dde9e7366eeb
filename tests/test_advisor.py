"""Doctor-driven self-tuning suite: the advisor decision table against
hand-built journals (one per finding kind — unoverlapped rdma comm,
rdma-vs-xla side-by-side deltas, low-roofline ``pallas.matmul``),
provenance round-trip through the cache file, the guarded apply path
(micro-probe rollback on an injected 2x-slower tune, measure-or-revert
on a probe that dies after the write), the ``autotune_regressed`` alert
firing exactly once per rollback and clearing as the sample ages out,
the ``advise`` / ``regress --explain`` CLI surfaces, and the summarize
tuning-provenance table."""

import io
import json
import subprocess
import sys
from pathlib import Path

import pytest

from distributedarrays_tpu.telemetry import advisor, alerts, perf, regress
from distributedarrays_tpu.telemetry.fixtures import telemetry_capture  # noqa: F401
from distributedarrays_tpu.telemetry.summarize import (format_summary,
                                                       summarize)
from distributedarrays_tpu.utils import autotune

REPO = Path(__file__).resolve().parents[1]

# synthetic platform: every peak 100 units/s makes the roofline math
# hand-computable (bytes_ici=100 over 1s == exactly the ICI peak)
PEAKS = {"flops": 100.0, "hbm": 100.0, "ici": 100.0, "platform": "t"}

A2A_KEY = "a2a|8|64|float32|8|t|t"
DISPATCH_KEY = "reshard|allconcat|64|64|float32|8|t|t"
GEMM_KEY = "512|512|512|float32|float32|t|t"


@pytest.fixture
def clean_autotune(monkeypatch):
    """Empty registry that never lazily reloads the seed/env cache."""
    autotune.clear()
    monkeypatch.setattr(autotune, "_LOADED_ENV", True)
    yield autotune
    autotune.clear()


def _sp(sid, name, start, dur, labels=None, parent=None):
    return {"cat": "span", "name": name, "span_id": sid,
            "parent_id": parent, "start": float(start),
            "dur": float(dur), "tid": 1, "labels": dict(labels or {})}


def _rdma_reshard_span(sid=1, *, dur=1.0, chunks=4, start=0.0):
    """A reshard span whose ICI stamp fills its whole duration with
    zero compute to hide behind -> unoverlapped_comm, severity == dur."""
    return _sp(sid, "reshard", start, dur, labels={
        "bytes_ici": 100.0 * dur, "dispatch": "rdma",
        "autotune_key": A2A_KEY, "dispatch_key": DISPATCH_KEY,
        "rdma_chunks": chunks, "shape": [64, 64], "dtype": "float32",
        "src_dim": 0, "dst_dim": 1, "nparts": 8})


def _xla_reshard_span(sid=2, *, dur=0.4, start=10.0):
    return _sp(sid, "reshard", start, dur, labels={
        "bytes_ici": 10.0, "dispatch": "xla",
        "dispatch_key": DISPATCH_KEY, "shape": [64, 64],
        "dtype": "float32", "src_dim": 0, "dst_dim": 1, "nparts": 8})


def _gemm_span(sid=3, *, dur=1.0, flops=30.0, start=20.0):
    """flops=30 over 1s against a 100-peak -> 30% roofline -> finding."""
    return _sp(sid, "pallas.matmul", start, dur, labels={
        "flops": flops, "autotune_key": GEMM_KEY,
        "shape": [512, 512, 512], "dtype": ["float32", "float32"]})


# ---------------------------------------------------------------------------
# finding action hints (satellite: machine-readable hint field)
# ---------------------------------------------------------------------------


def test_findings_carry_action_hints():
    evs = [_rdma_reshard_span(1), _xla_reshard_span(2), _gemm_span(3)]
    analysis = perf.analyze(evs, peaks=PEAKS)
    hints = {f["action"]["kernel"]: f["action"]
             for f in analysis["findings"] if f.get("action")}
    rc = hints["rdma_chunks"]
    assert rc["key"] == A2A_KEY
    assert rc["direction"] == "increase" and rc["current"] == 4
    assert rc["dispatch_key"] == DISPATCH_KEY
    assert hints["rdma_dispatch"]["current"] == "xla"   # the xla span
    lr = hints["pallas_matmul"]
    assert lr["key"] == GEMM_KEY
    assert lr["direction"] == "resweep"
    assert lr["shape"] == [512, 512, 512]


def test_action_hint_xla_span_suggests_dispatch_compare():
    # an unoverlapped xla span has no chunk knob; the hint degrades to a
    # dispatch comparison keyed on the span's shape class
    hint = perf._action_hint("unoverlapped_comm", "reshard",
                             {"dispatch": "xla",
                              "dispatch_key": DISPATCH_KEY})
    assert hint == {"kernel": "rdma_dispatch", "key": DISPATCH_KEY,
                    "param": "dispatch", "direction": "compare",
                    "current": "xla"}
    # no registry key on the span -> no hint, never a guess
    assert perf._action_hint("unoverlapped_comm", "reshard", {}) is None
    assert perf._action_hint("low_roofline", "other.op",
                             {"autotune_key": GEMM_KEY}) is None


# ---------------------------------------------------------------------------
# the decision table
# ---------------------------------------------------------------------------


def test_advise_unoverlapped_rdma_doubles_chunks(clean_autotune):
    analysis = perf.analyze([_rdma_reshard_span(chunks=4)], peaks=PEAKS)
    actions = {a.kind: a for a in advisor.advise(analysis)}
    a = actions["rdma_chunks"]
    assert a.kernel == "rdma_chunks" and a.key == A2A_KEY
    assert a.proposed == [8]                       # 4 -> 8
    assert a.finding == "unoverlapped_comm"
    assert a.evidence["chunks"] == 4
    assert a.evidence["overlap_frac"] == 0.0
    assert a.probe["op"] == "reshard" and a.probe["shape"] == [64, 64]


def test_advise_chunk_depth_edge_cases(clean_autotune):
    # chunks=1 doubles to 2; at the cap there is nothing to propose
    one = perf.analyze([_rdma_reshard_span(chunks=1)], peaks=PEAKS)
    acts = [a for a in advisor.advise(one) if a.kind == "rdma_chunks"]
    assert acts and acts[0].proposed == [2]
    capped = perf.analyze([_rdma_reshard_span(chunks=advisor.MAX_CHUNKS)],
                          peaks=PEAKS)
    assert not [a for a in advisor.advise(capped)
                if a.kind == "rdma_chunks"]
    # 48 doubles past the cap -> clamps to 64, still a real change
    near = perf.analyze([_rdma_reshard_span(chunks=48)], peaks=PEAKS)
    acts = [a for a in advisor.advise(near) if a.kind == "rdma_chunks"]
    assert acts and acts[0].proposed == [advisor.MAX_CHUNKS]


def test_dispatch_deltas_need_both_sides(clean_autotune):
    only_rdma = perf.analyze([_rdma_reshard_span()], peaks=PEAKS)
    assert advisor.dispatch_deltas(only_rdma) == []
    both = perf.analyze([_rdma_reshard_span(dur=1.0),
                         _xla_reshard_span(dur=0.4)], peaks=PEAKS)
    deltas = advisor.dispatch_deltas(both)
    assert len(deltas) == 1
    d = deltas[0]
    assert d["key"] == DISPATCH_KEY and d["faster"] == "xla"
    assert d["rdma_s"] == pytest.approx(1.0)
    assert d["xla_s"] == pytest.approx(0.4)
    assert d["delta_frac"] == pytest.approx(0.6)


def test_advise_pins_faster_dispatch(clean_autotune):
    analysis = perf.analyze([_rdma_reshard_span(dur=1.0),
                             _xla_reshard_span(dur=0.4)], peaks=PEAKS)
    acts = [a for a in advisor.advise(analysis) if a.kind == "dispatch"]
    assert len(acts) == 1
    a = acts[0]
    assert a.kernel == "rdma_dispatch" and a.key == DISPATCH_KEY
    assert a.proposed == "xla" and a.current is None
    assert a.evidence["delta_frac"] == pytest.approx(0.6)
    # already pinned to the winner -> nothing to do
    autotune.record("rdma_dispatch", DISPATCH_KEY, "xla")
    again = advisor.advise(analysis)
    assert not [x for x in again if x.kind == "dispatch"]


def test_advise_dispatch_jitter_gate(clean_autotune):
    # 5% apart is scheduler noise, not a preference
    analysis = perf.analyze([_rdma_reshard_span(dur=1.0),
                             _xla_reshard_span(dur=0.95)], peaks=PEAKS)
    assert not [a for a in advisor.advise(analysis)
                if a.kind == "dispatch"]


def test_advise_low_roofline_resweep(clean_autotune):
    autotune.record("pallas_matmul", GEMM_KEY, [8, 8, 8])
    analysis = perf.analyze([_gemm_span()], peaks=PEAKS)
    acts = [a for a in advisor.advise(analysis) if a.kind == "resweep"]
    assert len(acts) == 1
    a = acts[0]
    assert a.kernel == "pallas_matmul" and a.key == GEMM_KEY
    assert a.current == [8, 8, 8] and a.proposed is None
    assert a.candidates and len(a.candidates) <= 24
    for bm, bn, bk in a.candidates:
        assert 512 % bm == 0 and 512 % bn == 0 and 512 % bk == 0
    assert a.evidence["roofline_frac"] == pytest.approx(0.3)


def test_advise_dedups_per_registry_address(clean_autotune):
    # three findings for the same shape class -> one action per address
    evs = [_rdma_reshard_span(1, start=0.0),
           _rdma_reshard_span(2, start=5.0),
           _gemm_span(3), _gemm_span(4, start=30.0)]
    actions = advisor.advise(perf.analyze(evs, peaks=PEAKS))
    addrs = [(a.kernel, a.key) for a in actions]
    assert len(addrs) == len(set(addrs))
    assert set(a.kind for a in actions) == {"rdma_chunks", "resweep"}


# ---------------------------------------------------------------------------
# provenance round-trip + undo
# ---------------------------------------------------------------------------


def test_provenance_roundtrip_and_undo(clean_autotune, tmp_path):
    autotune.record("rdma_chunks", A2A_KEY, [1])           # plain seed
    assert autotune.provenance_for("rdma_chunks", A2A_KEY) is None
    stamp = {"source": "advisor", "finding": "unoverlapped_comm",
             "evidence": {"before_s": [0.01]}, "previous": [1]}
    autotune.record("rdma_chunks", A2A_KEY, [2], provenance=stamp)
    assert autotune.get("rdma_chunks", A2A_KEY) == [2]
    assert autotune.provenance_for(
        "rdma_chunks", A2A_KEY)["source"] == "advisor"
    # the stamp survives the cache file round-trip in a sidecar key
    path = tmp_path / "cache.json"
    autotune.save(str(path))
    doc = json.loads(path.read_text())
    assert doc["rdma_chunks"][A2A_KEY] == [2]
    assert doc["__provenance__"]["rdma_chunks"][A2A_KEY][
        "finding"] == "unoverlapped_comm"
    autotune.clear()
    autotune.load(str(path))
    assert autotune.get("rdma_chunks", A2A_KEY) == [2]
    assert autotune.get("__provenance__", A2A_KEY) is None  # not an entry
    assert autotune.provenance_for(
        "rdma_chunks", A2A_KEY)["source"] == "advisor"
    # undo restores the exact pre-write state (value AND no provenance);
    # reloading dropped the undo journal, so re-stamp first
    autotune.record("rdma_chunks", A2A_KEY, [4], provenance=stamp)
    assert autotune.undo("rdma_chunks", A2A_KEY) is True
    assert autotune.get("rdma_chunks", A2A_KEY) == [2]
    assert autotune.undo("rdma_chunks", A2A_KEY) is False  # journal drained


def test_undo_restores_deletion(clean_autotune):
    assert autotune.get("rdma_dispatch", DISPATCH_KEY) is None
    autotune.record("rdma_dispatch", DISPATCH_KEY, "xla",
                    provenance={"source": "advisor"})
    assert autotune.undo("rdma_dispatch", DISPATCH_KEY) is True
    assert autotune.get("rdma_dispatch", DISPATCH_KEY) is None
    assert DISPATCH_KEY not in autotune._REGISTRY.get("rdma_dispatch", {})


def test_undo_journal_is_bounded(clean_autotune):
    for i in range(autotune._UNDO_LIMIT + 10):
        autotune.record("k", f"key{i}", [i], provenance={"i": i})
    assert len(autotune.undo_log()) == autotune._UNDO_LIMIT


# ---------------------------------------------------------------------------
# guarded apply
# ---------------------------------------------------------------------------


def _chunk_action(current=None, proposed=None):
    return advisor.TuningAction(
        kind="rdma_chunks", kernel="rdma_chunks", key=A2A_KEY,
        current=current, proposed=proposed or [2],
        finding="unoverlapped_comm", evidence={"severity_s": 1.0},
        probe={"op": "reshard", "shape": [64, 64]})


def _registry_probe(slow_on, fast=0.01, slow=0.02):
    """Deterministic probe: reads the registry the way a real workload
    would — the configs in ``slow_on`` measure ``slow`` seconds."""
    def probe(action, config=None):
        cur = autotune.get(action.kernel, action.key)
        return slow if cur in slow_on else fast
    return probe


def test_apply_keeps_an_improving_tune(clean_autotune, telemetry_capture):
    autotune.record("rdma_chunks", A2A_KEY, [1])
    action = _chunk_action(current=[1], proposed=[2])
    results = advisor.apply([action], probe=_registry_probe([[1]]),
                            repeats=3, evaluate_alerts=False)
    assert [r["status"] for r in results] == ["applied"]
    assert autotune.get("rdma_chunks", A2A_KEY) == [2]
    prov = autotune.provenance_for("rdma_chunks", A2A_KEY)
    assert prov["source"] == "advisor"
    assert prov["finding"] == "unoverlapped_comm"
    assert prov["previous"] == [1]
    assert prov["evidence"]["before_s"] == [0.02, 0.02, 0.02]
    assert telemetry_capture.counter_value(
        "autotune.advisor_applies", kind="rdma_chunks") == 1


def test_apply_rolls_back_a_regressing_tune(clean_autotune,
                                            telemetry_capture):
    autotune.record("rdma_chunks", A2A_KEY, [1])
    action = _chunk_action(current=[1], proposed=[2])
    # the proposal measures 2x slower -> must not survive
    results = advisor.apply([action],
                            probe=_registry_probe([[2]], slow=0.02),
                            repeats=3, evaluate_alerts=False)
    r = results[0]
    assert r["status"] == "rolled_back"
    assert "micro-probe regressed" in r["reason"]
    assert autotune.get("rdma_chunks", A2A_KEY) == [1]        # restored
    assert autotune.provenance_for("rdma_chunks", A2A_KEY) is None
    assert autotune.undo_log() == []                  # entry consumed
    assert telemetry_capture.counter_value(
        "autotune.advisor_rollbacks", kind="rdma_chunks") == 1
    assert telemetry_capture.counter_value(
        "autotune.undo", kernel="rdma_chunks") == 1


def test_apply_measure_or_revert_contract(clean_autotune,
                                          telemetry_capture):
    autotune.record("rdma_chunks", A2A_KEY, [1])

    calls = {"n": 0}

    def probe(action, config=None):
        calls["n"] += 1
        if calls["n"] > 4:            # warmup+3 before OK; after dies
            raise RuntimeError("device lost")
        return 0.01

    results = advisor.apply([_chunk_action(current=[1], proposed=[2])],
                            probe=probe, repeats=3,
                            evaluate_alerts=False)
    assert results[0]["status"] == "rolled_back"
    assert "after-probe failed" in results[0]["reason"]
    assert autotune.get("rdma_chunks", A2A_KEY) == [1]
    # a probe that cannot even measure the baseline skips, writes nothing
    def dead(action, config=None):
        raise RuntimeError("no devices")
    results = advisor.apply([_chunk_action(current=[1], proposed=[4])],
                            probe=dead, repeats=3, evaluate_alerts=False)
    assert results[0]["status"] == "skipped"
    assert autotune.get("rdma_chunks", A2A_KEY) == [1]


def test_apply_resweep_records_sweep_winner(clean_autotune,
                                            telemetry_capture):
    autotune.record("pallas_matmul", GEMM_KEY, [8, 8, 8])
    action = advisor.TuningAction(
        kind="resweep", kernel="pallas_matmul", key=GEMM_KEY,
        current=[8, 8, 8], proposed=None, finding="low_roofline",
        evidence={"severity_s": 0.7},
        probe={"op": "pallas.matmul", "shape": [512, 512, 512],
               "dtype": ["float32", "float32"]},
        candidates=[(8, 8, 8), (128, 128, 128), (512, 512, 512)])

    def probe(act, config=None):
        # candidate timing: 128-blocks win; the bare probes (config None)
        # read the registry, so after the write the probe speeds up
        if config is not None:
            return {(8, 8, 8): 0.03, (128, 128, 128): 0.01,
                    (512, 512, 512): 0.02}[tuple(config)]
        cur = autotune.get(act.kernel, act.key)
        return 0.01 if cur == [128, 128, 128] else 0.03

    results = advisor.apply([action], probe=probe, repeats=3,
                            evaluate_alerts=False)
    r = results[0]
    assert r["status"] == "applied"
    assert r["proposed"] == [128, 128, 128]
    assert r["sweep_candidates"] == 3
    assert autotune.get("pallas_matmul", GEMM_KEY) == [128, 128, 128]
    assert autotune.provenance_for(
        "pallas_matmul", GEMM_KEY)["finding"] == "low_roofline"


def test_apply_skips_noop_proposal(clean_autotune, telemetry_capture):
    autotune.record("rdma_chunks", A2A_KEY, [2])
    results = advisor.apply([_chunk_action(current=[2], proposed=[2])],
                            probe=lambda a, c=None: 0.01,
                            evaluate_alerts=False)
    assert results[0]["status"] == "skipped"
    assert results[0]["reason"] == "already at proposal"
    assert autotune.provenance_for("rdma_chunks", A2A_KEY) is None


# ---------------------------------------------------------------------------
# the autotune_regressed alert
# ---------------------------------------------------------------------------


def test_autotune_regressed_fires_once_and_clears(clean_autotune,
                                                  telemetry_capture):
    autotune.record("rdma_chunks", A2A_KEY, [1])
    mgr = alerts.AlertManager()
    t0 = 1000.0
    # healthy tick before the rollback: signal exists, no breach
    alerts.ensure_autotune_rule(mgr)
    assert alerts.ensure_autotune_rule(mgr) is mgr.rules()[0]  # idempotent
    mgr.evaluate(t0 - 30.0)
    assert mgr.firing() == []
    advisor.apply([_chunk_action(current=[1], proposed=[2])],
                  probe=_registry_probe([[2]]), repeats=3,
                  manager=mgr, now=t0)
    assert mgr.firing() == ["autotune_regressed"]
    transitions = [e for e in telemetry_capture.events()
                   if e.get("cat") == "alert"
                   and e.get("name") == "autotune_regressed"]
    assert [e["state"] for e in transitions] == ["firing"]
    # the rollback sample ages out of the 60s fast window -> clears
    mgr.evaluate(t0 + 120.0)
    assert mgr.firing() == []
    transitions = [e for e in telemetry_capture.events()
                   if e.get("cat") == "alert"
                   and e.get("name") == "autotune_regressed"]
    assert [e["state"] for e in transitions] == ["firing", "cleared"]
    # exactly one firing transition for exactly one rollback
    assert telemetry_capture.counter_value(
        "alerts.transitions", alert="autotune_regressed",
        state="firing") == 1


def test_applied_tune_never_pages(clean_autotune, telemetry_capture):
    autotune.record("rdma_chunks", A2A_KEY, [1])
    mgr = alerts.AlertManager()
    advisor.apply([_chunk_action(current=[1], proposed=[2])],
                  probe=_registry_probe([[1]]), repeats=3,
                  manager=mgr, now=500.0)
    assert mgr.firing() == []


# ---------------------------------------------------------------------------
# journal + summarize tuning-provenance table
# ---------------------------------------------------------------------------


def test_summarize_renders_tuning_table(clean_autotune,
                                        telemetry_capture):
    autotune.record("rdma_chunks", A2A_KEY, [1])
    advisor.apply([_chunk_action(current=[1], proposed=[2])],
                  probe=_registry_probe([[2]]), repeats=3,
                  evaluate_alerts=False)
    from distributedarrays_tpu.telemetry.summarize import read_journal
    events = read_journal(telemetry_capture.journal_path())
    s = summarize(events)
    assert len(s["tuning"]) == 2          # the advise verdict + the undo
    adv = [t for t in s["tuning"] if t["name"] == "advise"][0]
    assert adv["kernel"] == "rdma_chunks" and adv["key"] == A2A_KEY
    assert adv["status"] == "rolled_back"
    assert adv["old"] == [1] and adv["new"] == [2]
    out = io.StringIO()
    format_summary(s, out)
    text = out.getvalue()
    assert "tuning provenance (advisor writes):" in text
    assert "ROLLED_BACK" in text and A2A_KEY in text


def test_format_results_renders_outcomes(clean_autotune):
    action = _chunk_action(current=[1], proposed=[2])
    results = [dict(action.to_dict(), status="applied",
                    before_s=[0.02], after_s=[0.01])]
    out = io.StringIO()
    advisor.format_results([action], results, out)
    text = out.getvalue()
    assert "APPLIED" in text and A2A_KEY in text
    assert "severity_s=1" in text
    assert "before median 0.02s" in text
    out = io.StringIO()
    advisor.format_results([], None, out)
    assert "no tuning actions" in out.getvalue()


# ---------------------------------------------------------------------------
# CLI surfaces
# ---------------------------------------------------------------------------


def _run_cli(*argv, env=None):
    import os
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    if env:
        e.update(env)
    return subprocess.run(
        [sys.executable, "-m", "distributedarrays_tpu.telemetry", *argv],
        capture_output=True, text=True, cwd=str(REPO), env=e)


@pytest.mark.slow
def test_advise_cli_json(tmp_path):
    journal = tmp_path / "run.jsonl"
    with open(journal, "w") as f:
        for ev in (_rdma_reshard_span(1), _xla_reshard_span(2)):
            f.write(json.dumps(ev) + "\n")
    cache = tmp_path / "cache.json"
    cache.write_text(json.dumps({"rdma_chunks": {A2A_KEY: [1]}}))
    p = _run_cli("advise", str(journal), "--json", "--platform", "cpu",
                 env={"DAT_AUTOTUNE_CACHE": str(cache),
                      "DA_TPU_PEAKS": json.dumps(
                          {"cpu": {k: v for k, v in PEAKS.items()
                                   if k != "platform"}})})
    assert p.returncode == 0, p.stderr
    doc = json.loads(p.stdout)
    kinds = {a["kind"] for a in doc["actions"]}
    assert "rdma_chunks" in kinds and "dispatch" in kinds
    assert doc["results"] is None                    # no --apply
    chunk = [a for a in doc["actions"]
             if a["kind"] == "rdma_chunks"][0]
    # the doubling starts from the chunk depth the span actually ran
    # with (4, off its labels), not the cache entry
    assert chunk["key"] == A2A_KEY and chunk["proposed"] == [8]
    assert chunk["current"] == [1]                   # the cache entry


@pytest.mark.slow
def test_regress_explain_cli(tmp_path):
    base = tmp_path / "BENCH_r1.json"
    fresh = tmp_path / "fresh.json"
    base.write_text(json.dumps(
        {"parsed": {"metric": "gemm_s", "value": 1.0}}))
    fresh.write_text(json.dumps(
        {"metric": "gemm_s", "value": 2.0}))
    p = _run_cli("regress", str(fresh), "--baseline", str(tmp_path),
                 "--explain")
    assert p.returncode == 1                        # regression found
    assert "REGRESSION" in p.stdout
    assert "baseline: median 1" in p.stdout
    assert "lower is better" in p.stdout
    assert "conservative 50% of |median|" in p.stdout


def test_regress_explain_library():
    results = regress.compare({"x_s": 2.0}, {"x_s": [1.0, 1.0, 1.0]})
    assert results[0]["direction"] == "lower_is_better"
    out = io.StringIO()
    regress.format_results(results, out, explain=True)
    assert "max(mad_k*1.4826*MAD, rel_floor*|median|)" in out.getvalue()
