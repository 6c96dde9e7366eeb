"""Performance-observatory suite: roofline classification against peak
tables, overlap-fraction and critical-path math on synthetic
hand-computed span timelines (fully-overlapped, fully-serial,
partial-overlap, multi-rank skew), the doctor CLI round-trip on the
scripted telemetry workload (tools/perf_workload.py — shared with the CI
observability leg), request-scoped trace ids from serve submit to
resolve, the Perfetto counter/flow/rank-track export additions, and the
noise-aware bench regression sentinel (``telemetry regress``)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import distributedarrays_tpu as dat
from distributedarrays_tpu import telemetry as tm
from distributedarrays_tpu.parallel import spmd_mode as S
from distributedarrays_tpu.telemetry import perf, regress
from distributedarrays_tpu.telemetry.export import to_perfetto
from distributedarrays_tpu.telemetry.fixtures import telemetry_capture  # noqa: F401
from distributedarrays_tpu.telemetry.summarize import read_journal

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# peak tables
# ---------------------------------------------------------------------------


def test_peak_table_defaults_and_aliases():
    assert perf.peaks_for("v5e")["flops"] == pytest.approx(197e12)
    assert perf.peaks_for("TPU v5 lite")["platform"] == "tpu-v5e"
    assert perf.peaks_for("v5p")["hbm"] == pytest.approx(2765e9)
    assert perf.peaks_for(None)["platform"] == "cpu"
    assert perf.peaks_for("some-unknown-chip")["platform"] == "cpu"


def test_peak_table_env_override_inline(monkeypatch):
    monkeypatch.setenv("DA_TPU_PEAKS", '{"cpu": {"flops": 123.0}}')
    p = perf.peaks_for("cpu")
    assert p["flops"] == 123.0
    assert p["hbm"] == perf.DEFAULT_PEAKS["cpu"]["hbm"]  # merged, not replaced
    # flat form applies to the selected platform
    monkeypatch.setenv("DA_TPU_PEAKS", '{"ici": 7.0}')
    assert perf.peaks_for("v5e")["ici"] == 7.0


def test_peak_table_env_override_path(monkeypatch, tmp_path):
    f = tmp_path / "peaks.json"
    f.write_text(json.dumps({"tpu-v5p": {"flops": 5.0}}))
    monkeypatch.setenv("DA_TPU_PEAKS", str(f))
    assert perf.peaks_for("v5p")["flops"] == 5.0
    # garbage env degrades to defaults, never raises
    monkeypatch.setenv("DA_TPU_PEAKS", "not json and not a path")
    assert perf.peaks_for("v5e")["flops"] == pytest.approx(197e12)


def test_cost_helpers():
    g = perf.gemm_cost(4, 5, 6, 2, out_itemsize=4)
    assert g["flops"] == 2 * 4 * 5 * 6
    assert g["bytes_hbm"] == (4 * 6 + 6 * 5) * 2 + 4 * 5 * 4
    a = perf.attention_cost(8, 2, 4, 4, p=4, causal=True)
    assert a["flops"] == 4 * 8 * 8 * 2 * 4 // 2
    assert a["bytes_ici"] == 3 * 2 * 8 * 2 * 4 * 4
    assert perf.reshard_cost(100, 30) == {
        "flops": 0, "bytes_hbm": 200, "bytes_ici": 30}


# ---------------------------------------------------------------------------
# synthetic span timelines
# ---------------------------------------------------------------------------


def _sp(sid, name, start, dur, parent=None, labels=None, tid=1):
    return {"cat": "span", "name": name, "span_id": sid,
            "parent_id": parent, "start": float(start),
            "dur": float(dur), "tid": tid,
            "labels": dict(labels or {})}


def test_classify_bound_classes():
    peaks = {"flops": 100.0, "hbm": 100.0, "ici": 100.0, "platform": "t"}
    evs = [
        _sp(1, "compute", 0, 1.0, labels={"flops": 90, "bytes_hbm": 10}),
        _sp(2, "hbm", 0, 1.0, labels={"flops": 10, "bytes_hbm": 80}),
        _sp(3, "ici", 0, 1.0, labels={"bytes_ici": 50}),
        _sp(4, "unstamped", 0, 1.0),
    ]
    out = {o["name"]: o for o in perf.classify(evs, peaks)}
    assert set(out) == {"compute", "hbm", "ici"}
    assert out["compute"]["bound"] == "compute"
    assert out["compute"]["roofline_frac"] == pytest.approx(0.9)
    assert out["hbm"]["bound"] == "hbm"
    assert out["ici"]["bound"] == "ici"
    assert out["ici"]["roofline_frac"] == pytest.approx(0.5)


def test_coverage_hand_computed():
    evs = [
        _sp(1, "root_unstamped", 0, 10.0),
        _sp(2, "stamped_child", 0, 9.0, parent=1,
            labels={"bytes_hbm": 1}),
        _sp(3, "stamped_root", 20, 5.0, labels={"flops": 1}),
    ]
    cov = perf.coverage(evs)
    assert cov["wall_s"] == pytest.approx(15.0)
    assert cov["attributed_s"] == pytest.approx(14.0)
    assert cov["fraction"] == pytest.approx(14 / 15, abs=1e-3)


def test_interval_overlap_cases():
    # fully overlapped
    full = perf.interval_overlap([(0, 4)], [(0, 6)])
    assert full["overlap_frac"] == pytest.approx(1.0)
    # fully serial
    serial = perf.interval_overlap([(0, 4)], [(4, 8)])
    assert serial["overlap_frac"] == pytest.approx(0.0)
    assert serial["unoverlapped_s"] == pytest.approx(4.0)
    # partial: comm [0,4], compute [2,8] -> 2 of 4 hidden
    part = perf.interval_overlap([(0, 4)], [(2, 8)])
    assert part["overlap_frac"] == pytest.approx(0.5)
    # multi-rank skew: comm on two ranks [0,2]+[1,3] (union [0,3]),
    # compute [2,5]+[3,6] (union [2,6]) -> hidden [2,3] = 1 of 3
    skew = perf.interval_overlap([(0, 2), (1, 3)], [(2, 5), (3, 6)])
    assert skew["comm_s"] == pytest.approx(3.0)
    assert skew["overlapped_s"] == pytest.approx(1.0)
    assert skew["overlap_frac"] == pytest.approx(1 / 3, abs=1e-3)


def test_timeline_overlap_groups_by_parent():
    evs = [
        _sp(1, "step", 0, 10.0),
        _sp(2, "send", 0, 4.0, parent=1, labels={"bytes_ici": 10}),
        _sp(3, "dot", 2, 6.0, parent=1, labels={"flops": 10}, tid=2),
    ]
    out = perf.timeline_overlap(evs)
    assert len(out) == 1
    assert out[0]["step"] == "step"
    assert out[0]["overlap_frac"] == pytest.approx(0.5)
    # explicit kind label overrides the stamp heuristic
    evs[2]["labels"] = {"kind": "compute"}
    assert perf.timeline_overlap(evs)[0]["overlap_frac"] == \
        pytest.approx(0.5)


def test_train_step_overlap_pinned_timeline():
    # hand-computed per-training-step grad-sync overlap: two train.step
    # parents, each with a compute (train.grad) and a comm (train.sync)
    # child.  Step 0: sync [4,8] vs grad [0,6] -> 2 of 4 hidden = 0.5.
    # Step 1: fully serial -> 0.0.  A non-train parent with the same
    # shape is ignored.
    evs = [
        _sp(1, "train.step", 0, 10.0,
            labels={"step": 0, "ranks": 8, "dispatch": "xla"}),
        _sp(2, "train.grad", 0, 6.0, parent=1,
            labels={"kind": "compute", "step": 0}),
        _sp(3, "train.sync", 4, 4.0, parent=1,
            labels={"kind": "comm", "step": 0}, tid=2),
        _sp(4, "train.step", 20, 10.0,
            labels={"step": 1, "ranks": 8, "dispatch": "xla"}),
        _sp(5, "train.grad", 20, 5.0, parent=4,
            labels={"kind": "compute", "step": 1}),
        _sp(6, "train.sync", 25, 3.0, parent=4,
            labels={"kind": "comm", "step": 1}),
        _sp(7, "other.step", 40, 10.0),
        _sp(8, "sync", 40, 4.0, parent=7, labels={"kind": "comm"}),
    ]
    out = perf.train_step_overlap(evs)
    assert [o["step"] for o in out] == [0, 1]
    assert out[0]["overlap_frac"] == pytest.approx(0.5)
    assert out[0]["comm_s"] == pytest.approx(4.0)
    assert out[0]["ranks"] == 8 and out[0]["dispatch"] == "xla"
    assert out[1]["overlap_frac"] == pytest.approx(0.0)
    assert out[1]["unoverlapped_s"] == pytest.approx(3.0)
    # analyze() surfaces the same numbers under "train_steps" and the
    # doctor rendering prints the per-step section
    a = perf.analyze(evs, peaks={"flops": 1.0, "hbm": 1.0, "ici": 1.0,
                                 "platform": "t"})
    assert [o["step"] for o in a["train_steps"]] == [0, 1]
    import io
    buf = io.StringIO()
    perf.format_analysis(a, buf)
    text = buf.getvalue()
    assert "grad-sync overlap per training step" in text
    assert "step 0" in text and "step 1" in text


def test_overlap_stats_model_tier():
    peaks = {"flops": 100.0, "hbm": 1e12, "ici": 100.0, "platform": "t"}
    labels = {"flops": 100, "bytes_ici": 100, "ranks": 5}
    # t_comm = t_work = 1.0.  Fully serial: dur = 2.0
    serial = perf.overlap_stats(_sp(1, "ring", 0, 2.0, labels=labels),
                                peaks)
    assert serial["overlap_frac"] == pytest.approx(0.0)
    assert serial["unoverlapped_s"] == pytest.approx(1.0)
    assert serial["steps"] == 4
    assert serial["per_step"]["unoverlapped_s"] == pytest.approx(0.25)
    # fully overlapped: dur = max(t_comm, t_work) = 1.0
    full = perf.overlap_stats(_sp(2, "ring", 0, 1.0, labels=labels),
                              peaks)
    assert full["overlap_frac"] == pytest.approx(1.0)
    assert full["unoverlapped_s"] == pytest.approx(0.0)
    # halfway: dur = 1.5
    half = perf.overlap_stats(_sp(3, "ring", 0, 1.5, labels=labels),
                              peaks)
    assert half["overlap_frac"] == pytest.approx(0.5)
    # no comm -> no entry
    assert perf.overlap_stats(
        _sp(4, "x", 0, 1.0, labels={"flops": 5}), peaks) is None


def test_critical_path_hand_computed():
    evs = [
        _sp(1, "root", 0, 10.0),
        _sp(2, "A", 0, 4.0, parent=1),
        _sp(3, "B", 5, 4.0, parent=1),
        _sp(4, "C", 6, 2.0, parent=3),
    ]
    path = perf.critical_path(evs)
    # timeline order: A 4s, root gap 1s, B 1s, C 2s, B 1s, root tail 1s
    assert [(s["name"], pytest.approx(s["self_s"])) for s in path] == [
        ("A", 4.0), ("root", 1.0), ("B", 1.0), ("C", 2.0), ("B", 1.0),
        ("root", 1.0)]
    assert sum(s["self_s"] for s in path) == pytest.approx(10.0)


def test_analyze_findings_ranked():
    peaks = {"flops": 100.0, "hbm": 1e12, "ici": 100.0, "platform": "t"}
    evs = [
        _sp(1, "ring", 0, 2.0,
            labels={"flops": 100, "bytes_ici": 100, "ranks": 3}),
        _sp(2, "fast", 0, 0.001, labels={"flops": 0.09}),
    ]
    a = perf.analyze(evs, peaks)
    assert a["findings"], "expected at least one finding"
    kinds = {f["kind"] for f in a["findings"]}
    assert "unoverlapped_comm" in kinds
    sev = [f["severity_s"] for f in a["findings"]]
    assert sev == sorted(sev, reverse=True)


# ---------------------------------------------------------------------------
# the doctor CLI round-trip on the scripted workload
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def workload_journal(tmp_path_factory):
    jpath = tmp_path_factory.mktemp("perf") / "journal.jsonl"
    r = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_workload.py"),
         str(jpath)],
        cwd=str(REPO), capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "DA_TPU_TELEMETRY": "1"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "perf-workload-ok" in r.stdout
    return jpath


def _doctor(jpath, *args):
    return subprocess.run(
        [sys.executable, "-m", "distributedarrays_tpu.telemetry",
         "doctor", str(jpath), *args],
        cwd=str(REPO), capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_doctor_cli_acceptance(workload_journal):
    r = _doctor(workload_journal, "--json", "--min-findings", "1")
    assert r.returncode == 0, r.stderr[-2000:]
    a = json.loads(r.stdout)
    # >= 90% of span wall time is cost-classified
    assert a["coverage"]["fraction"] >= 0.9, a["coverage"]
    # a per-step overlap fraction for the RDMA-armed (interpret) reshard
    # AND its XLA twin
    resh = {o["dispatch"]: o for o in a["overlap"]
            if o["name"] == "reshard" and o.get("dispatch")}
    assert {"rdma", "xla"} <= set(resh), list(a["overlap"])
    for o in resh.values():
        assert "overlap_frac" in o and "per_step" in o and o["steps"] >= 1
    assert len(a["findings"]) >= 1
    # human rendering mentions the essentials
    r2 = _doctor(workload_journal)
    assert r2.returncode == 0
    assert "coverage:" in r2.stdout and "roofline" in r2.stdout
    assert "reshard" in r2.stdout


def test_doctor_min_findings_gate(workload_journal):
    r = _doctor(workload_journal, "--min-findings", "10000")
    assert r.returncode == 2
    assert "finding" in r.stderr


def test_workload_trace_ids_submit_to_resolve(workload_journal):
    journal = read_journal(str(workload_journal))
    spans = [e for e in journal if e.get("cat") == "span"]
    submits = [s for s in spans if s["name"] == "serve.submit"]
    assert submits, "no serve.submit spans in the journal"
    for sub in submits:
        tids = sub.get("trace_id") or []
        assert len(tids) == 1, sub
        tid = tids[0]
        carrying = {s["name"] for s in spans
                    if tid in (s.get("trace_id") or [])}
        # every stage of the journey carries the id: submit, the batch
        # dispatch, the resolve, and the SPMD rank steps under it
        assert {"serve.submit", "serve.dispatch", "serve.resolve",
                "spmd.run", "spmd.step"} <= carrying, (tid, carrying)


def test_workload_perfetto_counters_flows_ranktracks(workload_journal):
    journal = read_journal(str(workload_journal))
    t = to_perfetto(journal)["traceEvents"]
    counters = {e["name"] for e in t if e["ph"] == "C"}
    assert "serve.queue_depth" in counters
    assert any(c.startswith("serve.tokens") for c in counters), counters
    # flows: at least one request chains >= 2 spans with s .. f phases
    flows = [e for e in t if e.get("cat") == "trace"]
    assert {"s", "f"} <= {e["ph"] for e in flows}
    # rank-labeled spans land on synthetic per-rank tracks with names
    names = {e["args"]["name"] for e in t if e["ph"] == "M"}
    assert {"rank 0", "rank 1"} <= names, names
    rank_tids = {e["tid"] for e in t
                 if e["ph"] == "X"
                 and str((e.get("args") or {}).get("rank")) in ("0", "1")}
    assert len(rank_tids) >= 2


# ---------------------------------------------------------------------------
# serve trace ids + SLO histograms (in-process)
# ---------------------------------------------------------------------------


def test_serve_trace_id_on_every_span_and_slo(telemetry_capture):
    from distributedarrays_tpu.serve import Server, ServeConfig
    srv = Server(ServeConfig(max_batch=2, flush_s=0.002))

    def ep(payloads):
        return [sum(S.spmd(lambda: S.myid(), pids=[0, 1]))
                + float(np.sum(p)) for p in payloads]

    srv.register("echo", ep)
    fut = srv.submit("echo", np.ones((2, 2), dtype=np.float32))
    assert fut.result(timeout=30) == pytest.approx(5.0)
    srv.close()
    spans = telemetry_capture.spans()
    sub = [s for s in spans if s["name"] == "serve.submit"][0]
    tid = sub["trace_id"][0]
    assert tid.startswith("req-")
    for name in ("serve.submit", "serve.dispatch", "serve.resolve",
                 "spmd.run"):
        got = [s for s in spans if s["name"] == name
               and tid in (s.get("trace_id") or [])]
        assert got, (name, tid)
    steps = [s for s in spans if s["name"] == "spmd.step"
             and tid in (s.get("trace_id") or [])]
    assert {s["labels"]["rank"] for s in steps} == {0, 1}
    # caller-supplied trace ids propagate verbatim
    fut = srv = None
    # SLO histogram in the report and the Prometheus export
    rep = telemetry_capture.report()
    slo = [k for k in rep["histograms"] if k.startswith("serve.slo")]
    assert slo and "buckets" in rep["histograms"][slo[0]]
    prom = telemetry_capture.to_prometheus()
    lines = [ln for ln in prom.splitlines()
             if ln.startswith("da_tpu_serve_slo_request_s_bucket")]
    assert lines, prom[:2000]
    assert any('le="+Inf"' in ln for ln in lines)
    # cumulative: +Inf equals _count
    inf = next(ln for ln in lines if 'le="+Inf"' in ln)
    count_ln = next(ln for ln in prom.splitlines()
                    if ln.startswith("da_tpu_serve_slo_request_s_count"))
    assert inf.rsplit(" ", 1)[1] == count_ln.rsplit(" ", 1)[1]
    dat.d_closeall()


def test_serve_caller_supplied_trace_id(telemetry_capture):
    from distributedarrays_tpu.serve import Server, ServeConfig
    srv = Server(ServeConfig(max_batch=1, flush_s=0.0))
    srv.register("e", lambda ps: [0 for _ in ps])
    fut = srv.submit("e", 1, trace_id="my-trace-42")
    fut.result(timeout=30)
    srv.close()
    d = [s for s in telemetry_capture.spans("serve.dispatch")
         if "my-trace-42" in (s.get("trace_id") or [])]
    assert d


def test_spmd_process_backend_rank_spans(telemetry_capture):
    if not hasattr(os, "fork"):
        pytest.skip("needs POSIX fork")
    S.spmd(lambda: 7, pids=[0, 1], backend="process")
    steps = [s for s in telemetry_capture.spans("spmd.step")
             if (s.get("labels") or {}).get("backend") == "process"]
    assert {s["labels"]["rank"] for s in steps} == {0, 1}
    for s in steps:
        assert s["dur"] is not None and s["dur"] >= 0


def test_elastic_gauge_counter_track(telemetry_capture):
    from distributedarrays_tpu.resilience import elastic
    m = elastic.manager()
    m.reset()
    m.probe()
    journal = read_journal(telemetry_capture.journal_path())
    gauges = [e for e in journal if e.get("cat") == "gauge"
              and e.get("name") == "elastic.live_devices"]
    assert gauges, [e.get("name") for e in journal]
    t = to_perfetto(journal)["traceEvents"]
    assert any(e["ph"] == "C" and e["name"] == "elastic.live_devices"
               for e in t)
    m.reset()


# ---------------------------------------------------------------------------
# the regression sentinel
# ---------------------------------------------------------------------------


def test_regress_direction_inference():
    assert regress.direction("gemm_4096_mixed_bf16pass_s_per_iter") == -1
    assert regress.direction("serve_load_p99_s") == -1
    assert regress.direction("gemm_4096_mixed_bf16pass_gflops") == 1
    assert regress.direction("sp_train_tokens_per_s") == 1
    # the banked headline metric carries its unit MID-name — the token
    # fallback must judge it, or the sentinel never guards the one row
    # the trajectory actually banks
    assert regress.direction("gemm_4096_gflops_mixed_precision_bf16pass") == 1
    # ... but an anchored suffix still wins over a mid-name token
    assert regress.direction("gemm_gflops_probe_s") == -1
    assert regress.direction("flash_attn_d128_tuned_block") == 0
    assert regress.direction("reshard_even_comm_bytes_est") == 0
    assert regress.direction("something_unknowable") == 0
    # solver rows: iteration counts and final residuals are
    # lower-is-better (a regressed preconditioner shows up as MORE
    # iterations at the same tolerance, not slower ones)
    assert regress.direction("cg_poisson_iters") == -1
    assert regress.direction("mgcg_iterations") == -1
    assert regress.direction("cg_poisson_residual") == -1
    assert regress.direction("cg_poisson_gbps") == 1


def test_regress_replay_detection():
    assert regress.is_replay({"replayed": True})
    assert regress.is_replay(
        {"note": "replayed from the banked table measured ..."})
    assert not regress.is_replay({"note": "fresh", "value": 1.0})


def test_regress_compare_noise_aware():
    baseline = {"x_gflops": [100.0, 103.0, 98.0, 101.0]}
    ok = regress.compare({"x_gflops": 97.0}, baseline)
    assert ok[0]["status"] == "ok"
    bad = regress.compare({"x_gflops": 50.0}, baseline)
    assert bad[0]["status"] == "regression"
    up = regress.compare({"x_gflops": 200.0}, baseline)
    assert up[0]["status"] == "improved"
    # lower-better metric: a 2x slowdown flags
    lb = {"y_s": [1.0, 1.02, 0.99]}
    assert regress.compare({"y_s": 2.0}, lb)[0]["status"] == "regression"
    assert regress.compare({"y_s": 1.05}, lb)[0]["status"] == "ok"
    # with < min_points the threshold is the conservative 50%
    two = regress.compare({"y_s": 2.1}, {"y_s": [1.0, 1.01]})
    assert two[0]["status"] == "regression"
    assert regress.compare({"y_s": 1.4},
                           {"y_s": [1.0, 1.01]})[0]["status"] == "ok"


def _fixture_trajectory(d: Path, values, metric="gemm_4096_gflops"):
    for i, v in enumerate(values, start=1):
        (d / f"BENCH_r{i:02d}.json").write_text(json.dumps(
            {"n": i, "parsed": {"metric": metric, "value": v,
                                "unit": "GFLOPS"}}))


def test_regress_baseline_excludes_replays_and_errors(tmp_path):
    _fixture_trajectory(tmp_path, [100.0, 102.0, 99.0])
    # a replayed round and an errored round must not enter the series
    (tmp_path / "BENCH_r04.json").write_text(json.dumps(
        {"n": 4, "parsed": {"metric": "gemm_4096_gflops", "value": 55.0,
                            "replayed": True, "note": "replayed from the "
                            "banked table measured x"}}))
    (tmp_path / "BENCH_r05.json").write_text(json.dumps(
        {"n": 5, "parsed": {"metric": "gemm_4096_gflops", "value": 0.0,
                            "error": "accelerator unreachable"}}))
    series = regress.load_baseline([str(tmp_path)])
    assert series["gemm_4096_gflops"] == [100.0, 102.0, 99.0]


def _regress_cli(fresh, baseline_dir, *args):
    return subprocess.run(
        [sys.executable, "-m", "distributedarrays_tpu.telemetry",
         "regress", str(fresh), "--baseline", str(baseline_dir), *args],
        cwd=str(REPO), capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_regress_cli_green_and_2x_slowdown(tmp_path):
    # a lower-is-better trajectory with ~2% noise
    _fixture_trajectory(tmp_path, [1.00, 1.02, 0.99, 1.01],
                        metric="gemm_4096_mixed_bf16pass_s_per_iter")
    ok = tmp_path / "fresh_ok.json"
    ok.write_text(json.dumps(
        {"metric": "gemm_4096_mixed_bf16pass_s_per_iter", "value": 1.03}))
    r = _regress_cli(ok, tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
    # the injected 2x slowdown flags and exits 1
    bad = tmp_path / "fresh_bad.json"
    bad.write_text(json.dumps(
        {"metric": "gemm_4096_mixed_bf16pass_s_per_iter", "value": 2.0}))
    r = _regress_cli(bad, tmp_path)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout


def test_regress_cli_replay_and_strict(tmp_path):
    _fixture_trajectory(tmp_path, [100.0, 101.0, 99.0])
    replay = tmp_path / "fresh_replay.json"
    replay.write_text(json.dumps(
        {"metric": "gemm_4096_gflops", "value": 60.0, "replayed": True}))
    r = _regress_cli(replay, tmp_path)
    assert r.returncode == 0 and "SKIPPED" in r.stdout
    r = _regress_cli(replay, tmp_path, "--strict")
    assert r.returncode == 2
    # a details-table fresh input with no matching baseline judges
    # nothing: rc 0 by default, 2 under --strict
    lonely = tmp_path / "fresh_lonely.json"
    lonely.write_text(json.dumps({"unrelated_metric_gflops": 5.0}))
    assert _regress_cli(lonely, tmp_path).returncode == 0
    assert _regress_cli(lonely, tmp_path, "--strict").returncode == 2


def test_annotate_and_trace_ctx_disabled_are_silent(tmp_path):
    code = (
        "import distributedarrays_tpu.telemetry as tm\n"
        "tm.annotate(flops=1)\n"
        "with tm.trace_ctx('x') as ids:\n"
        "    assert ids is None\n"
        "    with tm.span('s', flops=1) as sp:\n"
        "        assert sp is None\n"
        "assert tm.current_trace_ids() == ()\n"
        "assert tm.report()['spans']['finished'] == 0\n"
        "print('SILENT-OK')\n")
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=str(REPO), capture_output=True,
        text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "DA_TPU_TELEMETRY": "0"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SILENT-OK" in r.stdout
