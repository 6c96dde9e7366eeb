"""Request-scoped trace ids from serve submit to resolve, the Perfetto
counter/flow/rank-track export, rank spans from the process backend, and
the disabled-is-silent contract — on the scripted telemetry workload
(tools/perf_workload.py, shared with the CI observability leg) and
in-process."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import distributedarrays_tpu as dat
from distributedarrays_tpu.parallel import spmd_mode as S
from distributedarrays_tpu.telemetry.export import to_perfetto
from distributedarrays_tpu.telemetry.fixtures import telemetry_capture  # noqa: F401
from distributedarrays_tpu.telemetry.summarize import read_journal

REPO = Path(__file__).resolve().parents[1]


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


def test_annotate_and_trace_ctx_disabled_are_silent(tmp_path):
    code = (
        "import distributedarrays_tpu.telemetry as tm\n"
        "tm.annotate(shape=1)\n"
        "with tm.trace_ctx('x') as ids:\n"
        "    assert ids is None\n"
        "    with tm.span('s', shape=1) as sp:\n"
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
