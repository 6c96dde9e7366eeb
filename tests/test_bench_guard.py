"""Pin bench.py's banked-result protection semantics (round 5).

These rules are what make BENCH_DETAILS.json trustworthy as a master
table accumulated across invocations: a later run's failure or deadline
skip must never mask a result measured in a real silicon window, and a
success must clear every stale failure marker.  The bench harness is the
round's evidence pipeline, so its semantics get the same pinning as the
library.
"""

import importlib.util
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture()
def bench(monkeypatch):
    spec = importlib.util.spec_from_file_location("bench_for_guard_tests",
                                                  REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # NEVER let a test write the repo's master table
    monkeypatch.setattr(mod, "_save", lambda d: None)
    monkeypatch.setattr(mod, "_ONLY", set())
    return mod


def test_expired_budget_keeps_banked_entry(bench):
    bench._GLOBAL_BUDGET_S = 0.0
    d = {"sort_1e7_s": 1.23}
    bench._guarded(d, "sort", lambda: {"sort_1e7_s": 9.9})
    assert d == {"sort_1e7_s": 1.23}


def test_expired_budget_marks_unbanked_label(bench):
    bench._GLOBAL_BUDGET_S = 0.0
    d = {}
    bench._guarded(d, "mapreduce", lambda: {})
    assert d.get("mapreduce_error") == "skipped (global bench deadline)"


def test_failure_next_to_banked_result_goes_to_rerun_error(bench):
    bench._GLOBAL_BUDGET_S = 1e9
    d = {"sort_1e7_s": 1.23}
    bench._guarded(d, "sort",
                   lambda: (_ for _ in ()).throw(ValueError("boom")))
    assert d["sort_1e7_s"] == 1.23
    assert "boom" in d["sort_rerun_error"]
    assert "sort_error" not in d


def test_failure_with_no_banked_result_is_plain_error(bench):
    bench._GLOBAL_BUDGET_S = 1e9
    d = {}
    bench._guarded(d, "sort",
                   lambda: (_ for _ in ()).throw(ValueError("boom")))
    assert "boom" in d["sort_error"]


def test_stale_markers_cleared_at_execution_even_on_refailure(bench):
    # markers are cleared when the label EXECUTES (not at seed time, so
    # unreached labels keep their failure evidence); a re-failure then
    # records the fresh error, never the stale one
    bench._GLOBAL_BUDGET_S = 1e9
    d = {"sort_1e7_s": 1.0, "sort_rerun_error": "old"}
    bench._guarded(d, "sort",
                   lambda: (_ for _ in ()).throw(ValueError("fresh")))
    assert "fresh" in d["sort_rerun_error"]
    d2 = {"sort_error": "old"}
    bench._guarded(d2, "sort",
                   lambda: (_ for _ in ()).throw(ValueError("fresh")))
    assert "fresh" in d2["sort_error"]


def test_success_pops_every_stale_marker(bench):
    bench._GLOBAL_BUDGET_S = 1e9
    d = {"sort_error": "old", "sort_rerun_error": "old",
         "sort_orphan_running": True}
    bench._guarded(d, "sort", lambda: {"sort_1e7_s": 4.5})
    assert d["sort_1e7_s"] == 4.5
    assert not any(k.endswith(("_error", "_orphan_running")) for k in d), d


def test_success_banks_comm_bytes_column(bench):
    # every successful config banks its telemetry comms-bytes delta
    bench._GLOBAL_BUDGET_S = 1e9
    d = {}

    def cfg():
        from distributedarrays_tpu import telemetry
        if telemetry.enabled():
            telemetry.record_comm("reshard", 4096, op="benchtest",
                                  journal=False)
        return {"sort_1e7_s": 4.5}

    bench._guarded(d, "sort", cfg)
    assert d["sort_1e7_s"] == 4.5
    from distributedarrays_tpu import telemetry
    want = 4096 if telemetry.enabled() else 0
    assert d["sort_comm_bytes_est"] == want


def test_failure_banks_no_comm_bytes_column(bench):
    bench._GLOBAL_BUDGET_S = 1e9
    d = {}
    bench._guarded(d, "sort",
                   lambda: (_ for _ in ()).throw(ValueError("boom")))
    assert "sort_comm_bytes_est" not in d


def test_banked_in_handles_dynamic_gemm16k_labels(bench):
    # the one dynamic label family is grid-tagged; its sentinel is
    # derived, not listed (multi-chip runs tag e.g. gemm_16k_2x2)
    d = {"gemm_16k_2x2_bf16pass_gflops": 1.0,
         "gemm_16k_2x2_f32_highest_gflops": 1.0}
    assert bench._banked_in(d, "gemm_16k_2x2")
    assert bench._banked_in(d, "gemm_16k_2x2_f32_highest")
    assert not bench._banked_in(d, "gemm_16k_4x1")
    d["gemm_16k_2x2_error"] = "boom"
    assert not bench._banked_in(d, "gemm_16k_2x2")


def test_error_label_is_not_banked(bench):
    d = {"sort_1e7_s": 1.0, "sort_error": "boom"}
    assert not bench._banked_in(d, "sort")
    # a rerun failure does NOT unbank (the earlier result stays trusted)
    d2 = {"sort_1e7_s": 1.0, "sort_rerun_error": "boom"}
    assert bench._banked_in(d2, "sort")


# ---------------------------------------------------------------------------
# partial-row banking
# ---------------------------------------------------------------------------


def test_timeout_banks_published_partials_flagged(bench):
    # a config that published metrics mid-run, then timed out: the
    # completed metrics land in the row flagged {label}_partial, and the
    # flag keeps the label un-banked so the next window re-attempts it
    bench._GLOBAL_BUDGET_S = 1e9
    d = {}

    def cfg():
        bench.bank_partial("sort", sort_1e7_s=1.5, sort_iters=42)
        import time
        time.sleep(1)
        return {"sort_1e7_s": 9.9}

    bench._guarded(d, "sort", cfg, timeout_s=0.3)
    assert "timed out" in d["sort_error"]
    assert d["sort_1e7_s"] == 1.5 and d["sort_iters"] == 42
    assert d["sort_partial"] is True
    assert not bench._banked_in(d, "sort")


def test_exception_banks_published_partials_flagged(bench):
    bench._GLOBAL_BUDGET_S = 1e9
    d = {}

    def cfg():
        bench.bank_partial("sort", sort_iters=17)
        raise ValueError("died after the iteration count")

    bench._guarded(d, "sort", cfg)
    assert "died after" in d["sort_error"]
    assert d["sort_iters"] == 17 and d["sort_partial"] is True


def test_full_success_supersedes_partial_row(bench):
    # a later complete run clears the partial flag with the other stale
    # markers and the label counts as banked again
    bench._GLOBAL_BUDGET_S = 1e9
    d = {"sort_1e7_s": 1.5, "sort_partial": True, "sort_error": "old"}
    assert not bench._banked_in(d, "sort")
    bench._guarded(d, "sort", lambda: {"sort_1e7_s": 4.5})
    assert d["sort_1e7_s"] == 4.5
    assert "sort_partial" not in d and "sort_error" not in d
    assert bench._banked_in(d, "sort")


def test_stale_partials_dropped_at_execution(bench):
    # publications left over from an earlier attempt never leak into a
    # fresh run's row (success path shown; _guarded drops them on entry)
    bench._GLOBAL_BUDGET_S = 1e9
    bench.bank_partial("sort", sort_iters=99)
    d = {}
    bench._guarded(d, "sort", lambda: {"sort_1e7_s": 2.0})
    assert d["sort_1e7_s"] == 2.0
    assert "sort_iters" not in d and "sort_partial" not in d
