#!/usr/bin/env python
"""Benchmark harness: BASELINE.json configs on the available TPU devices.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Headline metric (from BASELINE.json configs[0]): GFLOPS on a 4096x4096
DArray GEMM through the framework (`djit` + `@`) at the TPU-native DEFAULT
precision (mixed bf16-pass matmul — labeled as such in the metric name);
the true-float32 (precision=HIGHEST) number is measured separately at the
end of the run and recorded in BENCH_DETAILS.json.  ``vs_baseline`` is the
speedup over the same GEMM in numpy (float32, multi-threaded host BLAS) —
a strictly-stronger stand-in for the reference's "4 CPU workers" config
(the reference's Julia Distributed GEMM over 4 local TCP workers cannot
beat the host's full BLAS).

Methodology.  Every timing chains L iterations of the op inside ONE
compiled ``lax.scan`` (data-dependent so XLA cannot hoist or elide), forces
completion with a scalar fetch, and reports DIRECT per-iteration cost
``t(L) / L`` with L grown until one call takes >= ~1.2 s — bounded by
physics: one call's wall time >= the device compute it contains, so derived
TFLOPS cannot exceed the chip's peak.  The marginal estimate
``t(L+1) - t(1)`` is recorded per entry as a cross-check diagnostic only,
and every TFLOPS entry carries its MFU against the chip's known bf16 peak;
any entry above peak is flagged ``_IMPOSSIBLE_above_peak``.  (The benchmark
PR of ROADMAP Queue 1 item 1 replaces this with host-clock timing around
``block_until_ready`` and a cell table.)

The run needs a TPU: with none it prints ``"ok": false`` and exits non-zero
(``DAT_BENCH_PLATFORM=cpu`` runs the harness on the host CPU, for testing
the harness logic only).  One process: nothing here starts a child that
imports JAX.  ``main`` exits non-zero when any row it ran failed.
"""

import functools
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

_HEADLINE_METRIC = "gemm_4096_gflops_mixed_precision_bf16pass"


def _t(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _periter(run_for_length, L0=8, target_s=1.2, max_L=4096):
    """Direct per-iteration cost: grow L until ONE compiled scan-chain call
    takes >= ``target_s`` (so dispatch latency is amortized below ~5%),
    then return ``(t(L)/L, L)``.  Each new L costs a compile, so L grows
    in as few steps as possible (estimate from the last timing).
    Physically bounded: wall time of one call >= its device compute."""
    L = L0
    while True:
        t = run_for_length(L)
        if t >= target_s or L >= max_L:
            return t / L, L
        est = max(t / L, 1e-7)                  # upper bound incl. dispatch
        L = min(max_L, max(L * 2, int(1.4 * target_s / est) + 1))


def _marginal(run_for_length, L0=10, min_delta=0.05, max_L=1000):
    """Marginal per-iteration cost t(L+1)-t(1) / L — round-2 methodology,
    kept ONLY as a cross-check diagnostic (see module docstring)."""
    t1 = run_for_length(1)
    L = L0
    while True:
        tL = run_for_length(L + 1)
        delta = tL - t1
        if delta >= min_delta or L >= max_L:
            return max(delta, 1e-9) / L
        L *= 4


# Dense bf16 peak TFLOPS per chip, for MFU and impossibility flags.
# Sources: public TPU spec sheets (v5e 197, v4 275, v5p 459, v6e 918).
_PEAKS_BF16 = [("v6 lite", 918.0), ("v6e", 918.0), ("v5 lite", 197.0),
               ("v5e", 197.0), ("v5p", 459.0), ("v5", 459.0),
               ("v4", 275.0), ("v3", 123.0), ("v2", 45.0)]

# Int8 peak TOPS: 2x bf16 on the e/lite chips (v5e 394, v6e 1836); the
# p-class and older chips run int8 at the bf16 rate (no doubling).
_PEAKS_INT8 = [("v6 lite", 1836.0), ("v6e", 1836.0), ("v5 lite", 394.0),
               ("v5e", 394.0), ("v5p", 459.0), ("v5", 459.0),
               ("v4", 275.0), ("v3", 123.0), ("v2", 45.0)]


def _chip_peak_tflops(device, table=_PEAKS_BF16):
    """Peak for ``device`` from ``table``.  A TPU the table does not know
    is an error, not a row without its utilization; only a non-TPU
    harness run (``DAT_BENCH_PLATFORM=cpu``) gets ``None``."""
    dk = device.device_kind.lower()
    for frag, peak in table:
        if frag in dk:
            return peak
    if device.platform == "tpu":
        raise RuntimeError(
            f"no peak known for TPU device_kind {device.device_kind!r}: "
            f"add it to bench.py's peak tables")
    return None


def _bank_tflops(details, name, tflops, peak, unit="tflops"):
    """Record a TFLOPS (or, with ``unit="tops"``, integer TOPS) entry with
    its MFU; flag physically impossible values instead of publishing them
    silently.  The flag is a per-entry key (not a shared list) so configs
    merged via ``details.update`` cannot clobber each other's flags."""
    details[name + "_" + unit] = tflops
    if peak:
        details[name + "_mfu"] = round(tflops / peak, 4)
        if tflops > peak:
            details[name + "_IMPOSSIBLE_above_peak"] = True


def _run_with_timeout(fn, timeout_s: float, grace_s: float = 0.0):
    """Run ``fn`` on a daemon thread with a hard timeout.  Returns
    ``(finished, value_or_exception, thread)``."""
    import threading

    box = {}

    def runner():
        try:
            box["value"] = fn()
        except Exception as e:
            box["error"] = e

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive() and grace_s:
        t.join(grace_s)
    if t.is_alive():
        return False, None, t
    if "error" in box:
        return True, box["error"], t
    return True, box.get("value"), t


# DAT_BENCH_PLATFORM=cpu runs the whole harness on host CPU — for testing
# the harness logic itself.
_PLATFORM = os.environ.get("DAT_BENCH_PLATFORM")


def _save(details):
    Path(__file__).with_name("BENCH_DETAILS.json").write_text(
        json.dumps(details, indent=2))


def _collapse_provenances(prior_provs):
    """Collapse provenance headers whose environment matches into one
    header carrying the list of measurement times.  Headers from a
    DIFFERENT device/platform/method stay separate — that distinction is
    the point of the chain."""
    collapsed = []
    for p in prior_provs:
        sig = {k: v for k, v in p.items() if k not in ("utc", "utcs")}
        utcs = p.get("utcs", []) + ([p["utc"]] if p.get("utc") else [])
        for c in collapsed:
            if {k: v for k, v in c.items() if k != "utcs"} == sig:
                c["utcs"].extend(u for u in utcs if u not in c["utcs"])
                break
        else:
            collapsed.append({**sig, "utcs": utcs})
    return collapsed


# once a timed-out config leaves an orphaned daemon thread alive, its
# ongoing dispatches keep feeding the process-wide telemetry totals —
# every later label's delta would silently include the orphan's traffic,
# so the comms-bytes column stops being bankable for the rest of this
# invocation
_COMM_TAINTED = False

def _comm_bytes_now():
    """Telemetry's cumulative estimated comm bytes (0 if unavailable)."""
    try:
        from distributedarrays_tpu import telemetry
        return telemetry.comm_bytes()
    except Exception:
        return 0


# partial-row banking (ROADMAP item 5): a config that measures several
# metrics publishes the ones already complete through ``bank_partial``;
# if the config then times out (or dies), _guarded banks the published
# metrics with ``{label}_partial: true`` provenance instead of discarding
# the whole row — a 20-minute silicon window that produced a real
# iteration count keeps it even when the timing reps never finished.  A
# later full success supersedes the partials (the flag clears with the
# other stale markers), and a partial row does NOT count as banked, so
# the next window re-attempts the full config.
import threading as _threading

_PARTIAL_LOCK = _threading.Lock()
_PARTIAL: dict = {}


def bank_partial(label, **metrics):
    """Publish already-measured metrics from inside a running config."""
    with _PARTIAL_LOCK:
        _PARTIAL.setdefault(label, {}).update(metrics)


def _take_partial(label):
    with _PARTIAL_LOCK:
        return _PARTIAL.pop(label, None)


def _span_wrapped(label, fn, stats=None):
    """Run a config under a ``bench.config`` telemetry span so the
    journal's comm/span events are attributable per bench label.  The
    span opens INSIDE the worker thread that executes ``fn`` (contextvar
    spans do not cross threads) — and so does the HBM-ledger watermark
    read: the peak is reset per config and sampled into ``stats`` right
    after ``fn`` returns, before any later config can move it.  Imported
    lazily like ``_comm_bytes_now``; degrades to the bare fn if
    telemetry is unavailable."""
    def run():
        try:
            from distributedarrays_tpu import telemetry
            from distributedarrays_tpu.telemetry import memory as _mem
        except Exception:
            return fn()
        _mem.reset_peak()
        with telemetry.span("bench.config", label=label):
            res = fn()
        if stats is not None:
            stats["hbm_peak_mb"] = round(_mem.peak_bytes() / 2 ** 20, 3)
        return res
    return run


_START = time.monotonic()
# headroom under the driver's own timeout; env override for harness tests
_GLOBAL_BUDGET_S = float(os.environ.get("DAT_BENCH_BUDGET_S", "3300"))
# targeted reruns can afford longer per-config windows (a full flash
# sweep compiles every arm)
_TSCALE = float(os.environ.get("DAT_BENCH_TIMEOUT_SCALE", "1"))


_ONLY = {s.strip() for s in os.environ.get("DAT_BENCH_ONLY", "").split(",")
         if s.strip()}
_SEEN_LABELS: set[str] = set()
# rows that ran in THIS invocation and failed (exception or timeout): the
# run still finishes and banks the rest, but exits non-zero
_FAILED_ROWS: list[str] = []

# One result key each guarded config is guaranteed to merge on success.
# Single source of truth for "is this label banked?" — consumed here so a
# rerun failure never masks a banked result.
BANKED_SENTINELS = {
    "flash_attn_d128": "flash_attn_d128_tuned_block",
    "flash_attn_tune": "flash_attn_tuned_block",
    "flash_attn_full": "flash_attn_full_tuned_block",
    "sp_train": "sp_train_step_s",
    "sp_train_d128": "sp_train_d128_step_s",
    "transformer_train": "transformer_train_step_s",
    "decode_kvcache": "decode_kvcache_tokens_per_s",
    "int8_gemm": "int8_gemm_4096_s_per_iter",
    "pallas_gemm": "pallas_gemm_4096_bf16_s_per_iter",
    "pallas_gemm_tune": "pallas_gemm_tuned_block",
    "gemm_16k_1x1": "gemm_16k_1x1_bf16pass_gflops",
    "ring_hop": "ring_hop_fused_8k_bf16_s",
    "ring_train": "ring_train_8k_bf16_s_per_iter",
    "flash_train": "flash_train_8k_bf16_s_per_iter",
    "stencil": "stencil_8192_step_s_per_iter",
    "stencil_jnp": "stencil_8192_jnp_gcells_per_s",
    "stencil_temporal": "stencil_8192_temporal_s_per_iter",
    "reshard_even": "reshard_even_s",
    "ring_gemm": "ring_gemm_xla_s",
    "serve_load": "serve_load_p99_s",
    "serve_decode": "serve_decode_tokens_per_s",
    "train_step": "train_step_s",
    "reshard_uneven": "reshard_uneven_fill_s",
    "reshard_mutate": "reshard_mutate_s",
    "reshard_multiaxis": "reshard_multiaxis_s",
    "broadcast_chain": "broadcast_chain_8192_s_per_iter",
    "mapreduce": "mapreduce_1e8_s_per_iter",
    "sort": "sort_1e7_s",
    "gemm_f32_highest": "gemm_4096_f32_highest_gflops",
    "gemm_16k_1x1_f32_highest": "gemm_16k_1x1_f32_highest_gflops",
    "gemm_crosscheck": "gemm_4096_marginal_crosscheck_s",
    "cg_poisson": "cg_poisson_time_s",
    "matmul_impl_tune": "matmul_impl_tune_n",
    "flash_attn": "flash_attn_8k_bf16_s_per_iter",
}


def _banked_in(details, label):
    """True iff the seeded master table already holds this label's result
    from an earlier silicon run (sentinel present, no error marker)."""
    sent = BANKED_SENTINELS.get(label)
    if sent is None and label.startswith("gemm_16k_"):
        # the one dynamic label family: gemm_16k_{r}x{c}[_f32_highest],
        # tagged with the run's device grid — derive the sentinel the way
        # the config closures build their keys
        sent = label + ("_gflops" if label.endswith("_f32_highest")
                        else "_bf16pass_gflops")
    return (sent is not None and sent in details
            and f"{label}_error" not in details
            # a partial row holds real numbers but not the full config:
            # the next hardware window must re-attempt it
            and not details.get(f"{label}_partial"))


def _guarded(details, label, fn, timeout_s=420.0):
    """Run one optional bench config on a daemon thread with a timeout and
    a global deadline: a config that hangs must cost at most its own
    budget, and never the already-banked numbers or the headline.  A row
    that fails is recorded in ``_FAILED_ROWS`` and fails the run's exit
    code.  ``fn`` returns a dict merged into ``details``.
    ``DAT_BENCH_ONLY=label1,label2`` restricts the optional configs to the
    named ones (targeted harness validation; a short hardware window can
    aim straight at the config it needs)."""
    def _remaining():
        return _GLOBAL_BUDGET_S - (time.monotonic() - _START)

    _SEEN_LABELS.add(label)
    if _ONLY and label not in _ONLY:
        # no marker write: a targeted rerun must not stamp skip-"errors"
        # over the seeded master table's banked results (review round-5)
        return
    banked = _banked_in(details, label)
    if _remaining() < 60:
        # a banked result outlives a later invocation's deadline: the
        # skip marker would read as "this config has no number" when the
        # master table holds a real one from the silicon window
        if not banked:
            details[f"{label}_error"] = "skipped (global bench deadline)"
            _save(details)
        return
    # the label is about to actually execute: clear ITS stale failure
    # markers (in memory only — no _save until an outcome exists) so
    # whatever ends up in the table is attributable to this attempt.
    # Labels this invocation never reaches keep their markers on disk.
    for stale in (f"{label}_error", f"{label}_rerun_error",
                  f"{label}_orphan_running", f"{label}_partial"):
        details.pop(stale, None)
    _take_partial(label)                 # drop any stale published metrics
    comm0 = _comm_bytes_now()
    worker_stats: dict = {}
    fn = _span_wrapped(label, fn, worker_stats)
    effective = min(timeout_s * _TSCALE, _remaining())
    finished, res, thread = _run_with_timeout(fn, effective)
    # a rerun failure next to a banked result goes under _rerun_error:
    # the earlier measurement stays trusted, the fresh failure stays
    # visible
    err_key = f"{label}_rerun_error" if banked else f"{label}_error"
    if not finished or isinstance(res, Exception):
        _FAILED_ROWS.append(label)
    if not finished:
        details[err_key] = f"timed out after {effective:.0f}s"
        partial = _take_partial(label)
        if partial:
            # bank what the config DID measure, flagged as partial
            details.update(partial)
            details[f"{label}_partial"] = True
        thread.join(60)
        if thread.is_alive():
            details[f"{label}_orphan_running"] = True
            global _COMM_TAINTED
            _COMM_TAINTED = True
    elif isinstance(res, Exception):
        details[err_key] = f"{type(res).__name__}: {res}"
        partial = _take_partial(label)
        if partial:
            details.update(partial)
            details[f"{label}_partial"] = True
    elif res:
        details.update(res)
        _take_partial(label)             # full row supersedes the partials
        for stale in (f"{label}_error", f"{label}_rerun_error",
                      f"{label}_orphan_running", f"{label}_partial"):
            details.pop(stale, None)
        # comms-bytes column: estimated bytes this config moved (telemetry
        # comm accounting delta over the config's whole run, retries
        # included) — 0 when telemetry is disabled.  Not banked once an
        # orphaned config's thread is loose: its concurrent traffic would
        # inflate every later label's delta.
        if not _COMM_TAINTED:
            details[f"{label}_comm_bytes_est"] = _comm_bytes_now() - comm0
            # HBM watermark column: the ledger peak over this config's
            # run (reset + read inside the worker thread) — same taint
            # rule as the comm column: an orphaned config's allocations
            # would inflate later labels' watermarks
            if "hbm_peak_mb" in worker_stats:
                details[f"{label}_hbm_peak_mb"] = worker_stats["hbm_peak_mb"]
    _save(details)


def _parse_args(argv=None):
    """``--rows a,b`` selects the named guarded configs (union with
    ``DAT_BENCH_ONLY``); ``--budget`` overrides the global deadline;
    ``--list-rows`` prints the known labels."""
    import argparse
    global _ONLY, _GLOBAL_BUDGET_S
    ap = argparse.ArgumentParser(
        prog="bench.py",
        description="Hardware bench: headline GEMM + guarded configs.")
    ap.add_argument("--rows", default=None, metavar="LABEL[,LABEL...]",
                    help="run only these guarded configs (plus 'headline'"
                         " to include the headline GEMM)")
    ap.add_argument("--budget", type=float, default=None, metavar="S",
                    help="global bench deadline in seconds "
                         "(default DAT_BENCH_BUDGET_S or 3300)")
    ap.add_argument("--list-rows", action="store_true",
                    help="print the known row labels and exit")
    args = ap.parse_args(argv)
    if args.list_rows:
        print("\n".join(["headline"] + sorted(BANKED_SENTINELS)))
        raise SystemExit(0)
    if args.rows:
        _ONLY = _ONLY | {s.strip() for s in args.rows.split(",")
                         if s.strip()}
    if args.budget is not None:
        _GLOBAL_BUDGET_S = float(args.budget)
    return args


def main() -> int:
    import jax
    if _PLATFORM:
        jax.config.update("jax_platforms", _PLATFORM)
    from distributedarrays_tpu.utils.compile_cache import \
        enable_compile_cache
    enable_compile_cache()
    import jax.numpy as jnp
    from jax import lax
    import distributedarrays_tpu as dat
    from distributedarrays_tpu.models import stencil

    devs = jax.devices()
    if devs[0].platform != "tpu" and not _PLATFORM:
        # no chip, no number: nothing is replayed from an earlier run
        print(json.dumps({
            "ok": False, "metric": _HEADLINE_METRIC,
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)},
            "error": "no TPU: jax.devices()[0].platform is "
                     f"{devs[0].platform!r}",
        }))
        return 1

    # keep the previous run's banked numbers recoverable: this run's first
    # _save overwrites the file (copy, not rename)
    cur = Path(__file__).with_name("BENCH_DETAILS.json")
    if cur.exists():
        import shutil
        shutil.copyfile(cur, cur.with_name("BENCH_DETAILS_prev.json"))

    ndev = len(devs)
    peak = _chip_peak_tflops(devs[0])
    details = {
        "_provenance": {
            "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "platform_override": _PLATFORM,
            "devices": [str(d) for d in devs],
            "device_kind": devs[0].device_kind,
            "bf16_peak_tflops": peak,
            "method": "direct t(L)/L over one compiled lax.scan chain, "
                      "scalar-fetch forced; marginal t(L+1)-t(1) recorded "
                      "as *_marginal_crosscheck_s diagnostics only",
        },
    }

    # Seed from the banked table in EVERY mode so ONE master file
    # accumulates across invocations (targeted ``--rows`` reruns and full
    # runs): a config this run reaches overwrites its banked entry, and
    # one it cannot reach keeps its number, with the provenance chain
    # recording which run measured what.
    try:
        prior = json.loads(cur.read_text()) if cur.exists() else {}
    except Exception:
        prior = {}
    # NOTE: stale failure markers are cleared per-label inside _guarded,
    # at the moment the label actually executes — clearing them here for
    # every DAT_BENCH_ONLY label would erase recorded failure evidence
    # for labels this invocation never reaches (killed mid-run, deadline)
    for k in ("bench_only_unmatched_labels", "bench_only_known_labels"):
        prior.pop(k, None)
    prior_prov = prior.pop("_provenance", None)
    prior_provs = prior.pop("_prior_provenances", [])
    details.update(prior)
    if prior_prov is not None:
        prior_provs = prior_provs + [prior_prov]
    # collapse runs whose environment matches into one header carrying
    # the list of measurement times
    collapsed = _collapse_provenances(prior_provs)
    if collapsed:
        details["_prior_provenances"] = collapsed
    # a banked headline is only reusable if it came from the direct
    # t(L)/L method — never reprint a distrusted-format table's number
    _prior_direct = bool(prior_prov) and \
        "direct" in str(prior_prov.get("method", ""))

    # ---- config 0 (headline): 4096^2 GEMM, DEFAULT precision ------------
    N = 4096
    dat.seed(7)
    A = dat.drand((N, N), dtype=jnp.float32)
    B = dat.drand((N, N), dtype=jnp.float32)
    scale = jnp.float32(1.0 / N)

    def gemm_chain_at(precision, reps=2):
        def gemm_chain(L):
            @dat.djit
            def f(a, b):
                def body(c, _):
                    return jnp.matmul(c, b, precision=precision) * scale, None
                c, _ = lax.scan(body, a, None, length=L)
                return jnp.sum(c)
            float(f(A, B))                  # compile + warmup
            return min(_t(lambda: float(f(A, B))) for _ in range(reps))
        return gemm_chain

    chain = gemm_chain_at(jax.lax.Precision.DEFAULT)
    # in a targeted rerun the headline is usually already banked — don't
    # re-pay its ~2 min before the config the short window is aimed at
    _SEEN_LABELS.add("headline")
    _have_headline = ("gemm_4096_mixed_bf16pass_gflops" in details
                      and "gemm_4096_mixed_bf16pass_s_per_iter" in details
                      and "cpu_numpy_gflops" in details
                      and _prior_direct)
    if not _ONLY or "headline" in _ONLY or not _have_headline:
        comm0 = _comm_bytes_now()
        t_gemm, L_used = _periter(chain, L0=64)
        gflops = 2 * N**3 / t_gemm / 1e9
        details["gemm_4096_mixed_bf16pass_s_per_iter"] = t_gemm
        details["gemm_4096_mixed_bf16pass_L"] = L_used
        details["gemm_4096_mixed_bf16pass_gflops"] = gflops
        _bank_tflops(details, "gemm_4096_mixed_bf16pass", gflops / 1e3, peak)
        (A @ B).garray                     # compile the eager path
        details["gemm_4096_mixed_bf16pass_eager_latency_s"] = _t(
            lambda: (A @ B).garray)
        details["gemm_4096_mixed_bf16pass_comm_bytes_est"] = (
            _comm_bytes_now() - comm0)
        _save(details)

        # ---- CPU baseline: same GEMM in numpy (host BLAS) ----------------
        an = np.asarray(A, dtype=np.float32)
        bn = np.asarray(B, dtype=np.float32)
        t_np = min(_t(lambda: an @ bn) for _ in range(2))
        cpu_gflops = 2 * N**3 / t_np / 1e9
        details["cpu_numpy_gflops"] = cpu_gflops
        _save(details)
    else:
        gflops = details["gemm_4096_mixed_bf16pass_gflops"]
        cpu_gflops = details["cpu_numpy_gflops"]
        t_gemm = details["gemm_4096_mixed_bf16pass_s_per_iter"]

    # headline out NOW: everything after this point is banked detail, and
    # a failure in a later config must not cost the run its one JSON line
    print(json.dumps({
        "metric": _HEADLINE_METRIC,
        "value": round(gflops, 2),
        "unit": "GFLOPS",
        "vs_baseline": round(gflops / cpu_gflops, 2),
    }), flush=True)

    if not _ONLY or "headline" in _ONLY:
        # sum(A.^2) half of config 0 (after the headline: detail only).
        # In targeted mode this runs ONLY when explicitly asked: it is
        # unguarded (no per-config timeout), and a wedge here would cost
        # the config the short hardware window was aimed at.
        float(dat.dmapreduce(jnp.square, "sum", A))
        details["sum_sq_4096_eager_s"] = _t(
            lambda: float(dat.dmapreduce(jnp.square, "sum", A)))
        _save(details)

    # methodology cross-check on the SAME op: the round-2 marginal
    # estimator vs the banked direct number (agreement ratio recorded; a
    # marginal-derived TFLOPS above peak proves the estimator, not the
    # chip)
    def cfg_crosscheck():
        t_m = _marginal(chain, L0=50)
        out = {"gemm_4096_marginal_crosscheck_s": t_m,
               "gemm_4096_marginal_vs_direct_ratio": t_m / t_gemm}
        return out

    _guarded(details, "gemm_crosscheck", cfg_crosscheck, timeout_s=300)

    # ---- matmul implementation tune (VERDICT round-4 item 4): measure
    # jnp.matmul vs the owned Pallas schedule at the headline shape for
    # the dtypes users actually hit, bank the winner in the autotune
    # registry (consulted by `matmul` / `DArray @ DArray`), and persist
    # it so every later process in this tree dispatches to the winner.
    def cfg_matmul_impl_tune():
        from distributedarrays_tpu.utils import autotune
        from distributedarrays_tpu.ops import linalg as _la
        # DAT_BENCH_TUNE_N: harness-validation override — the 4096 shape
        # in interpret-mode Pallas is unboundedly slow on host CPU
        TN = int(os.environ.get("DAT_BENCH_TUNE_N", N))

        def chain_timer(op, a, b):
            # the trusted t(L)/L method, handed to the API's tuner so
            # measure/record/persist has ONE owner (linalg._tune_impls)
            dt = a.dtype
            sc = jnp.asarray(1.0 / a.shape[-1], dt)

            def chain(L):
                @jax.jit
                def f(a_, b_):
                    def body(c, _):
                        return (op(c, b_) * sc).astype(dt), None
                    c, _ = lax.scan(body, a_, None, length=L)
                    return jnp.sum(c.astype(jnp.float32))
                float(f(a, b))              # compile + warmup
                return min(_t(lambda: float(f(a, b))) for _ in range(2))

            t, _ = _periter(chain, L0=32)
            return t

        # a winner measured under the forced host-CPU validation run must
        # never persist where a TPU process would load it (the registry
        # key carries the device kind as a second fence)
        persist = _PLATFORM != "cpu" and jax.default_backend() != "cpu"
        # the shape is part of the result's identity: an override run
        # (harness validation) must never read as headline-4096 numbers
        out = {"matmul_impl_tune_n": TN}
        # each tuner persists its own winner the moment it lands (wedge
        # resilience: a later tuner dying must not cost earlier spoils)
        for dt, tag in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
            winner, results = _la.tune_matmul_impl(
                TN, TN, TN, dtype=dt, timer=chain_timer, persist=persist)
            for impl, t in results.items():
                if t != float("inf"):
                    out[f"matmul_impl_{tag}_{impl}_s_per_iter"] = t
            out[f"matmul_impl_{tag}_winner"] = winner
        if len(jax.devices()) >= 2:
            winner, results = _la.tune_matmul_impl_dist(
                TN, TN, TN, timer=chain_timer, persist=persist)
            for impl, t in results.items():
                if t != float("inf"):
                    out[f"matmul_impl_dist_{impl}_s_per_iter"] = t
            out["matmul_impl_dist_winner"] = winner
        if len(jax.devices()) >= 4:
            # the 2-D-grid arm (BASELINE config 3's block layout): GSPMD
            # vs the owned tile schedule (Cannon on square grids, SUMMA
            # panels on rectangles) on the largest power-of-two (r, c)
            # grid the devices support — power-of-two factors so the
            # shape rounding below always divides; e.g. 4 -> 2x2,
            # 8 -> 2x4 (all chips used), 16 -> 4x4 — at the 16384²
            # config's shape (scaled by the harness override, rounded
            # to an lcm(r, c) multiple)
            ndev = len(jax.devices())
            gr = 2
            while (2 * gr) * (2 * gr) <= ndev:
                gr *= 2
            gc = gr * 2 if gr * gr * 2 <= ndev else gr
            TS = int(os.environ.get("DAT_BENCH_TUNE_N", 4 * N))
            TS -= TS % max(gr, gc)
            winner, results = _la.tune_matmul_impl_summa(
                TS, TS, TS, g=(gr, gc), timer=chain_timer, persist=persist)
            for impl, t in results.items():
                if t != float("inf"):
                    out[f"matmul_impl_summa_{gr}x{gc}_{impl}_s_per_iter"] = t
            out[f"matmul_impl_summa_{gr}x{gc}_winner"] = winner
            out["matmul_impl_summa_n"] = TS
        if persist:
            out["matmul_impl_cache_path"] = autotune.default_cache_path()
        return out

    _guarded(details, "matmul_impl_tune", cfg_matmul_impl_tune,
             timeout_s=600)


    # ---- extra: Pallas flash attention at long context -------------------
    def cfg_flash():
        from distributedarrays_tpu.ops.pallas_attention import flash_attention
        SQ, HQ, DQ = 8192, 8, 64
        q = jax.random.normal(jax.random.key(1), (SQ, HQ, DQ), jnp.bfloat16)

        def fa_len(L):
            def f():
                def body(x, _):
                    # 1024^2 blocks: the measured-best tiling on v5e
                    return flash_attention(x, q, q, causal=True,
                                           block_q=1024, block_k=1024), None
                x, _ = lax.scan(body, q, None, length=L)
                return jnp.sum(x.astype(jnp.float32))
            jf = jax.jit(f)
            float(jf())
            return min(_t(lambda: float(jf())) for _ in range(2))

        t_fa, L = _periter(fa_len, L0=8)
        # causal flash: ~2*S^2*D*H flops (QK^T + PV), halved by causality
        flops = 2 * 2 * SQ * SQ * DQ * HQ / 2
        out = {"flash_attn_8k_bf16_s_per_iter": t_fa}
        _bank_tflops(out, "flash_attn_8k_bf16_causal_effective",
                     flops / t_fa / 1e12, peak)
        return out

    _guarded(details, "flash_attn", cfg_flash)

    # ---- extra: flash-attention block autotune sweep ---------------------
    # sweeps (block_q, block_k) at the bench shape, records the winner in
    # the autotune registry (consulted by flash_attention when blocks are
    # unspecified), and reports the tuned TFLOPS
    def cfg_flash_tune():
        from distributedarrays_tpu.ops.pallas_attention import flash_attention
        from distributedarrays_tpu.utils import autotune
        SQ, HQ, DQ = 8192, 8, 64
        q = jax.random.normal(jax.random.key(1), (SQ, HQ, DQ), jnp.bfloat16)

        def timer(cfg):
            bq, bk = cfg[0], cfg[1]
            hf = cfg[2] if len(cfg) > 2 else 1

            # FIXED chain length — exactly ONE compile per arm: growing
            # L re-compiles; ranking arms needs ratios at ~0.5 s/call
            # (dispatch noise <5%), not dispatch-free absolutes — the
            # banked entry re-times the winner properly.
            L = 384

            def f():
                def body(x, _):
                    return flash_attention(x, q, q, causal=True,
                                           block_q=bq, block_k=bk,
                                           head_fold=hf), None
                x, _ = lax.scan(body, q, None, length=L)
                return jnp.sum(x.astype(jnp.float32))
            jf = jax.jit(f)
            float(jf())
            return min(_t(lambda: float(jf())) for _ in range(2)) / L

        cands = [(bq, bk) for bq in (512, 1024, 2048)
                 for bk in (512, 1024, 2048)]
        # head-fold arms: batched-dot grid steps amortize grid/DMA
        # overhead at small head_dim (the QK/PV contraction width stays
        # 64, so this tunes scheduling, not the MXU ceiling)
        cands += [(1024, 1024, 2), (1024, 1024, 4), (2048, 1024, 2),
                  (512, 512, 2), (512, 512, 4)]
        key = autotune.device_key_for(SQ, HQ, DQ, jnp.bfloat16(0).dtype, True)
        best, results = autotune.sweep("flash_attention", key, cands, timer, persist=True)
        cache = autotune.save_default()   # future processes pick this up
        flops = 2 * 2 * SQ * SQ * DQ * HQ / 2
        out = {
            "flash_attn_tuned_block": list(best),
            "flash_attn_sweep": {
                "x".join(str(v) for v in cfg): flops / t / 1e12
                for cfg, t in results.items()},
            "autotune_cache_path": cache,
        }
        _bank_tflops(out, "flash_attn_tuned_causal_effective",
                     flops / results[best] / 1e12, peak)
        return out

    _guarded(details, "flash_attn_tune", cfg_flash_tune, timeout_s=900)

    # ---- extra: non-causal flash MFU (VERDICT round-3 item 5) ------------
    def cfg_flash_full():
        from distributedarrays_tpu.ops.pallas_attention import flash_attention
        from distributedarrays_tpu.utils import autotune
        SQ, HQ, DQ = 8192, 8, 64
        q = jax.random.normal(jax.random.key(1), (SQ, HQ, DQ), jnp.bfloat16)

        def timer(cfg):
            bq, bk = cfg[0], cfg[1]
            hf = cfg[2] if len(cfg) > 2 else 1
            L = 192                      # fixed: one compile per arm

            def f():
                def body(x, _):
                    return flash_attention(x, q, q, causal=False,
                                           block_q=bq, block_k=bk,
                                           head_fold=hf), None
                x, _ = lax.scan(body, q, None, length=L)
                return jnp.sum(x.astype(jnp.float32))
            jf = jax.jit(f)
            float(jf())
            return min(_t(lambda: float(jf())) for _ in range(2)) / L

        cands = [(512, 512), (1024, 1024), (2048, 1024), (1024, 2048),
                 (2048, 2048), (4096, 1024),
                 (1024, 1024, 2), (1024, 1024, 4), (2048, 1024, 2)]
        key = autotune.device_key_for(SQ, HQ, DQ, jnp.bfloat16(0).dtype, False)
        best, results = autotune.sweep("flash_attention", key, cands, timer, persist=True)
        autotune.save_default()
        flops = 2 * 2 * SQ * SQ * DQ * HQ        # full: no causal halving
        out = {"flash_attn_full_tuned_block": list(best),
               "flash_attn_full_sweep": {
                   "x".join(str(v) for v in cfg): flops / t / 1e12
                   for cfg, t in results.items()}}
        _bank_tflops(out, "flash_attn_8k_bf16_full",
                     flops / results[best] / 1e12, peak)
        return out

    _guarded(details, "flash_attn_full", cfg_flash_full, timeout_s=900)

    # ---- extra: d=128 flash MFU (VERDICT round-3 item 5) -----------------
    # at d=64 BOTH flash matmuls carry a 64-wide dim (QK^T contracts over
    # d, PV's N is d), so each MXU pass uses half the 128x128 array — a
    # ~50% MFU ceiling no tiling can lift.  d=128 fills the array; this
    # config shows the kernel's MFU where the hardware allows >60%.
    def cfg_flash_d128():
        from distributedarrays_tpu.ops.pallas_attention import flash_attention
        from distributedarrays_tpu.utils import autotune
        SQ, HQ, DQ = 8192, 4, 128              # same bytes as the 8x64 run
        q = jax.random.normal(jax.random.key(7), (SQ, HQ, DQ), jnp.bfloat16)

        def timer(cfg):
            bq, bk = cfg[0], cfg[1]
            hf = cfg[2] if len(cfg) > 2 else 1
            L = 192                      # fixed: one compile per arm

            def f():
                def body(x, _):
                    return flash_attention(x, q, q, causal=False,
                                           block_q=bq, block_k=bk,
                                           head_fold=hf), None
                x, _ = lax.scan(body, q, None, length=L)
                return jnp.sum(x.astype(jnp.float32))
            jf = jax.jit(f)
            float(jf())
            return min(_t(lambda: float(jf())) for _ in range(2)) / L

        cands = [(512, 512), (1024, 512), (512, 1024), (1024, 1024),
                 (2048, 512), (2048, 1024),
                 (1024, 512, 2), (1024, 1024, 2), (2048, 1024, 2),
                 # round-5 second wave: the first silicon sweep showed
                 # bk=1024 dominating bk=512 (113-117 vs 66-82 TFLOPS) and
                 # bq=1024 beating 2048 — probe deeper K tiles and the
                 # all-heads fold before settling at 0.596 MFU
                 (512, 2048), (1024, 2048), (2048, 2048),
                 (1024, 2048, 2), (1024, 1024, 4)]
        key = autotune.device_key_for(SQ, HQ, DQ, jnp.bfloat16(0).dtype, False)
        best, results = autotune.sweep("flash_attention", key, cands, timer, persist=True)
        autotune.save_default()
        flops = 2 * 2 * SQ * SQ * DQ * HQ
        out = {"flash_attn_d128_tuned_block": list(best),
               "flash_attn_d128_sweep": {
                   "x".join(str(v) for v in cfg): flops / t / 1e12
                   for cfg, t in results.items()}}
        _bank_tflops(out, "flash_attn_8k_bf16_d128_full",
                     flops / results[best] / 1e12, peak)
        return out

    _guarded(details, "flash_attn_d128", cfg_flash_d128, timeout_s=600)

    # ---- config 1: broadcast chain sin.(A) .+ B .* C on 8192^2 ----------
    M = 8192
    X = dat.drand((M, M)); Y = dat.drand((M, M)); Z = dat.drand((M, M))

    def chain_chain(L):
        @dat.djit
        def f(a, b, c):
            def body(acc, _):
                return jnp.sin(acc) + b * c, None
            acc, _ = lax.scan(body, a, None, length=L)
            return jnp.sum(acc)
        float(f(X, Y, Z))
        return min(_t(lambda: float(f(X, Y, Z))) for _ in range(2))

    def cfg_chain():
        t_chain, L = _periter(chain_chain, L0=32)
        return {"broadcast_chain_8192_s_per_iter": t_chain,
                "broadcast_chain_8192_gbps": 4 * M * M * 4 / t_chain / 1e9}

    _guarded(details, "broadcast_chain", cfg_chain)

    # ---- config 2: mapreduce(abs2,+) and mean/std over 1e8 --------------
    V = dat.drand((100_000_000,))

    def mr_chain(L):
        @dat.djit
        def f(v):
            def body(acc, _):
                # acc feeds back so the reduction re-reads v every iteration
                return acc * 1e-30 + jnp.sum(jnp.square(v + acc * 1e-30)), None
            acc, _ = lax.scan(body, jnp.float32(0), None, length=L)
            return acc
        float(f(V))
        return min(_t(lambda: float(f(V))) for _ in range(2))

    def cfg_mr():
        t_mr, L = _periter(mr_chain, L0=64)
        out = {"mapreduce_1e8_s_per_iter": t_mr,
               "mapreduce_1e8_gbps": 4 * 1e8 / t_mr / 1e9}
        float(dat.dmean(V)); float(dat.dstd(V))
        out["mean_std_1e8_eager_s"] = _t(
            lambda: (float(dat.dmean(V)), float(dat.dstd(V))))
        return out

    _guarded(details, "mapreduce", cfg_mr)

    # ---- config 4: stencil halo exchange on 8192^2 -----------------------
    rows = (M // ndev) * ndev
    S = dat.drand((rows, M), procs=range(ndev), dist=(ndev, 1))

    def st(iters, use_pallas=None, temporal=None):
        r = stencil.stencil5(S, iters=iters, use_pallas=use_pallas,
                             temporal=temporal)
        v = float(dat.dsum(r))                       # one compiled scan
        r.close()
        return v

    def st_len_at(use_pallas, temporal=None):
        def st_len(L):
            st(L, use_pallas, temporal)              # compile
            return min(_t(lambda: st(L, use_pallas, temporal))
                       for _ in range(2))
        return st_len

    # single-step streaming kernel (the BASELINE config semantics: one
    # halo exchange per step), the jnp formulation for comparison, and the
    # temporal-blocked kernel (k=8 steps per launch, ghost-zone scheme)
    def cfg_stencil():
        t_st, L = _periter(st_len_at(None, temporal=1), L0=16)
        return {"stencil_8192_step_s_per_iter": t_st,
                "stencil_8192_gcells_per_s": rows * M / t_st / 1e9}

    def cfg_stencil_jnp():
        t_stj, L = _periter(st_len_at(False), L0=16)
        return {"stencil_8192_jnp_gcells_per_s": rows * M / t_stj / 1e9}

    def cfg_stencil_temporal():
        t_stt, L = _periter(st_len_at(None), L0=32)  # auto temporal depth
        return {"stencil_8192_temporal_s_per_iter": t_stt,
                "stencil_8192_temporal_gcells_per_s": rows * M / t_stt / 1e9}

    _guarded(details, "stencil", cfg_stencil)
    _guarded(details, "stencil_jnp", cfg_stencil_jnp)
    _guarded(details, "stencil_temporal", cfg_stencil_temporal)

    # free the bandwidth-config buffers before the 16k arrays go up
    for arr in (X, Y, Z, V, S):
        arr.close()

    # ---- config 3: 16384^2 GEMM on an explicit block layout --------------
    # BASELINE.json configs[3]; reference semantics = the tile-grid
    # _matmatmul! (/root/reference/src/linalg.jl:189-311), here one jitted
    # matmul over block-sharded operands (XLA SUMMA over ICI).  A true 2x2
    # grid needs >=4 devices; on fewer the grid degrades and the key label
    # says which grid actually ran.  bf16-pass first (banked); the riskier
    # f32-HIGHEST pass runs in the guarded tail below.
    K16 = 16384
    g3 = (2, 2) if ndev >= 4 else (1, 1)
    tag = f"gemm_16k_{g3[0]}x{g3[1]}"
    A3 = dat.drand((K16, K16), dtype=jnp.float32,
                   procs=range(g3[0] * g3[1]), dist=g3)
    B3 = dat.drand((K16, K16), dtype=jnp.float32,
                   procs=range(g3[0] * g3[1]), dist=g3)
    s16 = jnp.float32(1.0 / K16)

    def gemm16_chain_at(precision):
        def gemm16_chain(L):
            @dat.djit
            def f(a, b):
                def body(c, _):
                    return jnp.matmul(c, b, precision=precision) * s16, None
                c, _ = lax.scan(body, a, None, length=L)
                return jnp.sum(c)
            float(f(A3, B3))
            return min(_t(lambda: float(f(A3, B3))) for _ in range(2))
        return gemm16_chain

    def cfg_gemm16():
        t16, L = _periter(gemm16_chain_at(jax.lax.Precision.DEFAULT), L0=2)
        g = 2 * K16**3 / t16 / 1e9
        out = {f"{tag}_bf16pass_s_per_iter": t16,
               f"{tag}_bf16pass_gflops": g}
        _bank_tflops(out, f"{tag}_bf16pass", g / 1e3, peak)
        return out

    _guarded(details, tag, cfg_gemm16, timeout_s=600)

    # ---- extra: fused (Pallas) vs einsum ring-attention hop --------------
    # One chip = a 1-rank ring, so this isolates the per-hop compute the
    # ring pipelines against ppermute: the fused path must be >= the
    # einsum composition (VERDICT round-2 item 7).
    def cfg_ring():
        from distributedarrays_tpu import layout as L
        from distributedarrays_tpu.models.ring_attention import (
            ring_attention_kernel, ring_flash_attention_kernel)
        from jax.sharding import PartitionSpec as RP
        SR, HR, DR = 8192, 8, 64
        mesh1 = L.mesh_for([0], (1,))
        ax = mesh1.axis_names[0]
        qr = jax.random.normal(jax.random.key(2), (SR, HR, DR), jnp.bfloat16)

        def ring_len(kernel, **kw):
            shm = jax.shard_map(
                lambda a, b, c: kernel(a, b, c, ax, causal=True, **kw),
                mesh=mesh1, in_specs=(RP(ax),) * 3, out_specs=RP(ax),
                check_vma=False)

            def run(Ln):
                @jax.jit
                def f(qq):
                    def body(c, _):
                        return shm(c, qq, qq), None
                    c, _ = lax.scan(body, qq, None, length=Ln)
                    return jnp.sum(c.astype(jnp.float32))
                float(f(qr))
                return min(_t(lambda: float(f(qr))) for _ in range(2))
            return run

        # sweep the fused hop's blocks and bank the winner under
        # "ring_flash" (consulted by ring_flash_attention_kernel when
        # blocks are unspecified — the sp-transformer's hot path)
        from distributedarrays_tpu.utils import autotune
        cands = [(512, 512), (1024, 512), (1024, 1024), (2048, 1024),
                 (1024, 1024, 2), (1024, 1024, 4), (512, 512, 2)]
        key = autotune.device_key_for(SR, HR, DR, jnp.bfloat16(0).dtype, True)

        def hop_timer(cfg):
            run = ring_len(ring_flash_attention_kernel,
                           block_q=cfg[0], block_k=cfg[1],
                           head_fold=cfg[2] if len(cfg) > 2 else 1)
            # fixed chain length: one compile per arm
            return run(384) / 384

        best, sweep = autotune.sweep("ring_flash", key, cands, hop_timer, persist=True)
        # _tuned_hop_blocks keys on the PER-RANK local block, and a real
        # P-rank ring sees SR/P — extrapolate the swept winner to the
        # common ring sizes (the hop programs clip blocks to the local
        # extent, so an oversized tuned block degrades gracefully);
        # labeled extrapolated so nobody mistakes them for swept shapes
        extrap = []
        for rp in (2, 4, 8, 16, 32):
            if SR % rp == 0 and SR // rp >= 512:
                autotune.record("ring_flash",
                                autotune.device_key_for(
                                    SR // rp, HR, DR,
                                    jnp.bfloat16(0).dtype, True),
                                list(best))
                extrap.append(SR // rp)
        autotune.save_default()
        t_fused = sweep[best]
        t_einsum, _ = _periter(ring_len(ring_attention_kernel), L0=4)
        return {"ring_hop_fused_8k_bf16_s": t_fused,
                "ring_hop_tuned_block": list(best),
                "ring_hop_tuned_extrapolated_to_local_blocks": extrap,
                "ring_hop_sweep": {
                    "x".join(str(v) for v in cfg): t
                    for cfg, t in sweep.items()},
                "ring_hop_einsum_8k_bf16_s": t_einsum,
                "ring_hop_fused_speedup": t_einsum / t_fused}

    _guarded(details, "ring_hop", cfg_ring)

    # ---- extra: ring-attention TRAINING step (fused FA2 ring backward) ---
    # the round-3 deliverable: grads through the Pallas ring path
    def cfg_ring_train():
        from distributedarrays_tpu import layout as L
        from distributedarrays_tpu.models.ring_attention import (
            ring_flash_attention_kernel)
        from jax.sharding import PartitionSpec as RP
        SR, HR, DR = 8192, 8, 64
        mesh1 = L.mesh_for([0], (1,))
        ax = mesh1.axis_names[0]
        qr = jax.random.normal(jax.random.key(6), (SR, HR, DR), jnp.bfloat16)
        shm = jax.shard_map(
            lambda a, b, c: ring_flash_attention_kernel(
                a, b, c, ax, causal=True, block_q=1024, block_k=1024),
            mesh=mesh1, in_specs=(RP(ax),) * 3, out_specs=RP(ax),
            check_vma=False)
        g = jax.grad(lambda x: jnp.sum(shm(x, x, x).astype(jnp.float32)))

        def run(Ln):
            @jax.jit
            def f(qq):
                def body(x, _):
                    return (x + 1e-6 * g(x).astype(x.dtype)), None
                x, _ = lax.scan(body, qq, None, length=Ln)
                return jnp.sum(x.astype(jnp.float32))
            float(f(qr))
            return min(_t(lambda: float(f(qr))) for _ in range(2))

        t_rt, _ = _periter(run, L0=2)
        # fwd 2 matmuls + bwd 5 -> 3.5x fwd flops, causal half
        flops = 3.5 * (2 * 2 * SR * SR * DR * HR / 2)
        out = {"ring_train_8k_bf16_s_per_iter": t_rt}
        _bank_tflops(out, "ring_train_8k_bf16", flops / t_rt / 1e12, peak)
        return out

    _guarded(details, "ring_train", cfg_ring_train, timeout_s=600)

    # ---- extra: hand-written Pallas GEMM kernel (compiled) ---------------
    def cfg_pallas_gemm():
        from distributedarrays_tpu.ops.pallas_gemm import pallas_matmul
        ap = jax.random.normal(jax.random.key(3), (4096, 4096), jnp.bfloat16)
        bp = jax.random.normal(jax.random.key(4), (4096, 4096), jnp.bfloat16)
        spg = jnp.bfloat16(1.0 / 4096)

        def pg_len(L):
            def f():
                def body(c, _):
                    return (pallas_matmul(c, bp) * spg).astype(jnp.bfloat16), None
                c, _ = lax.scan(body, ap, None, length=L)
                return jnp.sum(c.astype(jnp.float32))
            jf = jax.jit(f)
            float(jf())
            return min(_t(lambda: float(jf())) for _ in range(2))

        t_pg, L = _periter(pg_len, L0=16)
        out = {"pallas_gemm_4096_bf16_s_per_iter": t_pg,
               "pallas_gemm_4096_marginal_crosscheck_s":
                   _marginal(pg_len, L0=4, min_delta=0.05)}
        _bank_tflops(out, "pallas_gemm_4096_bf16",
                     2 * 4096**3 / t_pg / 1e12, peak)
        return out

    _guarded(details, "pallas_gemm", cfg_pallas_gemm)

    # ---- extra: Pallas GEMM block autotune sweep -------------------------
    def cfg_pallas_gemm_tune():
        from distributedarrays_tpu.ops.pallas_gemm import pallas_matmul
        from distributedarrays_tpu.utils import autotune
        NP = 4096
        ap = jax.random.normal(jax.random.key(3), (NP, NP), jnp.bfloat16)
        bp = jax.random.normal(jax.random.key(4), (NP, NP), jnp.bfloat16)
        spg = jnp.bfloat16(1.0 / NP)

        def timer(cfg):
            L = 512                      # fixed: one compile per arm
            # (~0.9ms/iter at the 152-TFLOPS class -> ~0.5 s/call; the
            # winner is re-timed with full amortization by cfg_pallas_gemm)

            def f():
                def body(c, _):
                    return (pallas_matmul(c, bp, block=cfg) * spg
                            ).astype(jnp.bfloat16), None
                c, _ = lax.scan(body, ap, None, length=L)
                return jnp.sum(c.astype(jnp.float32))
            jf = jax.jit(f)
            float(jf())
            return min(_t(lambda: float(jf())) for _ in range(2)) / L

        cands = [(1024, 1024, 512), (1024, 1024, 1024), (2048, 1024, 512),
                 (1024, 2048, 512), (512, 1024, 1024), (2048, 2048, 256),
                 # wider K streams (fewer acc flushes) and full-row tiles;
                 # VMEM-overflow arms are skipped by the sweep's try/except
                 (512, 512, 2048), (1024, 512, 2048), (2048, 2048, 512),
                 (4096, 1024, 256), (1024, 4096, 256)]
        key = autotune.device_key_for(NP, NP, NP, ap.dtype, bp.dtype)
        best, results = autotune.sweep("pallas_matmul", key, cands, timer, persist=True)
        autotune.save_default()
        out = {
            "pallas_gemm_tuned_block": list(best),
            "pallas_gemm_sweep": {
                "x".join(map(str, c)): 2 * NP**3 / t / 1e12
                for c, t in results.items()},
        }
        _bank_tflops(out, "pallas_gemm_tuned",
                     2 * NP**3 / results[best] / 1e12, peak)
        return out

    _guarded(details, "pallas_gemm_tune", cfg_pallas_gemm_tune,
             timeout_s=600)

    # ---- extra: int8 quantized Pallas GEMM (beyond-bf16-peak path) -------
    # e-class MXUs run int8 at 2x the bf16 rate; the dynamic-quantization
    # GEMM (quantize -> int8 matmul -> fused dequant) can therefore beat
    # the chip's bf16 peak.  TOPS banked against the int8 peak table.
    def cfg_int8_gemm():
        from distributedarrays_tpu.ops.pallas_gemm import quantized_matmul
        peak8 = _chip_peak_tflops(devs[0], _PEAKS_INT8)
        NP = 4096
        ap = jax.random.normal(jax.random.key(3), (NP, NP), jnp.float32)
        bp = jax.random.normal(jax.random.key(4), (NP, NP), jnp.float32)
        s8 = jnp.float32(1.0 / NP)

        def q8_len(L):
            def f():
                def body(c, _):
                    # full dynamic path each iter: quantize + int8 MXU +
                    # fused dequant (the honest end-to-end op cost)
                    return quantized_matmul(c, bp) * s8, None
                c, _ = lax.scan(body, ap, None, length=L)
                return jnp.sum(c)
            jf = jax.jit(f)
            float(jf())
            return min(_t(lambda: float(jf())) for _ in range(2))

        t8, L = _periter(q8_len, L0=16)
        out = {"int8_gemm_4096_s_per_iter": t8,
               "int8_gemm_peak_tops": peak8}
        _bank_tflops(out, "int8_gemm_4096", 2 * NP**3 / t8 / 1e12, peak8,
                     unit="tops")
        # vs the chip's BF16 peak — >1.0 here is the beyond-parity headline
        if peak:
            out["int8_gemm_vs_bf16_peak"] = round(
                2 * NP**3 / t8 / 1e12 / peak, 4)
        return out

    _guarded(details, "int8_gemm", cfg_int8_gemm, timeout_s=600)

    # ---- extra: flash-attention TRAINING step (fwd+bwd, FA2 custom-vjp) --
    def cfg_flash_train():
        from distributedarrays_tpu.ops.pallas_attention import flash_attention
        ST, HT, DT = 8192, 8, 64
        qt = jax.random.normal(jax.random.key(5), (ST, HT, DT), jnp.bfloat16)

        def grad_len(L):
            def one(x):
                return jnp.sum(flash_attention(x, x, x, causal=True,
                                               block_q=1024, block_k=1024)
                               .astype(jnp.float32))
            g = jax.grad(one)

            def f():
                def body(x, _):
                    return (x + 1e-6 * g(x).astype(x.dtype)), None
                x, _ = lax.scan(body, qt, None, length=L)
                return jnp.sum(x.astype(jnp.float32))
            jf = jax.jit(f)
            float(jf())
            return min(_t(lambda: float(jf())) for _ in range(2))

        t_tr, L = _periter(grad_len, L0=4)
        # fwd 2 matmuls + bwd 5 -> 3.5x the fwd matmul flops, causal half
        flops = 3.5 * (2 * 2 * ST * ST * DT * HT / 2)
        out = {"flash_train_8k_bf16_s_per_iter": t_tr}
        _bank_tflops(out, "flash_train_8k_bf16", flops / t_tr / 1e12, peak)
        return out

    _guarded(details, "flash_train", cfg_flash_train)

    # ---- extra: full transformer train step (flagship model) -------------
    def cfg_transformer_train():
        from distributedarrays_tpu.models import transformer as T
        cfg = T.Config(vocab=8192, dim=1024, heads=16, layers=8,
                       ffn_mult=4, max_seq=2048, dtype=jnp.bfloat16)
        params = T.init_params(jax.random.key(0), cfg)
        Bt, St = 4, 2048
        toks = jax.random.randint(jax.random.key(1), (Bt, St), 0, cfg.vocab)
        lr = jnp.float32(1e-4)

        def steps_len(L):
            @jax.jit
            def f(p):
                def body(p, _):
                    loss, g = jax.value_and_grad(T.loss_fn)(p, toks, cfg)
                    p = jax.tree_util.tree_map(
                        lambda w, gg: (w.astype(jnp.float32)
                                       - lr * gg.astype(jnp.float32))
                        .astype(w.dtype), p, g)
                    return p, loss
                p, losses = lax.scan(body, p, None, length=L)
                return losses[-1]
            float(f(params))
            return min(_t(lambda: float(f(params))) for _ in range(2))

        t_step, L = _periter(steps_len, L0=4)
        nparams = sum(int(np.prod(x.shape))
                      for x in jax.tree_util.tree_leaves(params))
        toks_per_step = Bt * (St - 1)
        out = {
            "transformer_train_step_s": t_step,
            "transformer_train_tokens_per_s": toks_per_step / t_step,
            "transformer_train_params": nparams,
        }
        _bank_tflops(out, "transformer_train_est",
                     6 * nparams * toks_per_step / t_step / 1e12, peak)
        return out

    _guarded(details, "transformer_train", cfg_transformer_train,
             timeout_s=600)

    # ---- extra: sp-transformer train step + KV-cache decode --------------
    # The composed flagship perf story (VERDICT round-4 item 7): the
    # explicit-SPMD sequence-parallel model (ring flash attention +
    # tp_ffn) timed as train tokens/sec with model-FLOPs MFU, plus the
    # KV-cache decode step.  On one chip the ring is 1-rank (hop-free)
    # — still the full composed program; multi-chip scaling is covered
    # by the dryrun/CPU-mesh legs until a multi-chip window exists.
    def _sp_train_entry(SH, prefix):
        from distributedarrays_tpu.models import sp_transformer as SPT
        from distributedarrays_tpu.parallel import collectives as C_
        p_ = len(jax.devices())
        mesh = C_.spmd_mesh(p_)
        SV, SE, SL = 8192, 1024, 8
        SS = int(os.environ.get("DAT_BENCH_SP_SEQ", 8192))
        cfg = SPT.SPConfig(vocab=SV, dim=SE, heads=SH, layers=SL,
                           ffn_mult=4, max_seq=SS, dtype=jnp.bfloat16)
        params = SPT.init_params(jax.random.key(0), cfg)
        Bt = 1
        toks = jax.random.randint(jax.random.key(1), (Bt, SS), 0, SV,
                                  dtype=jnp.int32)
        lr = jnp.float32(1e-4)
        # resolve the tuned hop blocks OUTSIDE the chain jit (the
        # sp_transformer contract) so a tune banked earlier in this run
        # is what gets timed
        rcfg = SPT._resolve_cfg(cfg, mesh, "p", toks.shape)
        grad_fn = SPT._grad_program(mesh, rcfg, "p")

        def steps_len(L):
            @jax.jit
            def f(prm):
                def body(prm, _):
                    loss, g = grad_fn(prm, toks)
                    prm = jax.tree_util.tree_map(
                        lambda w, gg: (w.astype(jnp.float32)
                                       - lr * gg.astype(jnp.float32))
                        .astype(w.dtype), prm, g)
                    return prm, loss
                prm, losses = lax.scan(body, prm, None, length=L)
                return losses[-1]
            float(f(params))
            return min(_t(lambda: float(f(params))) for _ in range(2))

        t_step, L = _periter(steps_len, L0=2)
        nparams = sum(int(np.prod(x.shape))
                      for x in jax.tree_util.tree_leaves(params))
        Dh = SE // SH
        # model FLOPs: 6*params per token (fwd+bwd matmuls) + causal
        # flash attention (fwd QK^T+PV pair, bwd 2.5x -> 3.5x, /2 causal)
        flops = (6 * nparams * Bt * SS
                 + 3.5 * SL * (2 * 2 * SS * SS * Dh * SH) / 2 * Bt)
        out = {
            f"{prefix}_step_s": t_step,
            f"{prefix}_seq": SS,
            f"{prefix}_heads": SH,
            f"{prefix}_head_dim": Dh,
            f"{prefix}_tokens_per_s": Bt * SS / t_step,
            f"{prefix}_params": nparams,
            f"{prefix}_hop_blocks": [rcfg.block_q, rcfg.block_k,
                                     rcfg.head_fold],
        }
        _bank_tflops(out, f"{prefix}_model", flops / t_step / 1e12, peak)
        return out

    def cfg_sp_train():
        return _sp_train_entry(16, "sp_train")

    def cfg_sp_train_d128():
        # same parameter count (QKV/O shapes are head-count-invariant),
        # head_dim 128: attention tiles span the full 128-lane MXU width
        # instead of half of it — the d=64 flash ceiling is the measured
        # bottleneck of the 16-head entry (flash d=64 0.31 vs d=128 0.60
        # MFU on this chip)
        return _sp_train_entry(8, "sp_train_d128")

    _guarded(details, "sp_train", cfg_sp_train, timeout_s=900)
    _guarded(details, "sp_train_d128", cfg_sp_train_d128, timeout_s=900)

    def cfg_decode():
        from distributedarrays_tpu.models import transformer as T
        # DAT_BENCH_DECODE_STEPS: harness-validation override (the full
        # 2k-step scan is minutes-slow on host CPU, seconds on a chip)
        total = max(int(os.environ.get("DAT_BENCH_DECODE_STEPS", 2032)), 32)
        # cache length is a SEPARATE knob: the default path must keep the
        # 2048 KV cache it has always had (a cache resize changes the
        # per-step attention cost and breaks comparability across runs)
        cache = max(int(os.environ.get("DAT_BENCH_DECODE_CACHE", 2048)),
                    total)
        cfg = T.Config(vocab=8192, dim=1024, heads=16, layers=8,
                       ffn_mult=4, max_seq=cache, dtype=jnp.bfloat16)
        params = T.init_params(jax.random.key(2), cfg)
        Bd, S0, NEW = 8, 16, total - 16
        prompt = jax.random.randint(jax.random.key(3), (Bd, S0), 0,
                                    cfg.vocab, dtype=jnp.int32)

        def run():
            outt = T.generate(params, prompt, NEW, cfg)
            return float(jnp.sum(outt[:, -1]))   # scalar fetch = sync

        run()                                    # compile
        t_dec = min(_t(run) for _ in range(2))
        steps = S0 + NEW - 1                     # scan length (prefill+gen)
        return {"decode_kvcache_total_s": t_dec,
                "decode_kvcache_tokens_per_s": Bd * steps / t_dec,
                "decode_kvcache_batch": Bd,
                "decode_kvcache_steps": steps}

    _guarded(details, "decode_kvcache", cfg_decode, timeout_s=600)

    # ---- extra: reshard planner (chunked collective redistribution) ------
    # Three legs of the layout-aware reshard planner: the even→even
    # transpose repartition (all_to_all lowering on >1 chip, noop/1-chip
    # degenerate otherwise — strategy banked alongside the time so the
    # numbers are attributable), the uneven-layout in-place fill (now
    # emitted straight into blocked physical form: zero redistribution)
    # next to a full re-pad rebind, and the incremental slice-mutate
    # (owner-block writes only; the _comm_bytes_est column shows the
    # sub-full-array traffic).
    def cfg_reshard_even():
        from distributedarrays_tpu import layout as L_
        from distributedarrays_tpu.parallel import reshard as R_
        p = len(devs)
        NR = 8192
        src = L_.sharding_for(list(range(p)), (p, 1), (NR, NR))
        dst = L_.sharding_for(list(range(p)), (1, p), (NR, NR))
        x = jax.device_put(jax.random.normal(jax.random.key(11), (NR, NR),
                                             jnp.float32), src)
        plan = R_.plan_reshard(x, dst)

        def once():
            y = R_.reshard(x, dst)
            return float(y[0, 0])          # scalar fetch = sync

        once()                             # compile
        # first timed rep banks immediately: a hang during the
        # remaining reps still leaves a real reshard time (+ bandwidth)
        t_rs = _t(once)
        part = {"reshard_even_s": t_rs}
        if plan.moved_bytes:
            part["reshard_even_gbps"] = plan.moved_bytes / t_rs / 1e9
        bank_partial("reshard_even", **part)
        t_rs = min([t_rs] + [_t(once) for _ in range(2)])
        from distributedarrays_tpu.ops import pallas_collectives as P_
        rdma = P_.rdma_mode()
        out = {
            "reshard_even_n": NR,
            "reshard_even_nranks": p,
            "reshard_even_strategy": plan.strategy,
            "reshard_even_nchunks": plan.nchunks,
            "reshard_even_plan_moved_mb": plan.moved_bytes / 2**20,
            "reshard_even_dispatch": rdma or "xla",
            "reshard_even_s": t_rs,
        }
        if rdma and plan.strategy == "all_to_all":
            lshape = tuple(s // p if d == plan.src_dim else s
                           for d, s in enumerate(plan.shape))
            nc, csrc = P_.a2a_chunks_for(lshape, "float32", p,
                                         plan.src_dim)
            out["reshard_even_rdma_chunks"] = nc
            out["reshard_even_rdma_chunks_source"] = csrc
        if plan.moved_bytes:
            out["reshard_even_gbps"] = plan.moved_bytes / t_rs / 1e9
        # repeated same-pair planning must hit the plan cache
        st0 = R_.plan_stats()
        for _ in range(4):
            R_.plan_reshard(x, dst)
        out["reshard_plan_cache_hits_delta"] = \
            R_.plan_stats()["hits"] - st0["hits"]
        return out

    _guarded(details, "reshard_even", cfg_reshard_even)

    def cfg_reshard_uneven():
        p = len(devs)
        NU = 4096 * 2048 + 37              # indivisible -> blocked-padded
        d = dat.distribute(np.zeros(NU, np.float32),
                           procs=list(range(p)), dist=[p])
        try:
            def fill_once():
                d.fill_(3.0)
                return float(d.garray_padded[0])

            from distributedarrays_tpu import telemetry as _tm2
            fill_once()                    # compile
            rb0 = _tm2.comm_bytes("reshard")
            t_fill = min(_t(fill_once) for _ in range(3))
            fill_reshard_bytes = _tm2.comm_bytes("reshard") - rb0

            host = np.ones(NU, np.float32)

            def repad_once():
                dat.copyto_(d, host)       # logical -> blocked re-pad
                return float(d.garray_padded[0])

            repad_once()
            t_repad = min(_t(repad_once) for _ in range(2))
            return {
                "reshard_uneven_n": NU,
                "reshard_uneven_nranks": p,
                "reshard_uneven_fill_s": t_fill,
                "reshard_uneven_fill_reshard_bytes": fill_reshard_bytes,
                "reshard_uneven_repad_s": t_repad,
            }
        finally:
            d.close()

    _guarded(details, "reshard_uneven", cfg_reshard_uneven)

    def cfg_reshard_mutate():
        p = len(devs)
        NU = 4096 * 2048 + 37
        d = dat.distribute(np.zeros(NU, np.float32),
                           procs=list(range(p)), dist=[p])
        try:
            # one small interior window: the incremental path writes only
            # the owner blocks' physical regions
            lo = NU // (2 * max(p, 1))
            w = 4096
            v = np.full(w, 5.0, np.float32)

            def mutate_once():
                d[lo:lo + w] = v
                return float(d.garray_padded[0])

            from distributedarrays_tpu import telemetry as _tm2
            mutate_once()                  # compile
            rb0 = _tm2.comm_bytes("reshard")
            t_mut = min(_t(mutate_once) for _ in range(3))
            # reshard-kind bytes for the timed mutations alone: the
            # owner-block traffic (vs NU*4 per mutation pre-planner)
            rb = _tm2.comm_bytes("reshard") - rb0
            return {
                "reshard_mutate_n": NU,
                "reshard_mutate_window": w,
                "reshard_mutate_s": t_mut,
                "reshard_mutate_touched_frac": w / NU,
                "reshard_mutate_reshard_bytes_per_full": rb / 3 / (NU * 4),
            }
        finally:
            d.close()

    _guarded(details, "reshard_mutate", cfg_reshard_mutate)

    # ---- extra: reshard, multi-axis chain lowering -----------------------
    # The general per-axis collective chain (PR 19) against the
    # device_put baseline it demotes: an 8192² two-axis repartition
    # ((p,1) -> (p/2,2), a single axis-wise all-to-all moving half the
    # array) and a mesh-axis transpose (one block exchange).  Banks the
    # chain strategy and the plan's intra/cross-domain byte split so the
    # row attributes the win to the hierarchical tier.
    def cfg_reshard_multiaxis():
        from distributedarrays_tpu import layout as L_
        from distributedarrays_tpu.parallel import reshard as R_
        from jax.sharding import NamedSharding as _NS, \
            PartitionSpec as _P2
        p = len(devs)
        if p < 4 or p % 2:
            return {"reshard_multiaxis_skipped": f"needs p>=4 even, p={p}"}
        NR = 8192
        src = L_.sharding_for(list(range(p)), (p, 1), (NR, NR))
        dst = L_.sharding_for(list(range(p)), (p // 2, 2), (NR, NR))
        x = jax.device_put(jax.random.normal(jax.random.key(13), (NR, NR),
                                             jnp.float32), src)
        plan = R_.plan_reshard(x, dst)

        def once():
            y = R_.reshard(x, dst)
            return float(y[0, 0])          # scalar fetch = sync

        def baseline():
            y = jax.device_put(x, dst)     # the baseline under measurement
            return float(y[0, 0])

        once(); baseline()                 # compile/warm both arms
        # bank each arm as soon as its first rep lands: the multi-hop
        # row keeps its headline time even if the transpose arm below
        # never gets to run
        t_rs = _t(once)
        part = {"reshard_multiaxis_s": t_rs}
        if plan.moved_bytes:
            part["reshard_multiaxis_gbps"] = plan.moved_bytes / t_rs / 1e9
        bank_partial("reshard_multiaxis", **part)
        t_rs = min([t_rs] + [_t(once) for _ in range(2)])
        t_dp = _t(baseline)
        bank_partial("reshard_multiaxis",
                     reshard_multiaxis_device_put_s=t_dp)
        t_dp = min([t_dp] + [_t(baseline) for _ in range(2)])
        out = {
            "reshard_multiaxis_n": NR,
            "reshard_multiaxis_nranks": p,
            "reshard_multiaxis_strategy": plan.strategy,
            "reshard_multiaxis_steps": ",".join(s[0] for s in plan.steps),
            "reshard_multiaxis_plan_moved_mb": plan.moved_bytes / 2**20,
            "reshard_multiaxis_intra_mb": plan.intra_bytes / 2**20,
            "reshard_multiaxis_cross_mb": plan.cross_bytes / 2**20,
            "reshard_multiaxis_s": t_rs,
            "reshard_multiaxis_device_put_s": t_dp,
        }
        if plan.moved_bytes:
            out["reshard_multiaxis_gbps"] = plan.moved_bytes / t_rs / 1e9
            out["reshard_multiaxis_device_put_gbps"] = \
                plan.moved_bytes / t_dp / 1e9
        # the mesh-axis transpose on the destination's (p/2, 2) mesh
        mesh = L_.mesh_for(list(range(p)), (p // 2, 2))
        tsrc = _NS(mesh, _P2("d0", "d1"))
        tdst = _NS(mesh, _P2("d1", "d0"))
        xt = jax.device_put(jax.random.normal(jax.random.key(17),
                                              (NR, NR), jnp.float32), tsrc)
        tplan = R_.plan_reshard(xt, tdst)

        def tonce():
            y = R_.reshard(xt, tdst)
            return float(y[0, 0])

        tonce()
        t_tr = min(_t(tonce) for _ in range(3))
        out["reshard_multiaxis_transpose_strategy"] = tplan.strategy
        out["reshard_multiaxis_transpose_s"] = t_tr
        out["reshard_multiaxis_transpose_moved_mb"] = \
            tplan.moved_bytes / 2**20
        return out

    _guarded(details, "reshard_multiaxis", cfg_reshard_multiaxis,
             timeout_s=600)

    # ---- extra: ring GEMM, RDMA vs XLA-ppermute paths --------------------
    # The fused Pallas RDMA collective GEMM (pallas_collectives) against
    # the lax ring it replaces: same program shape, same operands, the
    # only delta is who schedules the wire time.  Banks both wall times,
    # the RDMA path's TFLOPS, and the dispatch that actually ran (on a
    # non-TPU platform the "rdma" arm resolves to the lax fallback and
    # the row says so — a no-delta row is evidence, not a failure).
    def cfg_ring_gemm():
        from distributedarrays_tpu.ops import pallas_collectives as _pc
        from distributedarrays_tpu.ops.collective_matmul import \
            allgather_matmul_rhs
        from distributedarrays_tpu.parallel.collectives import (run_spmd,
                                                                spmd_mesh)
        from jax.sharding import PartitionSpec as _P
        from distributedarrays_tpu import telemetry as _tmb
        p = len(devs)
        NG = 2048
        mesh = spmd_mesh(p)
        a = jnp.asarray(np.random.default_rng(21)
                        .standard_normal((NG, NG)), jnp.bfloat16)
        b = jnp.asarray(np.random.default_rng(22)
                        .standard_normal((NG, NG)), jnp.bfloat16)
        specs = (_P("p", None), _P("p", None))
        fns = {}
        for name, arm in (("xla", False), ("rdma", True)):
            fns[name] = run_spmd(
                functools.partial(lambda aa, bb, _arm: allgather_matmul_rhs(
                    aa, bb, "p", rdma=_arm), _arm=arm),
                mesh, specs, _P("p", None))

        def once(fn):
            return float(jnp.sum(fn(a, b)[0, :8]))   # scalar fetch = sync

        # the dispatch that ACTUALLY ran: rdma_mode() alone ignores the
        # kernel-level gates (VMEM budget, dtype) — the trace-time
        # dispatch counter is ground truth, sampled across the compiles
        disp0 = _tmb.counter_value("pallas_collectives.dispatch",
                                   op="ring_allgather_matmul_rhs",
                                   path="rdma")
        for fn in fns.values():
            once(fn)                                 # compile both arms
        armed = _tmb.counter_value("pallas_collectives.dispatch",
                                   op="ring_allgather_matmul_rhs",
                                   path="rdma") > disp0
        rdma = _pc.rdma_mode()
        flops = 2.0 * NG * NG * NG
        # the XLA arm banks the sentinel metric the moment its first rep
        # lands — a wedge in the RDMA arm can no longer void the row
        t_xla = _t(lambda: once(fns["xla"]))
        bank_partial("ring_gemm", ring_gemm_xla_s=t_xla,
                     ring_gemm_xla_tflops=flops / t_xla / 1e12)
        t_xla = min([t_xla]
                    + [_t(lambda: once(fns["xla"])) for _ in range(2)])
        t_rdma = min(_t(lambda: once(fns["rdma"])) for _ in range(3))
        return {
            "ring_gemm_n": NG,
            "ring_gemm_nranks": p,
            "ring_gemm_dispatch": (rdma or "xla") if armed else
                                  ("xla (gated)" if rdma else "xla"),
            "ring_gemm_xla_s": t_xla,
            "ring_gemm_rdma_s": t_rdma,
            "ring_gemm_xla_tflops": flops / t_xla / 1e12,
            "ring_gemm_rdma_tflops": flops / t_rdma / 1e12,
        }

    _guarded(details, "ring_gemm", cfg_ring_gemm)

    # ---- extra: serving layer under synthetic open-loop load -------------
    # The multi-tenant async executor end to end: a resident sharded
    # weight matrix, a batched scoring endpoint, a sequential pass for the
    # unloaded latency baseline, then an open-loop generator offering ~2x
    # the sustainable rate for a fixed window.  Banks sustained admitted
    # req/s, p50/p99 of ADMITTED requests, and the shed fraction — the
    # ROADMAP item 2 acceptance trio.
    def cfg_serve_load():
        from distributedarrays_tpu import serve as _serve
        p = len(devs)
        NSV = 1024
        w = dat.distribute(np.asarray(np.random.default_rng(5)
                                      .standard_normal((NSV, NSV)),
                                      np.float32))
        srv = None
        try:
            g = w.garray

            def ep(xs):
                y = jnp.matmul(jnp.stack([jnp.asarray(x) for x in xs]), g)
                return list(np.asarray(y[:, 0]))

            cfg = _serve.ServeConfig(max_batch=8, flush_s=0.002,
                                     max_queue=32, tenant_rate=1e9,
                                     tenant_burst=1e9)
            srv = _serve.Server(cfg)
            srv.register("score", ep)
            x = np.zeros((NSV,), np.float32)
            srv.submit("score", x).result(timeout=60)      # compile
            lats = []
            for _ in range(30):                            # unloaded pass
                t0 = time.monotonic()
                srv.submit("score", x).result(timeout=60)
                lats.append(time.monotonic() - t0)
            lats.sort()
            # same index formula as the loaded percentile below, so the
            # banked loaded-vs-unloaded comparison is one statistic
            p99_unloaded = lats[int(0.99 * (len(lats) - 1))]
            batch_s = max(srv.stats()["latency_p50_s"], 1e-4)
            sustainable = cfg.max_batch / batch_s
            interval = 1.0 / (2.0 * sustainable)
            window_s = 3.0
            # submit→resolve latency per admitted request, captured by a
            # done-callback at resolution time (collecting .result() after
            # the window would only time inter-completion gaps)
            import threading as _threading
            futs, shed, loaded = [], 0, []
            _lat_lock = _threading.Lock()

            def _mark(t0):
                def cb(_f):
                    dt = time.monotonic() - t0
                    with _lat_lock:
                        loaded.append(dt)
                return cb

            t_start = time.monotonic()
            while time.monotonic() - t_start < window_s:
                try:
                    t0 = time.monotonic()
                    f = srv.submit("score", x)
                    f.add_done_callback(_mark(t0))
                    futs.append(f)
                except _serve.Overloaded:
                    shed += 1
                time.sleep(interval)
            for f in futs:
                f.result(timeout=60)
            duration = time.monotonic() - t_start
            loaded.sort()
            offered = len(futs) + shed
            return {
                "serve_load_nranks": p,
                "serve_load_offered_rps": offered / duration,
                "serve_load_admitted_rps": len(futs) / duration,
                "serve_load_shed_frac": shed / max(offered, 1),
                "serve_load_p50_s": loaded[len(loaded) // 2] if loaded
                else 0.0,
                "serve_load_p99_s": loaded[int(0.99 * (len(loaded) - 1))]
                if loaded else 0.0,
                "serve_load_p99_unloaded_s": p99_unloaded,
            }
        finally:
            if srv is not None:
                srv.close()
            w.close()

    _guarded(details, "serve_load", cfg_serve_load, timeout_s=300)

    # ---- extra: the decode service under open-loop token load ------------
    # The paged-KV continuous-batching engine end to end: a warm pass
    # measures the single-stream token rate, then an open-loop generator
    # offers ~2x the engine's batch-sustainable sequence rate for a fixed
    # window.  Banks offered vs sustained tokens/s (and the at-SLO rate),
    # TTFT p50/p99, per-token latency p50/p99, the shed fraction, and the
    # KV ledger's HBM peak — the decode-service acceptance row.
    def cfg_serve_decode():
        import threading as _threading

        from distributedarrays_tpu import serve as _serve
        from distributedarrays_tpu.telemetry import memory as _tmem
        model = _serve.TinyLM()
        max_new = 16
        eng = _serve.DecodeEngine(
            model,
            _serve.PagedKVCache(_serve.KVCacheConfig(
                heads=model.heads, head_dim=model.head_dim,
                page_tokens=16, block_pages=4, max_pages=512)),
            _serve.DecodeConfig(max_new_tokens=max_new, poll_s=0.001,
                                max_sequences=64, token_budget=512,
                                # prompts below the floor prefill via the
                                # exact reference path: the row measures
                                # scheduler+cache throughput, not ring
                                # collectives (ring_hop/ring_train own
                                # those); CPU-harness rendezvous stalls
                                # would otherwise drown the token rate
                                min_ring_tokens=64,
                                default_deadline_s=120.0))
        rng = np.random.default_rng(7)

        def _prompt():
            return rng.integers(0, model.vocab, size=32).tolist()

        rec_lock = _threading.Lock()
        ttfts, gaps = [], []
        # KV peak is ledger-relative: earlier configs' still-live buffers
        # must not masquerade as cache bytes
        base_bytes = _tmem.live_bytes()
        kv_peak = [0]
        stop = _threading.Event()

        def _monitor():
            while not stop.is_set():
                kv_peak[0] = max(kv_peak[0],
                                 _tmem.live_bytes() - base_bytes)
                time.sleep(0.002)

        def _tracked_submit():
            t0 = time.monotonic()
            last = [t0]

            def _cb(kind, _v):
                if kind != "token":
                    return
                now = time.monotonic()
                with rec_lock:
                    (ttfts if last[0] == t0 else gaps).append(
                        now - last[0])
                    last[0] = now

            s = eng.submit(_prompt())
            s.add_listener(_cb)
            return s

        try:
            # warm single-stream pass: the unloaded token rate and the
            # SLO.  The first sequence pays every compile/first-touch
            # cost; the SECOND is the steady-state rate
            eng.submit(_prompt()).result(timeout=120)
            t0 = time.monotonic()
            eng.submit(_prompt()).result(timeout=120)
            seq_s = max(time.monotonic() - t0, 1e-4)
            tok_s_single = (max_new) / seq_s
            slo_s = 20.0 * (seq_s / max_new)   # per-token latency bound
            # the unloaded rate and the SLO it implies are complete
            # measurements the moment the warm pass returns — bank them
            # before the 3s open-loop window (the part that wedges)
            bank_partial("serve_decode",
                         serve_decode_single_stream_tokens_per_s=
                         tok_s_single,
                         serve_decode_slo_s=slo_s)
            sustainable_seqs = eng.config.max_decode_batch / seq_s
            interval = 1.0 / (2.0 * sustainable_seqs)
            window_s = 3.0
            mon = _threading.Thread(target=_monitor, daemon=True)
            mon.start()
            streams, shed = [], 0
            t_start = time.monotonic()
            while time.monotonic() - t_start < window_s:
                try:
                    streams.append(_tracked_submit())
                except _serve.Overloaded:
                    shed += 1
                time.sleep(interval)
            for s in streams:
                s.result(timeout=120)
            duration = time.monotonic() - t_start
            stop.set()
            mon.join(2.0)
            with rec_lock:
                tt = sorted(ttfts)
                gp = sorted(gaps)
            delivered = sum(len(s.tokens) for s in streams)
            within = len([g for g in gp if g <= slo_s]) + \
                len([t for t in tt if t <= slo_s])
            offered = len(streams) + shed
            st = eng.stats()["cache"]
            return {
                "serve_decode_nranks": len(devs),
                "serve_decode_single_stream_tokens_per_s": tok_s_single,
                "serve_decode_offered_tokens_per_s":
                    offered * (max_new + 1) / duration,
                "serve_decode_tokens_per_s": delivered / duration,
                "serve_decode_slo_s": slo_s,
                "serve_decode_at_slo_tokens_per_s": within / duration,
                "serve_decode_ttft_p50_s": tt[len(tt) // 2] if tt else 0.0,
                "serve_decode_ttft_p99_s":
                    tt[int(0.99 * (len(tt) - 1))] if tt else 0.0,
                "serve_decode_token_p50_s": gp[len(gp) // 2] if gp
                else 0.0,
                "serve_decode_token_p99_s":
                    gp[int(0.99 * (len(gp) - 1))] if gp else 0.0,
                "serve_decode_shed_frac": shed / max(offered, 1),
                "serve_decode_kv_hbm_peak_bytes": kv_peak[0],
                "serve_decode_evictions": st["evictions"],
            }
        finally:
            stop.set()
            eng.close()

    _guarded(details, "serve_decode", cfg_serve_decode, timeout_s=300)

    # ---- train_step: the fault-tolerant data-parallel trainer ------------
    def cfg_train_step():
        from distributedarrays_tpu import telemetry as _tmt
        from distributedarrays_tpu.ops import pallas_collectives as P_
        from distributedarrays_tpu.telemetry import perf as _perf
        from distributedarrays_tpu.train import Trainer, adam, mlp_task
        p = len(devs)
        task = mlp_task(sizes=(256, 512, 256), batch_size=32 * p)
        tr = Trainer(task, adam(lr=1e-3), seed=0)
        try:
            tr.step_once()                 # compile + first state layout
            t_step = _t(tr.step_once)
            # the step time (and its TFLOPS) banks after ONE timed step:
            # the overlap analysis below needs four more and telemetry
            # event parsing — none of which should hold the row hostage
            bank_partial("train_step", train_step_s=t_step,
                         train_step_tflops=task.step_flops(
                             task.batch_size) / t_step / 1e12)
            t_step = min([t_step] + [_t(tr.step_once) for _ in range(4)])
            # grad-sync overlap from the measured train.step timelines
            # of exactly the timed steps: the event buffer is a bounded
            # deque, so select by step label (the last 5 = the timed
            # ones) rather than by index offset into a rotating ring
            steps_ov = _perf.train_step_overlap(_tmt.events())[-5:]
            ov = (sum(o["overlap_frac"] for o in steps_ov)
                  / len(steps_ov)) if steps_ov else 0.0
            # dispatch provenance from the step spans themselves (the
            # trainer labels the path its kernels ACTUALLY took, gates
            # included), falling back to the armed mode
            dispatch = (steps_ov[-1].get("dispatch") if steps_ov
                        else None) or P_.rdma_mode() or "xla"
            return {
                "train_step_nranks": p,
                "train_step_batch": task.batch_size,
                "train_step_dispatch": dispatch,
                "train_step_overlap_frac": round(ov, 4),
                "train_step_tflops":
                    task.step_flops(task.batch_size) / t_step / 1e12,
                "train_step_s": t_step,
            }
        finally:
            tr.close()

    _guarded(details, "train_step", cfg_train_step)

    # ---- extra: distributed sort over 1e7 elements -----------------------
    def cfg_sort():
        from distributedarrays_tpu.ops.sort import dsort
        VS = dat.drand((10_000_000,))

        def sort_once():
            s = dsort(VS)
            # force completion with a scalar fetch
            v = float(s.garray[-1])
            s.close()
            return v

        sort_once()                       # compile
        t_sort = min(_t(sort_once) for _ in range(2))
        VS.close()
        return {"sort_1e7_s": t_sort,
                "sort_1e7_melem_per_s": 1e7 / t_sort / 1e6}

    _guarded(details, "sort", cfg_sort)

    # ---- solver: CG time-to-tolerance on the 2-D Poisson system ----------
    # the second hardware-meaningful number beyond GEMM: an HBM-bound
    # iteration (5-point stencil matvec + BLAS-1 sweeps), reported as
    # achieved GB/s against the spmv cost stamp.  Iteration count and
    # final residual publish as partials the moment the first solve
    # converges, so a timeout during the timing reps still banks them.
    def cfg_cg_poisson():
        from distributedarrays_tpu import solvers
        from distributedarrays_tpu.telemetry import perf as _perf
        NP = 1024
        op = solvers.StencilOperator((NP, NP))
        procs, pdist = op.vector_layout()
        rhs = np.random.default_rng(7).standard_normal(
            (NP, NP)).astype(np.float32)
        b = dat.distribute(rhs, procs=procs, dist=list(pdist))
        try:
            def solve_once():
                # iterations grow ~2.5*NP on this system (~2600 at 1024);
                # the cap is headroom, not the expected count
                r = solvers.cg(op, b, tol=1e-6, maxiter=6000)
                r.x.close()
                return r

            res = solve_once()           # compile + correctness probe
            bank_partial("cg_poisson",
                         cg_poisson_iters=res.iterations,
                         cg_poisson_residual=res.residual)
            if not res.converged:
                raise RuntimeError(
                    f"cg outcome {res.outcome} after {res.iterations} iters")
            t_solve = _t(solve_once)
            # the first timed solve is already a real time-to-tolerance:
            # bank it before the confirmation rep
            bank_partial("cg_poisson", cg_poisson_time_s=t_solve)
            t_solve = min(t_solve, _t(solve_once))
            # per-iteration HBM traffic: the stamped spmv volume plus ~10
            # whole-vector passes of BLAS-1 (r/p/x/Ap reads and writes)
            per_iter = (_perf.spmv_cost(5 * NP * NP, NP * NP, 4,
                                        index_itemsize=0)["bytes_hbm"]
                        + 10 * NP * NP * 4)
            return {
                "cg_poisson_iters": res.iterations,
                "cg_poisson_residual": res.residual,
                "cg_poisson_time_s": t_solve,
                "cg_poisson_gbps":
                    res.iterations * per_iter / t_solve / 1e9,
            }
        finally:
            b.close()

    _guarded(details, "cg_poisson", cfg_cg_poisson, timeout_s=600)

    # ---- last (riskiest): true-f32 GEMM (precision=HIGHEST) --------------
    # attempted after everything is banked, under a thread timeout: a
    # wedged remote compile must not cost the run its other numbers.
    def highest():
        t, L = _periter(gemm_chain_at(jax.lax.Precision.HIGHEST), L0=16)
        return {"gemm_4096_f32_highest_s_per_iter": t,
                "gemm_4096_f32_highest_gflops": 2 * N**3 / t / 1e9}

    _guarded(details, "gemm_f32_highest", highest, timeout_s=600)

    # the 16k f32-HIGHEST pass (the BASELINE config-3 metric), same guard
    def highest16():
        t, L = _periter(gemm16_chain_at(jax.lax.Precision.HIGHEST), L0=1)
        return {f"{tag}_f32_highest_s_per_iter": t,
                f"{tag}_f32_highest_gflops": 2 * K16**3 / t / 1e9}

    _guarded(details, f"{tag}_f32_highest", highest16, timeout_s=600)

    # a DAT_BENCH_ONLY entry that matched nothing is a typo that would
    # otherwise silently cost a short hardware window its target number —
    # surface it in the details AND on stderr
    unmatched = sorted(_ONLY - _SEEN_LABELS)
    if unmatched:
        details["bench_only_unmatched_labels"] = unmatched
        details["bench_only_known_labels"] = sorted(_SEEN_LABELS)
        print(f"bench: DAT_BENCH_ONLY entries matched no config: "
              f"{unmatched}; known labels: {sorted(_SEEN_LABELS)}",
              file=sys.stderr)
        _save(details)

    # bounded cleanup (headline already out)
    _run_with_timeout(dat.d_closeall, 60)
    if _FAILED_ROWS:
        print(f"bench: rows failed: {sorted(set(_FAILED_ROWS))}",
              file=sys.stderr)
    sys.stdout.flush()
    sys.stderr.flush()
    if any(k.endswith("_orphan_running") for k in details):
        # a timed-out config left a daemon thread stuck inside the XLA
        # runtime; normal interpreter teardown can SIGABRT through it.
        # Everything is printed and persisted — exit hard (a timed-out
        # row is a failed row: non-zero).
        os._exit(1)
    return 1 if _FAILED_ROWS else 0


if __name__ == "__main__":
    _parse_args()
    sys.exit(main())
