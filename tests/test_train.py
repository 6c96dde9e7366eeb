"""Fault-tolerant trainer suite: deterministic training, checkpoint
resume, straggler detection, per-step deadlines, and the chaos soak
acceptance — a seeded device kill mid-epoch PLUS one corrupted
checkpoint shard, after which the run must complete on survivors with a
post-resume loss trajectory bit-identical to a fault-free run restarted
from the same verified step.
"""

import os
import shutil

import numpy as np
import pytest

import distributedarrays_tpu as dat
from distributedarrays_tpu import telemetry as tm
from distributedarrays_tpu.resilience import elastic, faults, recovery
from distributedarrays_tpu.telemetry import flight
from distributedarrays_tpu.telemetry import memory as tmem
from distributedarrays_tpu.train import (DeadRankError, StragglerDetector,
                                         Trainer, adam, mlp_task, sgd,
                                         transformer_task)
from distributedarrays_tpu.utils.checkpoint import CheckpointManager


@pytest.fixture(autouse=True)
def _clean_chaos():
    """Fault injection disarmed, elastic manager pristine, flight
    recorder reset around every test (process-wide singletons)."""
    faults.clear()
    elastic.manager().reset()
    flight._reset()
    yield
    faults.clear()
    elastic.manager().reset()
    flight._reset()


def _fast_policy(**kw):
    kw.setdefault("base_delay", 0.005)
    kw.setdefault("max_delay", 0.02)
    return recovery.RetryPolicy(**kw)


def _trainer(tmp_path=None, task=None, save_every=2, **kw):
    kw.setdefault("policy", _fast_policy())
    kw.setdefault("seed", 0)
    return Trainer(task or mlp_task(batch_size=56),
                   ckpt_dir=None if tmp_path is None else tmp_path,
                   save_every=save_every, **kw)


# ---------------------------------------------------------------------------
# plain training: determinism, optimizers, tasks
# ---------------------------------------------------------------------------


def test_fit_decreases_loss_and_drains():
    with _trainer() as t:
        res = t.fit(6)
    assert len(res["losses"]) == 6
    assert res["losses"][-1] < res["losses"][0]
    assert dat.live_ids() == []
    assert tmem.live_bytes() == 0


def test_fit_is_deterministic_across_runs():
    with _trainer() as a:
        ra = a.fit(5)
    with _trainer() as b:
        rb = b.fit(5)
    assert ra["losses"] == rb["losses"]       # bitwise float equality


def test_sgd_and_momentum_and_adam_all_train():
    for opt in (sgd(lr=5e-2), sgd(lr=5e-2, momentum=0.9), adam(lr=1e-2)):
        with _trainer(optimizer=opt) as t:
            res = t.fit(5)
        assert res["losses"][-1] < res["losses"][0], opt


def test_transformer_task_trains():
    task = transformer_task(vocab=32, dim=16, heads=2, layers=1, seq=8,
                            batch_size=16)
    with _trainer(task=task, optimizer=adam(lr=3e-3)) as t:
        res = t.fit(4)
    assert res["losses"][-1] < res["losses"][0]


def test_uneven_batch_and_params_pad_cleanly():
    # batch 30 over 4 ranks pads to 32 with weight-0 rows, and the
    # 66-element flat parameter vector pads to 68 — neither padding may
    # change the math vs the unpadded single-rank run of the same task
    task = mlp_task(sizes=(5, 7, 3), batch_size=30)
    with _trainer(task=task, ranks=[0, 1, 2, 3]) as t4, \
            _trainer(task=task, ranks=[0]) as t1:
        l4 = t4.fit(3)["losses"]
        l1 = t1.fit(3)["losses"]
    np.testing.assert_allclose(l4, l1, rtol=1e-5)


def test_checkpoint_resume_matches_uninterrupted(tmp_path):
    with _trainer(tmp_path / "a", save_every=2) as t:
        full = t.fit(6)["losses"]
    # run 4 steps, reopen, run to 6: the tail must match bitwise
    with _trainer(tmp_path / "b", save_every=2) as t1:
        t1.fit(4)
    with _trainer(tmp_path / "b", save_every=2) as t2:
        res = t2.fit(6)
    assert res["start"] == 4
    assert res["losses"] == full[4:]


def test_resume_with_different_optimizer_is_safe(tmp_path):
    # sgd checkpoint, adam resume: the moments are MISSING — a clear
    # error naming the optimizer mismatch, restored DArrays closed
    with _trainer(tmp_path / "s", optimizer=sgd(lr=1e-2)) as t:
        t.fit(2)
    t2 = _trainer(tmp_path / "s", optimizer=adam(lr=1e-2))
    with pytest.raises(ValueError, match="different optimizer"):
        t2.fit(4)
    t2.close()
    assert dat.live_ids() == []
    # adam checkpoint, sgd resume: surplus moments are discarded
    # (closed, not leaked) and the params-only resume proceeds
    with _trainer(tmp_path / "a", optimizer=adam(lr=1e-2)) as t3:
        t3.fit(2)
    with _trainer(tmp_path / "a", optimizer=sgd(lr=1e-2)) as t4:
        res = t4.fit(4)
    assert res["start"] == 2 and len(res["losses"]) == 2
    assert dat.live_ids() == []


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------


def test_straggler_detector_budget_math():
    det = StragglerDetector(factor=2.0, min_budget_s=0.1, warmup=3)
    assert det.budget() is None               # warmup: no budget yet
    assert det.observe(5.0) is False          # un-judged during warmup
    for _ in range(3):
        det.observe(0.01)
    b = det.budget()
    assert b == pytest.approx(2.0 * 5.0)      # p99 == the max of window
    assert det.observe(b + 1.0) is True
    assert det.observe(0.01) is False


def test_straggler_probe_confirms_dead_rank_and_recovers(tmp_path):
    # a hang spec with an explicit device: the step completes slowly AND
    # the device joins the simulated-down set — the straggler budget
    # trips, the probe confirms the death, and recovery restores +
    # shrinks + recomputes deterministically
    s0 = tm.counter_value("train.stragglers")
    r0 = tm.counter_value("recovery.retries", verdict="device_loss")
    # the budget is 3x the slowest earlier step, and the first steps
    # compile: the hang has to outlast that on a slow or loaded host
    # (at 0.6 s the budget was missed whenever a step took 0.2 s)
    faults.configure(plan=[
        {"site": "train.step", "match": {"step": 6}, "action": "hang",
         "hang_s": 2.0, "at": 1, "count": 1, "device": 2}], seed=7)
    det = StragglerDetector(factor=3.0, min_budget_s=0.3, warmup=3)
    with _trainer(tmp_path, straggler=det) as t:
        res = t.fit(8)
    assert tm.counter_value("train.stragglers") == s0 + 1
    assert tm.counter_value("recovery.retries",
                            verdict="device_loss") == r0 + 1
    assert 2 not in elastic.manager().live_ranks()
    assert len(res["losses"]) == 8
    assert dat.live_ids() == []


def test_closed_trainer_refuses_fit_and_step_once():
    t = _trainer()
    t.fit(1)
    t.close()
    with pytest.raises(RuntimeError, match="closed"):
        t.fit(2)
    with pytest.raises(RuntimeError, match="closed"):
        t.step_once()
    assert dat.live_ids() == []               # close() freed everything


def test_pinned_ranks_all_dead_raises_not_migrates():
    # the pin is a hard boundary: if every pinned rank is down, the
    # trainer must fail, not silently migrate onto excluded devices
    with _trainer(ranks=[2, 3]) as t:
        elastic.manager().mark_down(2)
        elastic.manager().mark_down(3)
        with pytest.raises(RuntimeError, match="no pinned rank"):
            t.fit(1)


def test_dead_rank_error_classifies_device_loss():
    e = DeadRankError([3], budget_s=0.5, dur_s=2.0)
    assert recovery.classify(e) == "device_loss"
    assert "device lost" in str(e) and "[3]" in str(e)


# ---------------------------------------------------------------------------
# per-step wall-clock deadline (RetryPolicy.max_elapsed_s)
# ---------------------------------------------------------------------------


def test_max_elapsed_s_stops_retrying():
    calls = []

    def boom():
        calls.append(1)
        import time
        time.sleep(0.05)
        raise ValueError("flaky")

    g0 = tm.counter_value("recovery.deadline_exceeded",
                          verdict="transient")
    with pytest.raises(ValueError):
        recovery.run_with_recovery(
            boom, policy=recovery.RetryPolicy(
                max_retries=100, base_delay=0.001, max_delay=0.002,
                max_elapsed_s=0.15))
    # the retry count alone allowed 100 retries; the wall-clock budget
    # cut it off after a handful
    assert 1 < len(calls) < 20
    assert tm.counter_value("recovery.deadline_exceeded",
                            verdict="transient") == g0 + 1


def test_backoff_never_sleeps_past_remaining_budget():
    import time
    attempts = []

    def boom():
        attempts.append(time.monotonic())
        raise ValueError("flaky")

    t0 = time.monotonic()
    with pytest.raises(ValueError):
        recovery.run_with_recovery(
            boom, policy=recovery.RetryPolicy(
                max_retries=50, base_delay=10.0, max_delay=10.0,
                jitter=0.0, max_elapsed_s=0.2))
    # base_delay=10s would sleep 10s on the first retry; the budget
    # clamps it, so the whole loop ends within ~the budget
    assert time.monotonic() - t0 < 2.0
    assert len(attempts) >= 2                 # it DID retry (clamped sleep)


def test_delay_clamps_to_remaining():
    pol = recovery.RetryPolicy(base_delay=10.0, max_delay=10.0,
                               jitter=0.0)
    assert pol.delay(0, remaining_s=0.25) == pytest.approx(0.25)
    assert pol.delay(0, remaining_s=-1.0) == 0.0
    assert pol.delay(0, remaining_s=None) == pytest.approx(10.0)


def test_trainer_step_deadline_bounds_recovery(tmp_path):
    # an always-raising grad.sync makes the step unrecoverable; the
    # per-step deadline must cut the retry loop off
    faults.configure(plan=[
        {"site": "grad.sync", "action": "raise", "at": 1, "count": -1}],
        seed=3)
    with _trainer(tmp_path, step_deadline_s=0.5,
                  policy=_fast_policy(max_retries=10_000)) as t:
        import time
        t0 = time.monotonic()
        with pytest.raises(faults.InjectedFault):
            t.fit(2)
        assert time.monotonic() - t0 < 30.0   # not 10k retries


# ---------------------------------------------------------------------------
# checkpoint integrity: corrupt action, CRC verification, quarantine
# ---------------------------------------------------------------------------


def test_corrupt_restore_quarantines_and_falls_back(tmp_path):
    A = np.arange(64, dtype=np.float32).reshape(8, 8)
    mgr = CheckpointManager(tmp_path, async_save=False)
    d = dat.distribute(A.copy())
    mgr.save(0, {"x": d, "tag": "old"})
    mgr.save(1, {"x": d, "tag": "new"})
    d.close()
    q0 = tm.counter_value("checkpoint.quarantines")
    f0 = tm.counter_value("checkpoint.restore_fallbacks")
    faults.configure(plan=[
        {"site": "checkpoint.read", "action": "corrupt", "at": 1,
         "count": 1}], seed=11)
    out = mgr.restore()
    assert out["tag"] == "old"                # fell back past step 1
    np.testing.assert_array_equal(np.asarray(out["x"]), A)
    out["x"].close()
    assert tm.counter_value("checkpoint.quarantines") == q0 + 1
    assert tm.counter_value("checkpoint.restore_fallbacks") == f0 + 1
    assert mgr.steps() == [0]                 # step 1 no longer restorable
    assert (tmp_path / ".quarantine_step_00000001").exists()
    mgr.close()


def test_corrupt_byte_flips_are_seeded_deterministic(tmp_path):
    def corrupted_bytes(seed):
        faults.configure(plan=[
            {"site": "checkpoint.read", "action": "corrupt", "at": 1,
             "count": 1, "flips": 4}], seed=seed)
        spec = faults.decide("checkpoint.read", store="npz", path="x")
        arrays = {"a0": np.zeros(64, np.uint8), "a1": np.zeros(8, np.uint8)}
        out = faults.corrupt_arrays(spec, arrays)
        assert any((out[k] != arrays[k]).any() for k in arrays)
        return {k: out[k].tobytes() for k in out}

    assert corrupted_bytes(5) == corrupted_bytes(5)
    assert corrupted_bytes(5) != corrupted_bytes(6)


def test_explicit_step_restore_stays_strict_on_corruption(tmp_path):
    from distributedarrays_tpu.utils.checkpoint import \
        CheckpointIntegrityError
    mgr = CheckpointManager(tmp_path, async_save=False)
    mgr.save(3, {"v": np.arange(6)})
    faults.configure(plan=[
        {"site": "checkpoint.read", "action": "corrupt", "at": 1,
         "count": 1}], seed=2)
    with pytest.raises(CheckpointIntegrityError):
        mgr.restore(3)
    mgr.close()


def test_on_disk_corruption_detected_without_fault_harness(tmp_path):
    # real disk rot: flip one byte INSIDE the npz payload (past the zip
    # local header + npy header, well before the central directory) —
    # no fault plan armed, the CRC alone must catch it
    from distributedarrays_tpu.utils.checkpoint import \
        CheckpointIntegrityError, load, save
    save(tmp_path / "c", {"v": np.arange(100, dtype=np.int64)})
    npz = tmp_path / "c" / "arrays.npz"
    raw = bytearray(npz.read_bytes())
    raw[400] ^= 0xFF
    npz.write_bytes(bytes(raw))
    # the zip container's own member CRC may fire first (BadZipFile);
    # either way the restore MUST fail — and a CheckpointManager treats
    # both identically (restore_fallback).  Our CRC layer is the one
    # that still fires for the seeded read-corruption path and for
    # stores without container checksums.
    import zipfile
    with pytest.raises((CheckpointIntegrityError, zipfile.BadZipFile,
                        OSError)):
        load(tmp_path / "c")


def test_pre_integrity_checkpoints_still_load(tmp_path):
    # a checkpoint whose metadata has no integrity section (older
    # writer) restores unverified rather than failing
    import json
    from distributedarrays_tpu.utils.checkpoint import load, save
    save(tmp_path / "c", {"v": np.arange(4)})
    meta = json.loads((tmp_path / "c" / "dartpu_meta.json").read_text())
    del meta["integrity"]
    (tmp_path / "c" / "dartpu_meta.json").write_text(json.dumps(meta))
    out = load(tmp_path / "c")
    np.testing.assert_array_equal(out["v"], np.arange(4))


def test_all_corrupt_store_surfaces_through_recovery(tmp_path):
    # every published step corrupt: restore() quarantines them all and
    # raises — and recovery must SURFACE that (the cause-chained
    # FileNotFoundError), never silently degrade to a live-state retry
    # just because quarantine emptied steps()
    mgr = CheckpointManager(tmp_path, async_save=False, max_to_keep=None)
    mgr.save(1, {"v": np.arange(8)})
    mgr.save(2, {"v": np.arange(8)})
    faults.configure(plan=[
        {"site": "checkpoint.read", "action": "corrupt", "at": 1,
         "count": -1}], seed=4)

    def boom():
        raise ValueError("flaky")

    with pytest.raises(FileNotFoundError, match="no restorable"):
        recovery.run_with_recovery(
            boom, policy=_fast_policy(), checkpoints=mgr,
            restore_fn=lambda tree: None)
    assert mgr.steps() == []                  # all quarantined
    mgr.close()


def test_discard_from_rewinds_timeline(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=False, max_to_keep=None)
    for s in (2, 4, 6):
        mgr.save(s, {"s": s})
    assert mgr.discard_from(4) == [4, 6]
    assert mgr.steps() == [2]
    mgr.close()


# ---------------------------------------------------------------------------
# fault sites
# ---------------------------------------------------------------------------


def test_train_step_fault_site_device_loss_recovers(tmp_path):
    r0 = tm.counter_value("recovery.retries", verdict="device_loss")
    faults.configure(plan=[
        {"site": "train.step", "match": {"step": 3}, "action":
         "device_loss", "at": 1, "count": 1, "device": 1}], seed=5)
    with _trainer(tmp_path) as t:
        res = t.fit(5)
    assert tm.counter_value("recovery.retries",
                            verdict="device_loss") == r0 + 1
    assert 1 not in elastic.manager().live_ranks()
    assert len(res["losses"]) == 5


def test_grad_sync_fault_site_fires_between_programs(tmp_path):
    hist0 = len(faults.history())
    faults.configure(plan=[
        {"site": "grad.sync", "match": {"step": 1}, "action": "raise",
         "at": 1, "count": 1}], seed=5)
    with _trainer(tmp_path) as t:
        t.fit(3)
    fired = faults.history()[hist0:]
    assert any(f["site"] == "grad.sync" for f in fired)


def test_corrupt_action_is_noop_at_unconsuming_sites():
    faults.configure(plan=[
        {"site": "reshard.chunk", "action": "corrupt", "at": 1,
         "count": 1}], seed=1)
    faults.check("reshard.chunk", strategy="x")   # must not raise


# ---------------------------------------------------------------------------
# the chaos soak acceptance
# ---------------------------------------------------------------------------


def _soak(tmp_path, plan, seed, **kw):
    faults.clear()
    elastic.manager().reset()
    if plan is not None:
        faults.configure(plan=plan, seed=seed)
    t = _trainer(tmp_path, save_every=2, **kw)
    try:
        return t.fit(8), elastic.manager().live_ranks()
    finally:
        t.close()


@pytest.mark.slow
def test_chaos_soak_device_kill_plus_corrupt_shard(tmp_path):
    """The acceptance soak: a seeded plan kills device 3 mid-epoch at
    step 5 AND corrupts the latest checkpoint shard on the recovery
    read.  The run must complete on the 7 survivors, the corrupt step
    must quarantine + fall back (restore_fallback journaled), and the
    post-resume loss trajectory must be bit-identical to a fault-free
    run restarted from the same verified step on the same survivors."""
    plan = [
        {"site": "train.step", "match": {"step": 5},
         "action": "device_loss", "at": 1, "count": 1, "device": 3},
        {"site": "checkpoint.read", "action": "corrupt", "at": 1,
         "count": 1},
    ]
    b0 = flight.crash_bundle_count()
    r0 = tm.counter_value("recovery.retries", verdict="device_loss")
    k0 = tm.counter_value("elastic.shrinks")
    q0 = tm.counter_value("checkpoint.quarantines")
    f0 = tm.counter_value("checkpoint.restore_fallbacks")

    res, survivors = _soak(tmp_path / "chaos", plan, seed=42)

    # completed on survivors: the dead device is out of the live set
    assert survivors == [0, 1, 2, 4, 5, 6, 7]
    assert len(res["losses"]) == 8
    # exactly the expected flight bundles: ONE, for the one device loss
    assert flight.crash_bundle_count() - b0 == 1
    assert tm.counter_value("recovery.retries",
                            verdict="device_loss") == r0 + 1
    assert tm.counter_value("elastic.shrinks") == k0 + 1
    # the corrupt shard quarantined and fell back without operator input
    assert tm.counter_value("checkpoint.quarantines") == q0 + 1
    assert tm.counter_value("checkpoint.restore_fallbacks") == f0 + 1
    assert (tmp_path / "chaos" / ".quarantine_step_00000004").exists()

    # comparison: a fault-free run restarted from the same verified step
    # (2 — step 4 was the corrupted one) on the same survivor set
    faults.clear()
    src, dst = tmp_path / "chaos", tmp_path / "clean"
    shutil.copytree(src, dst,
                    ignore=shutil.ignore_patterns(".quarantine*"))
    for p in sorted(os.listdir(dst)):
        if p.startswith("step_") and int(p[5:]) > 2:
            shutil.rmtree(dst / p)
    with _trainer(dst, save_every=1000, ranks=survivors) as t2:
        res2 = t2.fit(8)
    assert res2["start"] == 2
    # bit-identical loss trajectory from the resume point
    assert res2["losses"] == res["losses"][2:]

    # leak gate: registry and HBM ledger drain (conftest re-asserts)
    assert dat.live_ids() == []
    assert tmem.live_bytes() == 0


@pytest.mark.slow
def test_chaos_soak_replay_is_deterministic(tmp_path):
    plan = [
        {"site": "train.step", "match": {"step": 5},
         "action": "device_loss", "at": 1, "count": 1, "device": 3},
        {"site": "checkpoint.read", "action": "corrupt", "at": 1,
         "count": 1},
    ]
    def _normalized_history():
        # the checkpoint.read site labels carry the (tmp) path — equal
        # up to the run directory, so strip it before comparing
        out = []
        for f in faults.history():
            f = dict(f, labels={k: v for k, v in f["labels"].items()
                                if k != "path"})
            out.append(f)
        return out

    res1, _ = _soak(tmp_path / "a", plan, seed=42)
    h1 = _normalized_history()
    res2, _ = _soak(tmp_path / "b", plan, seed=42)
    h2 = _normalized_history()
    assert res1["losses"] == res2["losses"]
    assert h1 == h2


# ---------------------------------------------------------------------------
# telemetry integration
# ---------------------------------------------------------------------------


def test_train_step_spans_say_what_ran():
    ev0 = len(tm.events())
    with _trainer() as t:
        t.fit(3)
    spans = [e for e in tm.events()[ev0:] if e.get("cat") == "span"]
    steps = [e for e in spans if e.get("name") == "train.step"]
    assert [int(e["labels"]["step"]) for e in steps] == [0, 1, 2]
    for e in steps:
        assert int(e["labels"]["ranks"]) >= 1
        assert e["labels"].get("dispatch") in ("rdma", "xla")
        # the step's two phases are its children: the gradient program
        # (compute) and the reduce-scatter/all-gather sync (comm)
        kids = {(k["name"], k["labels"].get("kind")) for k in spans
                if k.get("parent_id") == e["span_id"]}
        assert {("train.grad", "compute"), ("train.sync", "comm")} <= kids
