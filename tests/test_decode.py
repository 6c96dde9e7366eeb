"""Decode-service suite: paged KV cache residency, continuous batching,
WFQ scheduling, streaming cancellation, eviction + re-prefill
bit-identity, the asyncio bridge — and the decode chaos leg (seeded
device loss mid-decode/mid-prefill resolves every sequence
correct-or-typed with cache pages re-laid onto survivors, a minority
partition drains typed, and the acceptance soak holds the KV ledger
under budget through 2x overload with bit-identical results).
"""

import asyncio
import json
import threading
import time

import numpy as np
import pytest

from distributedarrays_tpu import serve, telemetry as tm
from distributedarrays_tpu.models.ring_attention import (
    reference_attention, ring_attention_prefill)
from distributedarrays_tpu.resilience import domains, elastic, faults, \
    recovery
from distributedarrays_tpu.serve import (Cancelled, DeadlineExceeded,
                                         Draining, Overloaded, Rejected,
                                         ServeError)
from distributedarrays_tpu.serve.decode import _decode_attention
from distributedarrays_tpu.telemetry import export, flight
from distributedarrays_tpu.telemetry import memory as tmem
from distributedarrays_tpu.telemetry.fixtures import telemetry_capture  # noqa: F401 (fixture)
from distributedarrays_tpu.telemetry.summarize import read_journal


@pytest.fixture(autouse=True)
def _clean_serving():
    """Process-wide singletons (fault plan, elastic manager, domain
    topology, flight recorder) start and end pristine."""
    faults.clear()
    elastic.manager().reset()
    domains.reset()
    flight._reset()
    yield
    faults.clear()
    elastic.manager().reset()
    domains.reset()
    flight._reset()


def _fast_policy(**kw):
    kw.setdefault("base_delay", 0.005)
    kw.setdefault("max_delay", 0.02)
    return recovery.RetryPolicy(**kw)


def _model(**kw):
    kw.setdefault("vocab", 32)
    kw.setdefault("heads", 2)
    kw.setdefault("head_dim", 4)
    kw.setdefault("max_pos", 512)
    kw.setdefault("seed", 3)
    return serve.TinyLM(**kw)


def _kv(**kw):
    kw.setdefault("page_tokens", 4)
    kw.setdefault("heads", 2)
    kw.setdefault("head_dim", 4)
    kw.setdefault("block_pages", 2)
    kw.setdefault("max_pages", 64)
    return serve.PagedKVCache(serve.KVCacheConfig(**kw))


def _engine(model=None, cache_kw=None, **kw):
    model = model or _model()
    ck = dict(cache_kw or {})
    ck.setdefault("heads", model.heads)
    ck.setdefault("head_dim", model.head_dim)
    kw.setdefault("poll_s", 0.002)
    kw.setdefault("use_ring_prefill", False)
    return serve.DecodeEngine(model, _kv(**ck), serve.DecodeConfig(**kw),
                              policy=_fast_policy())


def _oracle(model, prompt, max_new, *, use_ring=False, procs=None,
            min_ring_tokens=None):
    """Cache-free reference decode: same prefill entry, same decode
    attention, K/V kept in plain numpy — what the engine must match
    bit-for-bit through paging, eviction and rebuild."""
    toks = [int(t) for t in np.asarray(prompt).reshape(-1)]
    q, k, v = model.qkv(toks, 0)
    if use_ring:
        out = ring_attention_prefill(q, k, v, causal=True, procs=procs,
                                     min_ring_tokens=min_ring_tokens)
    else:
        out = reference_attention(q, k, v, True)
    K = np.asarray(k, np.float32)
    V = np.asarray(v, np.float32)
    gen = [int(np.argmax(model.logits(out[-1])))]
    toks.append(gen[0])
    _, k1, v1 = model.qkv([gen[0]], len(toks) - 1)
    K = np.concatenate([K, k1])
    V = np.concatenate([V, v1])
    while len(gen) < max_new:
        qr, _, _ = model.qkv([toks[-1]], len(toks) - 1)
        t = int(np.argmax(model.logits(_decode_attention(qr[0], K, V))))
        toks.append(t)
        gen.append(t)
        _, k1, v1 = model.qkv([t], len(toks) - 1)
        K = np.concatenate([K, k1])
        V = np.concatenate([V, v1])
    return gen


# ---------------------------------------------------------------------------
# paged KV cache: allocation, round-trip, LRU eviction, typed exhaustion
# ---------------------------------------------------------------------------


def test_kvcache_write_read_roundtrip():
    with _kv() as kv:
        rows = np.arange(10 * 2 * 4, dtype=np.float32).reshape(10, 2, 4)
        kv.ensure(1, 10)
        kv.write(1, 0, rows[:6], rows[:6] * 2)     # page-straddling chunks
        kv.write(1, 6, rows[6:], rows[6:] * 2)
        k, v = kv.read(1)
        np.testing.assert_array_equal(np.asarray(k), rows)
        np.testing.assert_array_equal(np.asarray(v), rows * 2)
        assert kv.ntok(1) == 10
        assert kv.stats()["pages_live"] == kv.pages_for(10) == 3
        kv.release(1)
        assert kv.stats()["pages_live"] == 0 and not kv.has(1)
    assert tmem.live_bytes() == 0


def test_kvcache_ledger_attribution_and_block_reap(telemetry_capture):
    kv = _kv(block_pages=2)
    assert tmem.live_bytes() == 0
    kv.ensure(1, 8, tenant="t0")      # 2 pages -> 1 block in the ledger
    assert tmem.live_bytes() > 0
    telemetry_capture.assert_span("serve.kv")     # allocation attributed
    sp = telemetry_capture.spans("serve.kv")[0]
    assert sp["labels"]["op"] == "alloc_block"
    telemetry_capture.assert_counter("serve.kv.blocks_created", 1)
    kv.release(1)                     # fully-free block reaps eagerly
    assert tmem.live_bytes() == 0
    telemetry_capture.assert_counter("serve.kv.blocks_reaped", 1)
    kv.close()


def test_kvcache_lru_eviction_order():
    with _kv(max_pages=4, block_pages=2) as kv:
        for sid in (1, 2, 3, 4):
            kv.ensure(sid, 1)
        kv.ensure(1, 1)               # touch 1: seq 2 is now the LRU
        evicted = kv.ensure(5, 1)
        assert evicted == [2]
        assert kv.has(1) and not kv.has(2)
        assert kv.stats()["evictions"] == 1


def test_kvcache_pinned_never_evicted_and_typed_exhaustion():
    with _kv(max_pages=2, block_pages=2) as kv:
        kv.ensure(1, 1)
        kv.ensure(2, 1)
        kv.pin(1)
        kv.pin(2)
        with pytest.raises(Overloaded) as ei:
            kv.ensure(3, 1, tenant="t")
        assert ei.value.reason == "kv" and ei.value.retry_after > 0
        kv.unpin(1)
        assert kv.ensure(3, 1) == [1]     # only the unpinned one goes
        assert kv.has(2)


def test_kvcache_rejects_oversized_before_evicting():
    with _kv(max_pages=2, block_pages=2, page_tokens=4) as kv:
        kv.ensure(1, 1)
        with pytest.raises(Rejected) as ei:
            kv.ensure(2, 1000)        # can never fit: typed, no eviction
        assert ei.value.reason == "kv"
        assert kv.has(1)              # no innocent was evicted


def test_kvcache_budget_eviction_and_idle_evictable_bytes():
    # page = 2*4*2*4*4 = 256 B, block (2 pages) = 512 B; budget 2048 at
    # fraction 0.5 -> bound 1024 -> at most two blocks live
    kv = _kv(max_pages=16, block_pages=2, hbm_budget_bytes=2048,
             hbm_evict_fraction=0.5)
    assert kv.page_nbytes == 256
    kv.ensure(1, 8)                   # 2 pages: block 1
    kv.ensure(2, 8)                   # 2 pages: block 2 (at the bound)
    assert tmem.live_bytes() == 1024
    assert kv.idle_evictable_bytes() == 1024
    kv.pin(1)
    assert kv.idle_evictable_bytes() == 512
    evicted = kv.maybe_evict()        # live >= bound: sweep idle LRU
    assert evicted == [2]
    assert tmem.live_bytes() == 512   # seq 2's block reaped
    kv.unpin(1)
    kv.close()
    assert tmem.live_bytes() == 0


# ---------------------------------------------------------------------------
# weighted-fair queuing
# ---------------------------------------------------------------------------


def test_wfq_weight_shares_and_priority_classes():
    q = serve.WeightedFairQueue()
    for i in range(3):                # interleaved arrivals, equal cost
        q.push(("a", i), tenant="a", cost=1.0, weight=1.0)
        q.push(("b", i), tenant="b", cost=1.0, weight=3.0)
    order = [q.pop()[0] for _ in range(6)]
    # SCFQ finish tags: b at 1/3, 2/3, 1; a at 1, 2, 3 — b takes 3 of
    # the first 4 grants (the 1:3 share), a drains afterwards
    assert order[:4].count("b") == 3
    assert order[4:] == ["a", "a"]
    # strict priority classes beat any weight
    q.push(("late", 0), tenant="a", cost=1.0, weight=0.001, priority=-1)
    q.push(("bulk", 0), tenant="b", cost=1.0, weight=100.0)
    assert q.pop()[0] == "late"


def test_engine_wfq_order_and_priority_preemption():
    """Deterministic service order: the loop thread is parked so the
    test turns the scheduler crank itself via ``_round()``."""
    eng = _engine(max_new_tokens=1, max_prefill_seqs=1)
    eng._stop.set()                   # loop thread exits; manual rounds
    done_order: list[str] = []
    try:
        eng.set_weight("b", 3.0)
        streams = []
        for i in range(3):
            for t in ("a", "b"):
                s = eng.submit([3 + i, 7, 2, 9, 1, 4, 8, 5], tenant=t)
                s.add_listener(lambda kind, _v, t=t: done_order.append(t)
                               if kind == "done" else None)
                streams.append(s)
        urgent = eng.submit([9, 9, 9, 9, 9, 9, 9, 9], tenant="a",
                            priority=-1)
        urgent.add_listener(lambda kind, _v: done_order.append("urgent")
                            if kind == "done" else None)
        for _ in range(40):
            if all(s.done() for s in streams) and urgent.done():
                break
            eng._round()
        assert urgent.done() and all(s.done() for s in streams)
    finally:
        eng.close(drain=False)
    # priority class first, then the 1:3 WFQ share within class 0
    assert done_order[0] == "urgent"
    assert done_order[1:5].count("b") == 3
    assert tmem.live_bytes() == 0


# ---------------------------------------------------------------------------
# decode correctness: engine output is bit-identical to the no-cache oracle
# ---------------------------------------------------------------------------


def test_engine_tokens_match_oracle_and_stream_iterates():
    model = _model()
    prompts = [[5, 3, 7, 2, 9, 1, 4], [8, 8, 1], [30, 2, 17, 11]]
    with _engine(model, max_new_tokens=6) as eng:
        streams = [eng.submit(p) for p in prompts]
        for p, s in zip(prompts, streams):
            want = _oracle(model, p, 6)
            assert s.result(timeout=30) == want
            assert list(s) == want            # iteration replays history
            assert s.tokens == want and s.error() is None
        st = eng.stats()
        assert st["sequences"] == 0 and st["cache"]["pages_live"] == 0
    assert tmem.live_bytes() == 0
    assert tm.counter_value("serve.decode.completed",
                            tenant="default") >= 3


def test_ring_prefill_long_prompt_matches_oracle():
    model = _model()
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, model.vocab, size=64).tolist()
    procs = elastic.manager().live_ranks()
    q, k, v = model.qkv(prompt, 0)
    ring = ring_attention_prefill(q, k, v, causal=True, procs=procs)
    ref = reference_attention(q, k, v, True)
    np.testing.assert_allclose(ring, ref, rtol=2e-4, atol=2e-4)
    # below the ring floor the fallback IS the reference — bit-equal
    q2, k2, v2 = model.qkv(prompt[:6], 0)
    np.testing.assert_array_equal(
        ring_attention_prefill(q2, k2, v2, causal=True, procs=procs),
        reference_attention(q2, k2, v2, True))
    with _engine(model, use_ring_prefill=True, max_new_tokens=4) as eng:
        got = eng.submit(prompt).result(timeout=30)
    assert got == _oracle(model, prompt, 4, use_ring=True, procs=procs)
    assert tmem.live_bytes() == 0


def test_eviction_reprefill_bit_identical_to_unevicted_run():
    """Two engines, same traffic: one with a 4-page pool that must
    thrash-evict, one with a roomy pool.  Token streams must be
    bit-identical — eviction + re-prefill rebuilds exactly."""
    model = _model()
    prompts = [[5, 3, 7, 2, 9, 1], [8, 8, 1, 30, 2, 17]]
    results = {}
    evictions = {}
    for label, pages in (("tight", 4), ("roomy", 64)):
        with _engine(model, cache_kw={"max_pages": pages},
                     max_new_tokens=8) as eng:
            streams = [eng.submit(p) for p in prompts]
            results[label] = [s.result(timeout=60) for s in streams]
            evictions[label] = eng.cache.stats()["evictions"]
    assert evictions["tight"] > 0 and evictions["roomy"] == 0
    assert results["tight"] == results["roomy"]
    assert results["roomy"] == [_oracle(model, p, 8) for p in prompts]
    assert tm.counter_value("serve.decode.evicted", tenant="default") > 0
    assert tmem.live_bytes() == 0


# ---------------------------------------------------------------------------
# cancellation, deadlines, admission bounds, drain
# ---------------------------------------------------------------------------


def test_cancel_frees_pages_immediately():
    with _engine(max_new_tokens=100, poll_s=0.001) as eng:
        s = eng.submit([5, 3, 7, 2])
        it = iter(s)
        next(it)
        next(it)                      # two tokens landed; mid-generation
        assert eng.cache.stats()["pages_live"] > 0
        assert s.cancel() is True
        # pages returned and blocks reaped BEFORE cancel() returned
        assert eng.cache.stats()["pages_live"] == 0
        assert tmem.live_bytes() == 0
        assert isinstance(s.error(), Cancelled)
        with pytest.raises(Cancelled):
            s.result(timeout=5)
        with pytest.raises(Cancelled):
            list(it)
        assert s.cancel() is False    # idempotent: already gone
    assert tm.counter_value("serve.decode.cancelled",
                            tenant="default") >= 1


def test_deadline_exceeded_typed_with_stage():
    with _engine() as eng:
        s = eng.submit([1, 2, 3], deadline_s=0.0)
        with pytest.raises(DeadlineExceeded) as ei:
            s.result(timeout=10)
        assert ei.value.stage == "prefill"
    assert tmem.live_bytes() == 0


def test_max_sequences_sheds_typed_and_submit_gates():
    with _engine(max_sequences=2, max_new_tokens=100,
                 poll_s=0.001) as eng:
        a = eng.submit([1, 2, 3])
        b = eng.submit([4, 5, 6])
        with pytest.raises(Overloaded) as ei:
            eng.submit([7, 8, 9])
        assert ei.value.reason == "queue" and ei.value.retry_after > 0
        with pytest.raises(Rejected) as ri:
            eng.submit(list(range(10_000)))     # can never fit the pool
        assert ri.value.reason == "kv"
        with pytest.raises(ServeError):
            eng.submit([])
        a.cancel()
        b.cancel()
    assert tm.counter_value("serve.shed", reason="queue",
                            tenant="default") >= 1


def test_drain_then_submit_is_typed_draining():
    eng = _engine(max_new_tokens=2)
    s = eng.submit([5, 3, 7])
    assert eng.drain(timeout=30) is True
    assert s.done() and s.error() is None
    with pytest.raises(Draining):
        eng.submit([1, 2])
    eng.close()
    eng.close()                       # idempotent
    assert tmem.live_bytes() == 0


def test_token_stream_listener_replay_after_done():
    with _engine(max_new_tokens=3) as eng:
        s = eng.submit([5, 3, 7, 2])
        want = s.result(timeout=30)
        got = []
        s.add_listener(lambda kind, v: got.append((kind, v)))
        assert got == [("token", t) for t in want] + [("done", None)]


# ---------------------------------------------------------------------------
# server integration + asyncio bridge
# ---------------------------------------------------------------------------


def _srv_cfg(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("flush_s", 0.005)
    kw.setdefault("max_queue", 32)
    kw.setdefault("tenant_rate", 10_000.0)
    kw.setdefault("tenant_burst", 10_000.0)
    return serve.ServeConfig(**kw)


def test_attach_server_roundtrip_and_reclaimable_wiring():
    model = _model()
    eng = _engine(model, max_new_tokens=5)
    srv = serve.Server(_srv_cfg())
    try:
        eng.attach(srv, "decode")
        # the cache's reclaimable signal feeds the admission controller
        assert srv._admission.reclaimable_fn == \
            eng.cache.idle_evictable_bytes
        stream = srv.submit("decode", [5, 3, 7, 2]).result(timeout=30)
        assert isinstance(stream, serve.TokenStream)
        assert stream.result(timeout=30) == _oracle(model, [5, 3, 7, 2], 5)
        # dict payloads carry per-sequence knobs through the server
        s2 = srv.submit("decode", {"prompt": [8, 8, 1], "tenant": "t2",
                                   "max_new_tokens": 2}).result(timeout=30)
        assert s2.result(timeout=30) == _oracle(model, [8, 8, 1], 2)
        assert s2.tenant == "t2"
    finally:
        srv.close()
        eng.close()
    assert tmem.live_bytes() == 0


def test_aio_generate_streams_and_cancels_on_exit():
    model = _model()
    eng = _engine(model, max_new_tokens=6, poll_s=0.001)
    srv = serve.Server(_srv_cfg())
    try:
        eng.attach(srv, "decode")

        async def _full():
            return [t async for t in serve.aio.generate(
                srv, [5, 3, 7, 2], tenant="aio")]

        assert asyncio.run(_full()) == _oracle(model, [5, 3, 7, 2], 6)

        async def _partial():
            handle = await serve.aio.submit(srv, "decode", [9, 1, 4])
            got = []
            async for t in serve.aio.stream_tokens(handle):
                got.append(t)
                if len(got) == 2:
                    break             # client walks away mid-stream
            return handle, got

        handle, got = asyncio.run(_partial())
        assert len(got) == 2
        deadline = time.monotonic() + 5
        while not handle.done() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert isinstance(handle.error(), Cancelled)
        assert eng.cache.stats()["pages_live"] == 0

        async def _not_a_stream():
            async for _ in serve.aio.generate(srv, 1, endpoint="echo"):
                pass

        srv.register("echo", lambda xs: xs)
        with pytest.raises(TypeError):
            asyncio.run(_not_a_stream())
    finally:
        srv.close()
        eng.close()
    assert tmem.live_bytes() == 0


# ---------------------------------------------------------------------------
# the two batch classes as spans + per-endpoint SLO histograms
# ---------------------------------------------------------------------------


def test_prefill_and_decode_spans_say_what_ran(telemetry_capture):
    model = _model()
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, model.vocab, size=64).tolist()
    with _engine(model, use_ring_prefill=True, max_new_tokens=4) as eng:
        eng.submit(prompt).result(timeout=30)
    spans = [e for e in read_journal(telemetry_capture.journal_path())
             if e.get("cat") == "span"]
    pre = [s["labels"] for s in spans if s["name"] == "serve.prefill"]
    dec = [s["labels"] for s in spans if s["name"] == "serve.decode"]
    # one prefill of the whole prompt, then one decode round a new token
    # after the first (which the prefill's last row yields)
    assert [(int(p["ntok"]), p["rebuild"]) for p in pre] == [(64, False)]
    assert len(dec) == 3 and all(int(d["size"]) == 1 for d in dec), dec
    assert {d["endpoint"] for d in pre + dec} == {"decode"}
    # both regimes land in the per-endpoint SLO histogram family
    text = export.to_prometheus(telemetry_capture.report())
    assert 'da_tpu_serve_slo_request_s_bucket{endpoint="decode.prefill"' \
        in text
    assert 'da_tpu_serve_slo_request_s_bucket{endpoint="decode.decode"' \
        in text
    assert tmem.live_bytes() == 0


# ---------------------------------------------------------------------------
# the decode chaos leg
# ---------------------------------------------------------------------------


def test_chaos_device_loss_mid_decode_correct_and_relayed(monkeypatch):
    """Seeded plan downs a device on the second decode dispatch: the
    recovery executor probes, shrinks — re-laying the registered cache
    blocks onto survivors — and retries; the token stream is
    bit-identical to the fault-free oracle."""
    plan = [{"site": "serve.decode", "action": "device_loss", "at": 2,
             "count": 1, "device": 3}]
    monkeypatch.setenv("DA_TPU_FAULT_PLAN", json.dumps(plan))
    monkeypatch.setenv("DA_TPU_FAULT_SEED", "1234")
    faults.configure()
    model = _model()
    retries0 = tm.counter_value("recovery.retries", verdict="device_loss")
    with _engine(model, max_new_tokens=10, poll_s=0.001) as eng:
        s = eng.submit([5, 3, 7, 2, 9])
        assert s.result(timeout=60) == _oracle(model, [5, 3, 7, 2, 9], 10)
        # survivors-only: a sequence admitted after the loss lays its
        # pages strictly on live ranks
        s2 = eng.submit([8, 8, 1], max_new_tokens=200)
        deadline = time.monotonic() + 10
        pids = None
        while time.monotonic() < deadline:
            with eng.cache._lock:
                blocks = list(eng.cache._blocks.values())
            if blocks:
                pids = {int(p) for b in blocks for p in b.d.pids.flat}
                break
            time.sleep(0.002)
        assert pids is not None and 3 not in pids, pids
        s2.cancel()
    assert [h["action"] for h in faults.history()] == ["device_loss"]
    assert tm.counter_value("recovery.retries",
                            verdict="device_loss") > retries0
    assert 3 not in elastic.manager().live_ranks()
    assert tmem.live_bytes() == 0


def test_chaos_device_loss_mid_prefill_correct(monkeypatch):
    plan = [{"site": "serve.prefill", "action": "device_loss", "at": 1,
             "count": 1, "device": 2}]
    monkeypatch.setenv("DA_TPU_FAULT_PLAN", json.dumps(plan))
    monkeypatch.setenv("DA_TPU_FAULT_SEED", "1234")
    faults.configure()
    model = _model()
    retries0 = tm.counter_value("recovery.retries", verdict="device_loss")
    with _engine(model, max_new_tokens=4) as eng:
        s = eng.submit([5, 3, 7, 2, 9, 1])
        assert s.result(timeout=60) == _oracle(model, [5, 3, 7, 2, 9, 1], 4)
    assert tm.counter_value("recovery.retries",
                            verdict="device_loss") > retries0
    assert 2 not in elastic.manager().live_ranks()
    assert tmem.live_bytes() == 0


def test_chaos_minority_partition_drains_typed(monkeypatch):
    """The engine observes a partition from the minority side: every
    in-flight sequence resolves typed Draining (clients failover, they
    never wait out a timeout), and new submits are refused typed."""
    split = [[0, 1, 2, 3, 4], [5, 6, 7]]
    domains.configure(split)
    plan = [{"site": "serve.decode", "action": "partition", "at": 1,
             "groups": split, "observer": 6}]
    monkeypatch.setenv("DA_TPU_FAULT_PLAN", json.dumps(plan))
    monkeypatch.setenv("DA_TPU_FAULT_SEED", "1234")
    faults.configure()
    eng = _engine(max_new_tokens=10, poll_s=0.001)
    try:
        streams = [eng.submit([5, 3, 7, 2]), eng.submit([8, 8, 1])]
        for s in streams:
            with pytest.raises(Draining) as ei:
                s.result(timeout=60)
            assert isinstance(ei.value.__cause__,
                              recovery.MinorityPartitionExit)
        assert eng.stats()["draining"] is True
        with pytest.raises(Draining):
            eng.submit([1, 2])
        assert tm.counter_value("serve.partition_drains") >= 1
    finally:
        eng.close(drain=False)
    assert tmem.live_bytes() == 0


# ---------------------------------------------------------------------------
# the acceptance soak: 2x overload, tight HBM budget, seeded device loss
# ---------------------------------------------------------------------------


def test_acceptance_soak_overload_budget_eviction_chaos(monkeypatch):
    """ISSUE acceptance: open-loop ~2x overload against a budget that
    holds ~7 of the ~24 demanded pages' blocks.  The ledger witness must
    never exceed the budget, sheds are typed with retry_after, evictions
    + re-prefills keep every admitted stream bit-identical to the
    oracle, a seeded device loss mid-decode resolves correct-or-typed,
    and the leak gate drains to zero."""
    plan = [{"site": "serve.decode", "action": "device_loss", "at": 3,
             "count": 1, "device": 5}]
    monkeypatch.setenv("DA_TPU_FAULT_PLAN", json.dumps(plan))
    monkeypatch.setenv("DA_TPU_FAULT_SEED", "1234")
    faults.configure()
    model = _model()
    budget = 4096                     # 7 x 512 B blocks under 0.9 frac
    eng = serve.DecodeEngine(
        model,
        serve.PagedKVCache(serve.KVCacheConfig(
            page_tokens=4, heads=model.heads, head_dim=model.head_dim,
            block_pages=2, max_pages=16, hbm_budget_bytes=budget,
            retry_after_s=0.01)),
        serve.DecodeConfig(max_new_tokens=6, max_sequences=6,
                           token_budget=64, poll_s=0.001,
                           use_ring_prefill=False),
        policy=_fast_policy())
    peak = {"v": 0}
    stop = threading.Event()

    def _monitor():                   # the ledger witness
        while not stop.is_set():
            peak["v"] = max(peak["v"], tmem.live_bytes())
            time.sleep(0.001)

    mon = threading.Thread(target=_monitor, daemon=True)
    mon.start()
    rng = np.random.default_rng(5)
    admitted: list[tuple[list, serve.TokenStream]] = []
    sheds = 0
    try:
        for i in range(16):           # ~2x the 6-sequence capacity
            prompt = rng.integers(0, model.vocab, size=6).tolist()
            try:
                admitted.append((prompt, eng.submit(prompt)))
            except Overloaded as e:
                assert e.retry_after > 0 and e.reason in ("kv", "queue")
                sheds += 1
            time.sleep(0.003)
        assert sheds >= 1, "overload never shed: not a soak"
        assert len(admitted) >= 6
        for prompt, s in admitted:    # correct-or-typed: here, correct
            assert s.result(timeout=60) == _oracle(model, prompt, 6), \
                f"prompt {prompt} diverged after eviction/chaos"
        assert eng.cache.stats()["evictions"] > 0, \
            "budget never forced an eviction: not a soak"
    finally:
        stop.set()
        mon.join(2.0)
        eng.close()
    assert peak["v"] > 0 and peak["v"] <= budget, peak
    assert [h["action"] for h in faults.history()] == ["device_loss"]
    assert 5 not in elastic.manager().live_ranks()
    assert tmem.live_bytes() == 0     # the leak gate's explicit witness
