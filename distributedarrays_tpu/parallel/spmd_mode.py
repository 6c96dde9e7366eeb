"""MPI-style SPMD programming mode.

TPU-native counterpart of /root/reference/src/spmd.jl (260 LoC).  The
reference gives each worker a RemoteChannel, a demux task routing
``(ctxt_id, typ, from, data, tag)`` tuples into per-context channels
(spmd.jl:72-98), out-of-order buffering for unexpected messages
(spmd.jl:126-143), and collectives built from send/recv (159-231).

Design split for TPU:

- **This module** is the *dynamic* half: fully general tagged send/recv
  between ranks, contexts with context-local storage, barrier/bcast/
  scatter/gather — runs host-side, one Python task (thread) per rank under
  the single controller.  Message passing is in-memory mailbox matching,
  which preserves the reference's semantics (tags, out-of-order buffering,
  any pattern, any payload) exactly — there is no TCP to emulate.
- ``parallel.collectives`` is the *static* half: communication patterns
  known at trace time (ring shifts, halo exchange, all-to-all) compile to
  ``shard_map`` + ``lax.ppermute``/``psum``/``all_to_all`` over ICI — that
  is the path where the reference's send/recv ring programs (e.g.
  test/spmd.jl:90-101, the stencil in docs/src/index.md:160-181) belong on
  TPU, and what the benchmarks exercise.

Inside ``spmd(f, ...)`` each rank task sees ``myid()`` (its rank) and
DArray ``localpart`` resolves against that rank, mirroring how reference
SPMD closures address their chunk.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Sequence

import numpy as np

from .. import core
from .. import layout as L
from .. import telemetry as _tm
from ..analysis import divergence as _dv
from ..resilience import faults as _fl

__all__ = [
    "spmd", "spmd_async", "sendto", "recvfrom", "recvfrom_any", "barrier",
    "bcast", "scatter", "gather_spmd", "context", "context_local_storage",
    "myid", "nprocs", "SPMDContext", "close_context",
]

_TIMEOUT_ENV = "DA_TPU_SPMD_TIMEOUT"
_DEFAULT_TIMEOUT = 60.0  # seconds; a stuck collective fails loudly, not forever


def _default_timeout() -> float:
    """The receive-timeout default: ``DA_TPU_SPMD_TIMEOUT`` seconds when
    set (resilience tests shrink it to trip fast; pod jobs with slow DCN
    raise it), else 60s.  Read per call so a test can flip the env
    without reimporting; both the thread and process backends resolve
    their ``timeout=None`` defaults through here."""
    try:
        return float(os.environ.get(_TIMEOUT_ENV, _DEFAULT_TIMEOUT))
    except ValueError:
        return _DEFAULT_TIMEOUT


_PEER_ABORT = "SPMD peer task failed; aborting receive"


def _record_crash(exc) -> None:
    """Flight-recorder trigger on the spmd failure paths: a crashed run
    leaves one postmortem bundle (ring + open spans + HBM ledger +
    registry census).  Single boolean check when telemetry is off; the
    recorder must never mask the real error."""
    if _tm.enabled():
        try:
            _tm.flight.record_crash(exc, where="spmd")
        except Exception:
            pass


def _scan_stash(msgs: list, match: Callable[[tuple], bool]):
    """Pop and return the first stashed message satisfying ``match``
    (out-of-order buffering, reference spmd.jl:126-143), else None.
    Shared by the thread mailbox and the process backend's queue view."""
    for i, m in enumerate(msgs):
        if match(m):
            return msgs.pop(i)
    return None


def _timeout_source(timeout: float) -> str:
    """Where the effective receive timeout came from — named honestly:
    the env var is credited only when it actually produced this value
    (an explicit ``timeout=`` argument overrides it, and an unparsable
    value silently falls back to the default)."""
    configured = os.environ.get(_TIMEOUT_ENV)
    if configured is not None:
        try:
            if float(configured) == timeout:
                return f"{_TIMEOUT_ENV}={configured}"
        except ValueError:
            if timeout == _DEFAULT_TIMEOUT:
                return (f"{_TIMEOUT_ENV}={configured!r} invalid, using "
                        f"default {_DEFAULT_TIMEOUT:g}s")
        return "explicit timeout argument"
    if timeout == _DEFAULT_TIMEOUT:
        return f"default {_DEFAULT_TIMEOUT:g}s; set {_TIMEOUT_ENV}"
    return "explicit timeout argument"


def _receive_timeout(timeout: float, msgs: list,
                     tag: Any = None) -> TimeoutError:
    return TimeoutError(
        f"SPMD receive timed out after {timeout}s "
        f"({_timeout_source(timeout)}) blocked on tag={tag!r} "
        f"(pending: {[(m[0], m[1], m[3]) for m in msgs[:8]]})")


class _Mailbox:
    """Per-(context, rank) message store with tag/type/source matching and
    out-of-order buffering (reference spmd.jl:126-143: unexpected messages
    are stashed and re-examined)."""

    def __init__(self):
        self._msgs: list[tuple] = []          # (typ, from_pid, data, tag)
        self._cond = threading.Condition()

    def put(self, msg: tuple):
        with self._cond:
            self._msgs.append(msg)
            self._cond.notify_all()

    def take(self, match: Callable[[tuple], bool], failed: "threading.Event",
             timeout: float, tag: Any = None):
        # span: the drain wait is where SPMD programs spend their blocked
        # time — aggregate-only (_journal=False: a chatty ring would emit
        # thousands of journal lines), visible in span_stats()/report()
        with _tm.span("spmd.mailbox.drain", _journal=False):
            deadline = time.monotonic() + timeout
            with self._cond:
                while True:
                    m = _scan_stash(self._msgs, match)
                    if m is not None:
                        return m
                    if failed.is_set():
                        raise RuntimeError(_PEER_ABORT)
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise _receive_timeout(timeout, self._msgs, tag)
                    self._cond.wait(min(remaining, 0.1))


class SPMDContext:
    """Execution context: isolates message traffic and carries per-rank local
    storage (reference SPMDContext, spmd.jl:18-35; storage spmd.jl:59-64)."""

    def __init__(self, pids: Sequence[int] | None = None):
        self.id = core.next_did()
        self.pids = [int(p) for p in (pids if pids is not None else L.all_ranks())]
        self.store: dict[int, dict] = {p: {} for p in self.pids}
        self._mailboxes: dict[int, _Mailbox] = {p: _Mailbox() for p in self.pids}
        self._barrier_gen: dict[int, int] = {p: 0 for p in self.pids}
        self._failed = threading.Event()
        self._release_gen = 0
        self._proc_state = None   # process backend's persistent queues
        # per-run collective-divergence checker (DA_TPU_CHECK_DIVERGENCE=1,
        # thread backend); installed/cleared by spmd()
        self._divergence = None

    def mailbox(self, pid: int) -> _Mailbox:
        try:
            return self._mailboxes[pid]
        except KeyError:
            raise ValueError(f"rank {pid} is not in context {self.id} "
                             f"(pids={self.pids})") from None

    def close(self):
        """Free message state (reference delete_ctxt_id broadcast,
        spmd.jl:30-35,256-258)."""
        self._mailboxes = {p: _Mailbox() for p in self.pids}
        self.store = {p: {} for p in self.pids}
        self._drop_proc_state()

    def _reset_comm(self):
        """Drain in-flight messages and resynchronize barrier generations
        after a failed run, keeping per-rank storage.  Without this an
        explicit context is poisoned: stale messages satisfy future
        receives and diverged barrier generations deadlock the next run."""
        self._mailboxes = {p: _Mailbox() for p in self.pids}
        self._barrier_gen = {p: 0 for p in self.pids}
        self._failed = threading.Event()
        self._drop_proc_state()

    def _drop_proc_state(self):
        """Drop the process backend's cross-run leftover messages (set
        lazily by spmd_process.run_spmd_process) — the process-mode
        analog of replacing the thread mailboxes above."""
        self._proc_state = None


_CONTEXTS_LOCK = threading.Lock()
_CONTEXTS: dict = {}

_tls = threading.local()


def context(pids: Sequence[int] | None = None) -> SPMDContext:
    """Create an explicit SPMD context (reference context(), spmd.jl:59-61)."""
    c = SPMDContext(pids)
    with _CONTEXTS_LOCK:
        _CONTEXTS[c.id] = c
    return c


def close_context(c: SPMDContext):
    with _CONTEXTS_LOCK:
        _CONTEXTS.pop(c.id, None)
    c.close()


def _current() -> tuple[SPMDContext, int]:
    ctx = getattr(_tls, "ctxt", None)
    if ctx is None:
        raise RuntimeError(
            "not inside an spmd() run — sendto/recvfrom/barrier/... are only "
            "meaningful within spmd(f, ...) (reference spmd.jl:118)")
    return ctx, core.current_rank()


def myid() -> int:
    """Rank of the calling SPMD task (reference myid())."""
    return core.current_rank()


def nprocs() -> int:
    ctx = getattr(_tls, "ctxt", None)
    return len(ctx.pids) if ctx is not None else L.nranks()


def context_local_storage() -> dict:
    """This rank's per-context dict, persistent across spmd() runs on the
    same explicit context (reference context_local_storage, spmd.jl:62-64)."""
    ctx, rank = _current()
    return ctx.store[rank]


# ---------------------------------------------------------------------------
# point-to-point
# ---------------------------------------------------------------------------


def sendto(pid: int, data: Any, tag: Any = None):
    """Async send to ``pid`` (reference sendto, spmd.jl:145-147)."""
    ctx, rank = _current()
    # per-send byte accounting (estimate: array payloads report nbytes,
    # unsized Python objects report 0); journal dedup'd per direction so
    # a chatty ring program cannot flood the journal.  enabled() guard:
    # this is the SPMD hot path, disabled mode must not even build the
    # key strings
    if _tm.enabled():
        _tm.record_comm("spmd_send", _tm.nbytes_of(data), op="sendto",
                        once_key=f"spmd_send:{rank}->{pid}",
                        src=rank, dst=pid)
    ctx.mailbox(pid).put(("sendto", rank, data, tag))


def recvfrom(pid: int, tag: Any = None, timeout: float | None = None):
    """Blocking receive of a message from ``pid`` with matching ``tag``
    (reference recvfrom, spmd.jl:149-151).  Out-of-order messages stay
    buffered until their matching receive.  ``timeout`` defaults to
    ``DA_TPU_SPMD_TIMEOUT`` (60s unset)."""
    ctx, rank = _current()
    if timeout is None:
        timeout = _default_timeout()
    m = ctx.mailbox(rank).take(
        lambda m: m[0] == "sendto" and m[1] == pid and m[3] == tag,
        ctx._failed, timeout, tag=tag)
    _tm.count("spmd.recv")
    return m[2]


def recvfrom_any(tag: Any = None, timeout: float | None = None):
    """Receive from whichever rank sends first; returns ``(from_pid, data)``
    (reference recvfrom_any, spmd.jl:153-157)."""
    ctx, rank = _current()
    if timeout is None:
        timeout = _default_timeout()
    m = ctx.mailbox(rank).take(
        lambda m: m[0] == "sendto" and m[3] == tag, ctx._failed, timeout,
        tag=tag)
    _tm.count("spmd.recv")
    return m[1], m[2]


# ---------------------------------------------------------------------------
# collectives (reference spmd.jl:159-231)
# ---------------------------------------------------------------------------


def _dv_note(ctx, rank: int, op: str, detail: str) -> None:
    """Record an eager collective with the run's divergence checker (no-op
    unless DA_TPU_CHECK_DIVERGENCE armed this run).  Raises
    CollectiveDivergenceError in the issuing rank's task on mismatch.
    getattr: the process backend's _RunContext duck-types SPMDContext and
    is never instrumented (checking is thread-backend only)."""
    ck = getattr(ctx, "_divergence", None)
    if ck is not None:
        ck.record(rank, op, detail)


def barrier(tag: Any = None, timeout: float | None = None):
    """All-to-all barrier with double-barrier protection via per-rank
    generation counters (reference barrier, spmd.jl:159-184)."""
    ctx, rank = _current()
    _fl.check("spmd.collective", op="barrier", rank=rank)
    _dv_note(ctx, rank, "barrier", f"tag={tag!r}")
    _tm.count("spmd.barrier")
    if timeout is None:
        timeout = _default_timeout()
    gen = ctx._barrier_gen[rank]
    ctx._barrier_gen[rank] = gen + 1
    btag = ("barrier", gen, tag)
    for p in ctx.pids:
        ctx.mailbox(p).put(("barrier", rank, None, btag))
    for p in ctx.pids:
        ctx.mailbox(rank).take(
            lambda m, p=p: m[0] == "barrier" and m[1] == p and m[3] == btag,
            ctx._failed, timeout, tag=btag)


def _check_root(ctx, root):
    if root not in ctx.pids:
        raise ValueError(f"root {root} is not in context pids {ctx.pids}")


def bcast(data: Any, root: int, tag: Any = None,
          timeout: float | None = None):
    """Broadcast from ``root`` to every rank (reference bcast,
    spmd.jl:186-196)."""
    ctx, rank = _current()
    _check_root(ctx, root)
    _fl.check("spmd.collective", op="bcast", rank=rank)
    if timeout is None:
        timeout = _default_timeout()
    # payload signature excluded: only root's data participates (non-root
    # ranks conventionally pass None), so shapes legitimately differ
    _dv_note(ctx, rank, "bcast", f"root={root}, tag={tag!r}")
    btag = ("bcast", tag)
    if rank == root:
        if _tm.enabled():
            _tm.record_comm("spmd_send",
                            _tm.nbytes_of(data) * (len(ctx.pids) - 1),
                            op="bcast", once_key=f"spmd_send:bcast:{root}",
                            src=root)
        for p in ctx.pids:
            if p != root:
                ctx.mailbox(p).put(("sendto", root, data, btag))
        return data
    m = ctx.mailbox(rank).take(
        lambda m: m[0] == "sendto" and m[1] == root and m[3] == btag,
        ctx._failed, timeout, tag=btag)
    return m[2]


def scatter(x, root: int, tag: Any = None, timeout: float | None = None):
    """Split ``x`` evenly across ranks from ``root`` (reference scatter,
    spmd.jl:198-212; equal division is asserted like the reference's
    ``@assert rem(length(x), length(pids)) == 0``)."""
    ctx, rank = _current()
    _check_root(ctx, root)
    _fl.check("spmd.collective", op="scatter", rank=rank)
    if timeout is None:
        timeout = _default_timeout()
    _dv_note(ctx, rank, "scatter", f"root={root}, tag={tag!r}")
    stag = ("scatter", tag)
    if rank == root:
        n = len(x)
        if n % len(ctx.pids) != 0:
            raise ValueError(
                f"scatter: length {n} not divisible by {len(ctx.pids)} ranks")
        per = n // len(ctx.pids)
        if _tm.enabled():
            _tm.record_comm("spmd_send", _tm.nbytes_of(x), op="scatter",
                            once_key=f"spmd_send:scatter:{root}", src=root)
        mine = None
        for i, p in enumerate(ctx.pids):
            part = x[i * per:(i + 1) * per]
            if p == rank:
                mine = part
            else:
                ctx.mailbox(p).put(("sendto", root, part, stag))
        return mine
    m = ctx.mailbox(rank).take(
        lambda m: m[0] == "sendto" and m[1] == root and m[3] == stag,
        ctx._failed, timeout, tag=stag)
    return m[2]


def gather_spmd(x, root: int, tag: Any = None,
                timeout: float | None = None):
    """Collect one value per rank at ``root``, pid-ordered (reference gather,
    spmd.jl:214-231).  Returns the list on root, None elsewhere."""
    ctx, rank = _current()
    _check_root(ctx, root)
    _fl.check("spmd.collective", op="gather_spmd", rank=rank)
    if timeout is None:
        timeout = _default_timeout()
    _dv_note(ctx, rank, "gather_spmd",
             f"root={root}, tag={tag!r}, "
             f"payload={_dv.payload_signature(x)}")
    gtag = ("gather", tag)
    if rank != root:
        if _tm.enabled():
            _tm.record_comm("spmd_send", _tm.nbytes_of(x), op="gather",
                            once_key=f"spmd_send:gather:{rank}->{root}",
                            src=rank, dst=root)
        ctx.mailbox(root).put(("sendto", rank, x, gtag))
        return None
    out = {}
    out[rank] = x
    for p in ctx.pids:
        if p == root:
            continue
        m = ctx.mailbox(rank).take(
            lambda m, p=p: m[0] == "sendto" and m[1] == p and m[3] == gtag,
            ctx._failed, timeout, tag=gtag)
        out[p] = m[2]
    return [out[p] for p in ctx.pids]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


@_tm.traced(name="spmd.run")
def spmd(f: Callable, *args, pids: Sequence[int] | None = None,
         context: SPMDContext | None = None, timeout: float = 300.0,
         backend: str = "thread"):
    """Run ``f(*args)`` once per rank, concurrently (reference spmd driver,
    spmd.jl:233-254).

    Each rank runs in its own task with ``myid()`` set, an implicit fresh
    context unless an explicit one is passed (implicit contexts are cleared
    after the run, like the reference's ``clear_ctxt`` path), and DArray
    arguments resolve ``localpart()`` against the task's rank.  Returns the
    per-rank return values, pid-ordered.

    ``backend="process"`` forks one OS process per rank (the reference's
    addprocs worker model, runtests.jl:10-13): pure-Python rank compute
    runs GIL-free, messages/results/storage cross process boundaries (and
    must be picklable), and context storage is merged back after the run.
    Host-side compute only — see parallel/spmd_process.py.
    """
    implicit = context is None
    ctx = SPMDContext(pids) if implicit else context
    if pids is not None and not implicit and list(pids) != ctx.pids:
        raise ValueError("pids disagree with explicit context's pids")
    _tm.count("spmd.runs", backend=backend)
    if _tm.enabled():
        # the @traced spmd.run span opened without knowing the backend
        # or rank count — stamp them now (per-call labels on the span)
        _tm.annotate(backend=backend, ranks=len(ctx.pids))
        _tm.event("spmd", "run", backend=backend, ranks=len(ctx.pids),
                  once_key=f"spmd:run:{backend}:{len(ctx.pids)}")
    checker = None
    if _dv.checking():
        if backend == "thread":
            checker = _dv.DivergenceChecker(ctx.pids,
                                            on_mismatch=ctx._failed.set)
        else:
            # the stderr warning is one-shot and easily lost — journal a
            # typed event + counter so incident
            # reconstruction can see the coverage gap (this run was NOT
            # divergence-checked, even though the env var says it was)
            _tm.count("analysis.divergence_unchecked", backend=backend)
            if _tm.enabled():
                _tm.event("divergence", "unchecked_backend",
                          backend=backend, ranks=len(ctx.pids),
                          once_key=f"divergence:unchecked:{backend}")
            from ..utils.debug import warn_once
            warn_once("divergence:process-backend",
                      "DA_TPU_CHECK_DIVERGENCE is set but the process "
                      "backend is not instrumented; collective-divergence "
                      "checking only covers backend='thread'")
    ctx._divergence = checker
    if backend == "process":
        from .spmd_process import run_spmd_process
        try:
            res = run_spmd_process(f, args, ctx, timeout)
        except BaseException as e:
            _record_crash(e)
            if not implicit:
                ctx._reset_comm()    # same post-failure hygiene as threads
            raise
        finally:
            if implicit:
                ctx.close()
        return [res[p] for p in ctx.pids]
    if backend != "thread":
        raise ValueError(f"unknown spmd backend {backend!r} "
                         "(expected 'thread' or 'process')")
    dirty = False
    try:
        results = _fanout_thread_ranks(ctx, f, args, timeout, checker)
    except BaseException:
        # failed or timed-out run: drain stale messages and resync
        # barrier generations so an explicit context stays usable
        dirty = True
        raise
    finally:
        ctx._divergence = None
        if implicit:
            ctx.close()
        elif dirty:
            ctx._reset_comm()
    return [results[p] for p in ctx.pids]


def _fanout_thread_ranks(ctx: SPMDContext, f: Callable, args: tuple,
                         timeout: float, checker) -> dict[int, Any]:
    """The thread backend's rank fan-out, extracted from the driver so the
    blocking :func:`spmd` and the async dispatch path share one engine:
    one daemon thread per rank, a single shared deadline, peer-abort
    wakeups, and root-cause error aggregation.  Returns ``{rank:
    result}``; raises (after recording a flight bundle) on any failure."""
    results: dict[int, Any] = {}
    errors: dict[int, BaseException] = {}
    # request-trace propagation: contextvars do not cross thread starts,
    # so capture the caller's trace ids here and rebind inside each rank
    # task — a serve request's id reaches its rank steps (and the spans/
    # events they record) without touching the span parent isolation
    # (fresh threads still root their own span timelines)
    trace_ids = _tm.current_trace_ids()

    def run(rank: int):
        core._rank_tls.rank = rank
        _tls.ctxt = ctx
        if trace_ids:
            _tm.tracing.bind_trace_ids(trace_ids)
        try:
            # deterministic chaos: an armed fault plan can kill/hang this
            # rank at task start — the thread-backend "host death" site
            _fl.check("spmd.rank", rank=rank, backend="thread")
            # per-rank step span: a fresh thread has no contextvar parent,
            # so rank timelines are independent root spans (one Perfetto
            # track per rank thread)
            with _tm.span("spmd.step", rank=rank):
                results[rank] = f(*args)
            if checker is not None:
                # clean completion: peers mid-collective beyond this rank's
                # final count can never be matched — fail fast, don't let
                # them wait out the receive timeout
                checker.finish(rank)
        except BaseException as e:  # noqa: BLE001 — propagated to caller
            errors[rank] = e
            ctx._failed.set()
        finally:
            core._rank_tls.rank = 0
            _tls.ctxt = None

    threads = [threading.Thread(target=run, args=(p,), name=f"spmd-{p}",
                                daemon=True) for p in ctx.pids]
    for t in threads:
        t.start()
    # one shared deadline: the documented timeout bounds the whole run, not
    # each join (nranks sequential joins would multiply the worst case)
    deadline = time.monotonic() + timeout
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
        if t.is_alive():
            ctx._failed.set()      # wake blocked receivers
            for t2 in threads:
                t2.join(5)
            err = TimeoutError(
                f"spmd task {t.name} did not finish in {timeout}s")
            _record_crash(err)
            raise err
    if errors:
        def _secondary(e):
            # failures that are consequences, not causes: peer aborts,
            # receive timeouts, and the divergence error itself
            return ((isinstance(e, RuntimeError)
                     and "peer task failed" in str(e))
                    or isinstance(e, (TimeoutError,
                                      _dv.CollectiveDivergenceError)))
        if (checker is not None and checker.error is not None
                and all(_secondary(e) for e in errors.values())):
            # the divergence IS the root cause: every other failure is a
            # peer abort/timeout it triggered.  Raise it directly so the
            # per-rank sequence diff reaches the caller unwrapped.
            _record_crash(checker.error)
            raise checker.error
        # prefer the root-cause failure over secondary "peer failed" aborts
        primary = [(r, e) for r, e in sorted(errors.items())
                   if not (isinstance(e, RuntimeError)
                           and "peer task failed" in str(e))]
        rank, err = primary[0] if primary else sorted(errors.items())[0]
        _record_crash(err)
        raise RuntimeError(
            f"spmd task on rank {rank} failed ({len(errors)} total failures)"
        ) from err
    if checker is not None:
        checker.verify()   # backstop: identical sequences end to end
    return results


# ---------------------------------------------------------------------------
# async dispatch
# ---------------------------------------------------------------------------

_DISPATCHERS_ENV = "DA_TPU_SPMD_DISPATCHERS"
_dispatch_pool = None
_dispatch_lock = threading.Lock()


def _dispatcher():
    """The shared async-dispatch pool (daemon threads; size
    ``DA_TPU_SPMD_DISPATCHERS``, default 4).  Lazy: a process that only
    ever calls blocking :func:`spmd` never creates it."""
    global _dispatch_pool
    if _dispatch_pool is None:
        from concurrent.futures import ThreadPoolExecutor
        with _dispatch_lock:
            if _dispatch_pool is None:
                try:
                    n = int(os.environ.get(_DISPATCHERS_ENV, "4"))
                except ValueError:
                    n = 4
                _dispatch_pool = ThreadPoolExecutor(
                    max_workers=max(1, n),
                    thread_name_prefix="spmd-dispatch")
    return _dispatch_pool


def spmd_async(f: Callable, *args, pids: Sequence[int] | None = None,
               context: SPMDContext | None = None, timeout: float = 300.0,
               backend: str = "thread"):
    """Asynchronous :func:`spmd`: enqueue the run on the shared dispatch
    pool and return a ``concurrent.futures.Future`` resolving to the
    pid-ordered per-rank results (or raising exactly what ``spmd`` would).

    This is the async half of the serving refactor: dispatchers overlap
    independent runs (up to ``DA_TPU_SPMD_DISPATCHERS`` concurrently)
    instead of the caller blocking through each eager fan-out — the
    serving executor and any pipelined workload submit here.  Runs on
    the same explicit ``context`` are NOT serialized by this function;
    overlapping them has the same semantics as overlapping threads did.
    """
    return _dispatcher().submit(
        lambda: spmd(f, *args, pids=pids, context=context, timeout=timeout,
                     backend=backend))
