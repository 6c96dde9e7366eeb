"""Map/reduce operations over DArrays.

TPU-native re-design of /root/reference/src/mapreduce.jl (323 LoC).  The
reference's two-phase scheme — per-worker local reduce, then reduce of the
partials on the caller (mapreduce.jl:29-35) — is exactly what XLA emits for a
reduction over a sharded array: a local reduce per device plus an all-reduce
over ICI.  So whole-array and dim-wise reductions here are single jitted
``jnp`` reductions over the sharded global array; the collective is
compiler-inserted, not hand-rolled.

Also here: ``map_localparts`` (mapreduce.jl:137-169) — lifted to ``shard_map``
when the layout is even and the function traceable, host-per-chunk otherwise —
``mapslices`` (mapreduce.jl:191-208), ``ppeval`` (mapreduce.jl:210-323) as
``vmap`` over slices, and ``samedist`` re-layout (mapreduce.jl:172-178) as an
XLA resharding.
"""

from __future__ import annotations

import functools
import inspect
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from .. import layout as L
from .. import telemetry as _tm
from ..darray import (DArray, SubDArray, _wrap_global, darray, distribute,
                      from_chunks)
from .broadcast import _jitted, _unwrap, _align_devices, elementwise
from ..parallel.collectives import (axis_size as _axis_size,
                                    shard_map_compat)

__all__ = [
    "dreduce", "dmapreduce", "dsum", "dprod", "dmaximum", "dminimum",
    "dmean", "dstd", "dvar", "dall", "dany", "dcount", "dextrema",
    "dcumsum", "dcumprod", "dcummax", "dcummin",
    "map_localparts", "map_localparts_into", "samedist", "mapslices", "ppeval",
]


# a deviation's shift is the mean of a corner of the array: this many
# leading elements of the last reduced axis, and of every other reduced one
_SAMPLE_LAST, _SAMPLE_OTHER = 128, 8
# the deviation's second pass runs where (s1/n)^2 > _RESHIFT * variance
_RESHIFT = 1.0


def _moments(a, axis=None, keepdims=False, ddof=0, *, mapper=None, root):
    """Variance (``root``: its square root) of ``mapper(a)`` over ``axis``
    in ONE read of ``a``: with ``d = mapper(a) - k``, ``s1 = sum(d)`` and
    ``s2 = sum(d * d)`` come out of one fused pass and the variance is
    ``max(s2 - s1^2/n, 0) / (n - ddof)``, finished on scalars (on one value
    a kept slice with ``axis``).  The shift ``k`` is the mean of a corner
    sample of each reduced slice (a slice, never a reshape: a sharded array
    is not gathered), so the subtraction cancels nothing that matters
    while the corner is like the rest.  Where it is not, which the sums
    themselves say (``(s1/n)^2 > _RESHIFT * (s2/n - (s1/n)^2)``: the shift
    lies further from the mean than one deviation), a ``lax.cond`` on that
    scalar makes the same pass again about the exact mean ``k + s1/n``:
    the two reads ``jnp.var`` always takes.  Accumulation is in the type
    ``jnp.var`` computes in (float32 for float32, bfloat16 and float16;
    float64 for float64) and the result has the type it returns.

    Real floating input only: integer, boolean and complex input keep
    ``jnp.var`` / ``jnp.std`` (numpy's promotion and ``|x - mean|^2``),
    decided from the mapped dtype when the program is traced;
    ``jit.builds{fn=reduction_moments, form=moments|numpy}`` counts which.
    """
    m = a if mapper is None else mapper(a)
    if not jnp.issubdtype(m.dtype, jnp.floating):
        _tm.count("jit.builds", fn="reduction_moments", form="numpy")
        return (jnp.std if root else jnp.var)(m, axis=axis, keepdims=keepdims,
                                              ddof=ddof)
    _tm.count("jit.builds", fn="reduction_moments", form="moments")
    acc = jnp.promote_types(m.dtype, jnp.float32)
    axes = tuple(range(m.ndim)) if axis is None else axis
    n = int(np.prod([m.shape[i] for i in axes]))

    def sums(x, shift):
        d = x.astype(acc) - shift
        return (jnp.sum(d, axis=axes, keepdims=True),
                jnp.sum(d * d, axis=axes, keepdims=True))

    def cut(x):
        # one axis at a time, each cut behind a barrier: the partitioner of
        # a sharded array then moves a few rows, where one cut of two axes
        # moved whole columns of a shard
        for i in axes:
            x = jax.lax.optimization_barrier(jax.lax.slice_in_dim(
                x, 0, min(x.shape[i], _SAMPLE_LAST if i == axes[-1]
                          else _SAMPLE_OTHER), axis=i))
        return x

    # the corner of `a`, mapped, where the mapper can take it: cut from the
    # mapped array, XLA keeps a mapped copy of all of `a` for the cut's
    # sake.  Any shift is a right one, so a mapper that is not elementwise
    # only makes it a poorer one; one that cannot take the corner (it holds
    # an array of the whole shape, or gives another shape or type) has its
    # own result cut
    corner = cut(m)
    if mapper is not None and m.shape == a.shape:
        try:
            mapped = mapper(cut(a))
            if (mapped.shape, mapped.dtype) == (corner.shape, m.dtype):
                corner = mapped
        except Exception:  # noqa: BLE001 — whatever it is, cut(m) stands
            pass
    corner = corner.astype(acc)
    # about its own first element, so that a constant array's shift is that
    # constant to the last bit and its deviation exactly 0
    first = corner[tuple(slice(1) if i in axes else slice(None)
                         for i in range(m.ndim))]
    k = first + jnp.mean(corner - first, axis=axes, keepdims=True)
    s1, s2 = sums(m, k)

    def again():
        # mapped anew inside the branch: the conditional then takes `a` by
        # reference and no mapped copy of it is kept for the branch's sake
        return sums(a if mapper is None else mapper(a), k + s1 / n)

    unlike = jnp.any(s1 * s1 / n > _RESHIFT * (s2 - s1 * s1 / n))
    s1, s2 = jax.lax.cond(unlike, again, lambda: (s1, s2))
    var = jnp.maximum(s2 - s1 * s1 / n, 0) / (n - ddof)
    var = jnp.where(n - ddof > 0, var, jnp.nan)
    if not keepdims:
        var = jnp.squeeze(var, axes)
    return (jnp.sqrt(var) if root else var).astype(m.dtype)


_var = functools.partial(_moments, root=False)
_std = functools.partial(_moments, root=True)


_REDUCERS = {
    "sum": jnp.sum, "prod": jnp.prod, "max": jnp.max, "min": jnp.min,
    "all": jnp.all, "any": jnp.any, "mean": jnp.mean, "std": _std,
    "var": _var,
}


def _reduce_impl(d, mapper: Callable | None, reducer: Callable, dims=None,
                 **kw):
    """One jitted (map ∘ reduce) over the sharded global array.

    Whole-array: reference mapreduce.jl:29-35 (two-phase tree reduce).
    With ``dims``: reference mapreducedim machinery mapreduce.jl:41-94 —
    Julia keeps reduced dims with size 1, which we mirror via keepdims.
    """
    x = _unwrap(d)
    axes = _norm_dims(dims, np.ndim(x))
    with _tm.span("mapreduce.reduce", _journal=False):
        res = _reduction_jit(mapper, reducer, axes,
                             tuple(sorted(kw.items())))(x)
    if axes is None:
        return res
    # result keeps the pid-grid shape of the source with the reduced dims
    # collapsed (reference mapreducedim_within, mapreduce.jl:54-66)
    if isinstance(d, DArray):
        dist = [1 if i in axes else c for i, c in enumerate(d.pids.shape)]
        pids = [int(p) for p in d.pids.flat]
        return _wrap_global(res, procs=pids, dist=_fit_dist(res.shape, dist))
    return _wrap_global(res)


# Keyed on the *semantic* identity (mapper fn, reducer fn, axes, kwargs) so
# repeated reductions reuse one jit wrapper and its compiled executables.
# Bounded: user lambdas are fresh objects per call and would otherwise
# accumulate wrappers forever.
@functools.lru_cache(maxsize=512)
def _reduction_jit(mapper, reducer, axes, kw_items):
    kw = dict(kw_items)

    def fn(a):
        if reducer in (_std, _var):     # these map for themselves
            return reducer(a, axis=axes, keepdims=axes is not None,
                           mapper=mapper, **kw)
        m = mapper(a) if mapper is not None else a
        if axes is None:
            return reducer(m, **kw)
        return reducer(m, axis=axes, keepdims=True, **kw)

    return jax.jit(fn)


@functools.lru_cache(maxsize=512)
def _jitted_by_key(fn):
    """jit cache for stable callables (module-level fns, jnp ops)."""
    return jax.jit(fn)


def _fn_site(fn):
    """Callable identifier for host-fallback warn keys: name plus the
    definition site, so two different lambdas (both named ``<lambda>``)
    never share one warn_once key and each degradation site surfaces."""
    import os as _os
    name = getattr(fn, "__name__", None) or repr(fn)
    code = getattr(fn, "__code__", None)
    if code is not None:
        return (f"{name}@{_os.path.basename(code.co_filename)}:"
                f"{code.co_firstlineno}")
    return name


def _fit_dist(shape, dist):
    return [min(c, s) if s > 0 else 1 for c, s in zip(dist, shape)]


def _norm_dims(dims, ndim):
    if dims is None:
        return None
    if isinstance(dims, (int, np.integer)):
        dims = (int(dims),)
    return tuple(sorted(int(a) % ndim for a in dims))


def dmapreduce(f: Callable, op_name_or_fn, d, dims=None):
    """``mapreduce(f, op, d)`` (reference mapreduce.jl:17-35).

    ``op`` may be a name from {sum, prod, max, min, all, any}, any
    jnp-style reducing callable taking ``axis``/``keepdims`` kwargs, or —
    like the reference, which accepts *any* associative binary ``op`` —
    a plain two-argument callable, reduced by a traced pairwise tree fold
    (the compiled analog of the reference's two-phase local-then-partials
    reduce) with a host fold as the untraceable-op fallback.
    """
    _tm.count("op.mapreduce")
    with _tm.span("mapreduce"):
        reducer = _REDUCERS.get(op_name_or_fn, op_name_or_fn) \
            if isinstance(op_name_or_fn, str) else op_name_or_fn
        if callable(reducer) and _is_binary_op(reducer):
            return _binary_reduce(d, f, reducer, dims)
        return _reduce_impl(d, f, reducer, dims=dims)


def dreduce(op_name_or_fn, d, dims=None):
    return dmapreduce(None, op_name_or_fn, d, dims=dims)


def _is_binary_op(fn) -> bool:
    """True for a plain binary operator ``op(a, b)`` — as opposed to a
    jnp-style reducer ``op(a, axis=..., keepdims=...)``."""
    if fn in _REDUCERS.values():
        return False
    if isinstance(fn, np.ufunc):
        return fn.nin == 2
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):  # builtins without introspectable sigs
        return False
    params = list(sig.parameters.values())
    if any(p.name in ("axis", "dims") for p in params):
        return False
    required = [p for p in params
                if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                and p.default is p.empty]
    return len(required) == 2


@functools.lru_cache(maxsize=512)
def _binary_fold_jit(mapper, op, axes, ndim):
    """Jitted pairwise tree fold of ``op`` over the flattened reduce axes.

    The halving loop runs at trace time (static shapes), emitting
    O(log n) vectorized applications of ``op`` — the compiled counterpart
    of the reference's local-reduce + partials tree (mapreduce.jl:29-35).
    ``op`` must be elementwise-vectorizable (true for anything built from
    jnp ops); scalar-only Python ops take the host fallback path.
    """
    def fn(a):
        m = mapper(a) if mapper is not None else a
        if axes is None:
            v = m.reshape(-1)
        else:
            keep = tuple(i for i in range(ndim) if i not in axes)
            v = jnp.transpose(m, axes + keep)
            v = v.reshape((-1,) + tuple(m.shape[i] for i in keep))
        while v.shape[0] > 1:
            k = v.shape[0] // 2
            # order-preserving pairing (adjacent elements combine) so
            # associative-but-non-commutative ops match a left fold
            head = op(v[0:2 * k:2], v[1:2 * k:2])
            v = head if v.shape[0] % 2 == 0 else \
                jnp.concatenate([head, v[2 * k:]], axis=0)
        return v[0]
    return jax.jit(fn)


def _binary_reduce(d, mapper, op, dims):
    x = _unwrap(d)
    ndim = np.ndim(x)
    axes = _norm_dims(dims, ndim)
    n = int(np.prod([np.shape(x)[i] for i in axes])) if axes is not None \
        else int(np.prod(np.shape(x)))
    if n == 0:
        raise ValueError("reduce of empty DArray with no init value")
    try:
        with _tm.span("mapreduce.tree", _journal=False):
            res = _binary_fold_jit(mapper, op, axes, ndim)(x)
    except (jax.errors.JAXTypeError, TypeError):
        # op cannot trace (concretizes/branches on values): host fold.
        # Device-side failures (OOM, bad shapes) surface unmasked.
        from ..utils.debug import warn_once
        warn_once(f"dreduce-host-{_fn_site(op)}",
                  f"dreduce: op {_fn_site(op)} "
                  "cannot be jax-traced; gathering to host for a scalar "
                  "left-fold")
        with _tm.span("mapreduce.host_fold"):
            res = _binary_reduce_host(np.asarray(x), mapper, op, axes, ndim)
    if axes is None:
        return res
    res = jnp.expand_dims(jnp.asarray(res), axes)  # keepdims, like _reduce_impl
    if isinstance(d, DArray):
        dist = [1 if i in axes else c for i, c in enumerate(d.pids.shape)]
        pids = [int(p) for p in d.pids.flat]
        return _wrap_global(res, procs=pids, dist=_fit_dist(res.shape, dist))
    return _wrap_global(res)


def _binary_reduce_host(x, mapper, op, axes, ndim):
    """Linear (left-fold) host reduction for ops that cannot trace.  Such
    ops are scalar Python functions, so the fold is applied per kept-axis
    position, scalar by scalar."""
    if mapper is not None:
        x = np.asarray(mapper(x))
    if axes is None:
        return functools.reduce(op, x.reshape(-1).tolist())
    keep = tuple(i for i in range(ndim) if i not in axes)
    v = np.transpose(x, axes + keep).reshape(
        (-1,) + tuple(x.shape[i] for i in keep))
    flat = v.reshape(v.shape[0], -1)
    cols = [functools.reduce(op, flat[:, j].tolist())
            for j in range(flat.shape[1])]
    return np.asarray(cols).reshape(v.shape[1:])


def _reduce_entry(d, mapper, reducer, dims=None, **kw):
    """``_reduce_impl`` for a public entry that is not ``dmapreduce``:
    under the same journaled ``mapreduce`` root span, so every reduction
    leaves one root span a call whichever entry it came through."""
    with _tm.span("mapreduce"):
        return _reduce_impl(d, mapper, reducer, dims=dims, **kw)


def _named(name):
    def f(d, dims=None, **kw):
        return _reduce_entry(d, None, _REDUCERS[name], dims=dims, **kw)
    f.__name__ = "d" + name
    return f


dsum = _named("sum")
dprod = _named("prod")
dmaximum = _named("max")
dminimum = _named("min")
dmean = _named("mean")
dall = _named("all")
dany = _named("any")


def dvar(d, dims=None, ddof=1):
    """Corrected (ddof=1) variance, matching Julia's Statistics.var default,
    in one read of the array: shifted sums accumulated in float32 (float64
    for float64) and finished on scalars, a second read only where the
    corner the shift is taken from is unlike the rest (``_moments``).
    Integer, boolean and complex input keep ``jnp.var``."""
    return _reduce_entry(d, None, _var, dims=dims, ddof=ddof)


def dstd(d, dims=None, ddof=1):
    """Sample std, matching Julia's Statistics.std default (corrected);
    reference ext/StatisticsExt.jl:6 builds mean from sum and reads the
    array again for the deviations.  Here it is one read: with ``k`` the
    mean of a corner of the array, ``sum(a - k)`` and ``sum((a - k)^2)``
    come out of one fused pass in float32 (float64 for float64) and the
    deviation is finished on scalars; only where that corner is unlike the
    rest (the shift lies further from the mean than one deviation) does a
    conditional on the device read the array a second time, about the
    exact mean (``_moments``).  Integer, boolean and complex input keep
    ``jnp.std``."""
    return _reduce_entry(d, None, _std, dims=dims, ddof=ddof)


def dcount(pred, d, dims=None):
    """count(pred, d) (reference mapreduce.jl:117-126)."""
    return _reduce_entry(d, lambda a: pred(a).astype(jnp.int32), jnp.sum,
                         dims=dims)


@functools.lru_cache(maxsize=64)
def _extrema_jit(axes):
    def fn(a):
        if axes is None:
            return jnp.min(a), jnp.max(a)
        return (jnp.min(a, axis=axes, keepdims=True),
                jnp.max(a, axis=axes, keepdims=True))
    return jax.jit(fn)


def dextrema(d, dims=None):
    """extrema(d) → (min, max) (reference mapreduce.jl:128-131)."""
    x = _unwrap(d)
    axes = _norm_dims(dims, np.ndim(x))
    lo, hi = _extrema_jit(axes)(x)
    if axes is None:
        return lo, hi
    return _wrap_global(lo), _wrap_global(hi)


# ---------------------------------------------------------------------------
# map_localparts / samedist
# ---------------------------------------------------------------------------


def _scan_impl(d: DArray, axis: int, kind: str) -> DArray:
    """Distributed inclusive scan along ``axis`` — the classic parallel
    prefix primitive (no reference analog; Julia's ``accumulate`` is not
    lifted to DArrays).  TPU-native path for even layouts: ONE shard_map
    program — local ``jnp.cum{sum,prod}``, ``all_gather`` of the (tiny)
    per-rank totals over the dim's mesh axis, each rank combining the
    totals of lower ranks into its offset.  Communication is O(p · slice)
    regardless of array size.  Uneven layouts run the SAME program over
    the blocked-padded physical buffer with per-rank valid extents from
    the cuts — no host gather on any layout."""
    if not isinstance(d, DArray):
        raise TypeError(f"expected DArray, got {type(d).__name__}")
    ax = axis + d.ndim if axis < 0 else axis
    if not 0 <= ax < d.ndim:
        raise ValueError(f"axis {axis} out of range for ndim {d.ndim}")
    if _even_shared_layout((d,)):
        name = d.sharding.spec[ax] if ax < len(d.sharding.spec) else None
        if name is None:
            res = _scan_local_jit(kind, ax)(d.garray)
        else:
            res = _scan_shm_jit(d.sharding.mesh, d.sharding.spec, kind,
                                ax, name)(d.garray)
        return _wrap_global(res, procs=[int(p) for p in d.pids.flat],
                            dist=list(d.pids.shape))

    # uneven: the SAME parallel-prefix program over the blocked-padded
    # physical buffer (PSRS-style, round-4) — local scan per block, the
    # per-block total read at each rank's VALID extent (from the cuts),
    # gathered along the scan dim's mesh axis.  No host gather; the
    # result keeps the exact padded storage + cut structure.
    vcounts = jnp.asarray(np.diff(np.asarray(d.cuts[ax])), jnp.int32)
    pspec = tuple(d._psharding.spec)
    fn = _scan_uneven_shm_jit(
        d._psharding, kind, ax,
        pspec[ax] if ax < len(pspec) else None)
    res = fn(d.garray_padded, vcounts)
    return DArray(res, d.pids, d.indices, d.cuts)


# kind -> (local scan, cross-rank combine, elementwise merge)
def _cum_extreme(op):
    def f(a, axis):
        if jnp.issubdtype(a.dtype, jnp.bool_):
            # lax.cummax/cummin reject bool; or-/and-scan via int8
            return op(a.astype(jnp.int8), axis=axis).astype(jnp.bool_)
        return op(a, axis=axis)
    return f


_SCAN_LOCAL = {"sum": jnp.cumsum, "prod": jnp.cumprod,
               "max": _cum_extreme(jax.lax.cummax),
               "min": _cum_extreme(jax.lax.cummin)}
_SCAN_COMBINE = {"sum": jnp.sum, "prod": jnp.prod,
                 "max": jnp.max, "min": jnp.min}
_SCAN_MERGE = {"sum": jnp.add, "prod": jnp.multiply,
               "max": jnp.maximum, "min": jnp.minimum}


def _scan_neutral(kind: str, dtype):
    """Identity element of the combine, dtype-aware for max/min: ±inf
    for floats (finfo.min would corrupt data containing infinities),
    False/True for bool (iinfo rejects it), iinfo bounds for ints."""
    if kind in ("sum", "prod"):
        return jnp.asarray(1 if kind == "prod" else 0, dtype)
    if jnp.issubdtype(dtype, jnp.bool_):
        return jnp.asarray(kind == "min", dtype)
    if jnp.issubdtype(dtype, jnp.inexact):
        return jnp.asarray(-jnp.inf if kind == "max" else jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.min if kind == "max" else info.max, dtype)


@functools.lru_cache(maxsize=128)
def _scan_local_jit(kind: str, ax: int):
    op = _SCAN_LOCAL[kind]
    return jax.jit(lambda a: op(a, axis=ax))


@functools.lru_cache(maxsize=64)
def _scan_uneven_shm_jit(psharding, kind: str, ax: int, name):
    """Compiled scan over the blocked-padded buffer of an UNEVEN layout:
    identical structure to ``_scan_shm_jit`` except each rank's chunk
    total is read at its valid extent (``vcounts``) instead of the block
    edge, and 0-sized chunks contribute the scan's neutral element.
    Positions past a block's valid extent hold garbage — exactly the pad
    zone the logical view never exposes."""
    local_scan = _SCAN_LOCAL[kind]
    from jax.sharding import PartitionSpec as _P

    def kernel(x, vcounts):
        loc = local_scan(x, axis=ax)
        if name is None:        # scan dim whole per rank: local only
            return loc
        r = jax.lax.axis_index(name)
        p = _axis_size(name)
        v = vcounts[r]
        neutral = _scan_neutral(kind, loc.dtype)
        tot = jax.lax.dynamic_index_in_dim(
            loc, jnp.maximum(v - 1, 0), ax, keepdims=True)
        tot = jnp.where(v > 0, tot, neutral)
        g = jax.lax.all_gather(tot, name)        # (p, ..., 1, ...)
        mask = (jnp.arange(p) < r).reshape((p,) + (1,) * loc.ndim)
        filled = jnp.where(mask, g, neutral)
        prefix = _SCAN_COMBINE[kind](filled, axis=0)
        return _SCAN_MERGE[kind](loc, prefix)

    return jax.jit(shard_map_compat(
        kernel, mesh=psharding.mesh,
        in_specs=(psharding.spec, _P()), out_specs=psharding.spec))


@functools.lru_cache(maxsize=128)
def _scan_shm_jit(mesh, spec, kind: str, ax: int, name: str):
    """One compiled SPMD scan program per (mesh, spec, kind, axis)."""
    local_scan = _SCAN_LOCAL[kind]

    def kernel(x):
        loc = local_scan(x, axis=ax)
        tot = jax.lax.index_in_dim(loc, loc.shape[ax] - 1, ax,
                                   keepdims=True)
        g = jax.lax.all_gather(tot, name)        # (p, ..., 1, ...)
        r = jax.lax.axis_index(name)
        p = _axis_size(name)
        mask = (jnp.arange(p) < r).reshape((p,) + (1,) * loc.ndim)
        filled = jnp.where(mask, g, _scan_neutral(kind, g.dtype))
        prefix = _SCAN_COMBINE[kind](filled, axis=0)
        return _SCAN_MERGE[kind](loc, prefix)

    return jax.jit(shard_map_compat(kernel, mesh=mesh, in_specs=spec,
                                 out_specs=spec))


def dcumsum(d: DArray, axis: int = 0) -> DArray:
    """Distributed cumulative sum along ``axis`` (inclusive), same layout
    as ``d`` — one compiled SPMD program: local cumsum per rank plus an
    all_gather of the per-rank totals for the prefix offsets."""
    return _scan_impl(d, axis, "sum")


def dcumprod(d: DArray, axis: int = 0) -> DArray:
    """Distributed cumulative product along ``axis`` (inclusive), same
    layout as ``d``."""
    return _scan_impl(d, axis, "prod")


def dcummax(d: DArray, axis: int = 0) -> DArray:
    """Distributed running maximum along ``axis`` (inclusive), same
    layout as ``d``."""
    return _scan_impl(d, axis, "max")


def dcummin(d: DArray, axis: int = 0) -> DArray:
    """Distributed running minimum along ``axis`` (inclusive), same
    layout as ``d``."""
    return _scan_impl(d, axis, "min")


def map_localparts(f: Callable, *ds, procs=None):
    """Apply ``f`` to each rank's chunk, building a new DArray from the
    results (reference map_localparts, mapreduce.jl:137-169).

    TPU-native path: when every argument shares one even layout and ``f`` is
    traceable, this is ``jax.shard_map`` — one compiled SPMD program, zero
    host traffic.  Fallback: eager host loop over logical chunks (needed for
    uneven layouts and untraceable ``f``), reassembled with ``from_chunks`` —
    chunk shapes may change, like the reference.
    """
    d0 = next(a for a in ds if isinstance(a, DArray))
    if _even_shared_layout(ds):
        try:
            mesh = d0.sharding.mesh
            specs = tuple(a.sharding.spec if isinstance(a, DArray) else None
                          for a in ds)
            shmapped = shard_map_compat(
                f, mesh=mesh, in_specs=specs, out_specs=d0.sharding.spec)
            raw = [a.garray if isinstance(a, DArray) else a for a in ds]
            res = jax.jit(shmapped)(*raw)
            return _wrap_global(res, procs=[int(p) for p in d0.pids.flat],
                                dist=list(d0.pids.shape))
        except Exception as e:
            # legitimate reasons to fall back: f untraceable, or f changes
            # the chunk shape (out_specs mismatch).  Either way the host
            # loop below re-runs f — a genuine error inside f surfaces
            # there — but the silent 100x slowdown must not be silent:
            from ..utils.debug import warn_once
            # stable key: qualname (or the callable's TYPE for partials/
            # callable objects) — a repr would embed id() and defeat the
            # once-per-site dedup
            fname = getattr(f, "__qualname__", None) or type(f).__name__
            warn_once(
                f"map_localparts:{fname}",
                f"map_localparts: shard_map fast path failed for "
                f"{fname!r} ({type(e).__name__}: {e}); falling back to "
                f"the eager host loop (untraceable or shape-changing f)")
    grid = d0.pids.shape
    for a in ds:
        if isinstance(a, DArray) and a.dims != d0.dims:
            raise ValueError(
                f"map_localparts args must share global dims: {a.dims} vs "
                f"{d0.dims}")
    out = np.empty(grid, dtype=object)
    for ci in np.ndindex(*grid):
        sl = tuple(slice(r.start, r.stop) for r in d0.indices[ci])
        # every arg is chunked by d0's layout; mismatched layouts are
        # resharded implicitly by the global slice (reference samedist,
        # mapreduce.jl:172-178)
        args = [a.garray[sl] if isinstance(a, DArray) else a for a in ds]
        out[ci] = np.asarray(f(*args))
    return from_chunks(out, procs=[int(p) for p in d0.pids.flat])


def map_localparts_into(f: Callable, dest: DArray, *ds):
    """In-place map_localparts (reference map_localparts!, mapreduce.jl:151-158)."""
    res = map_localparts(f, *ds)
    dest._rebind(res.garray)
    res._release_wrapper()  # buffer ownership moved into dest
    return dest


def _even_shared_layout(ds):
    d_arrs = [a for a in ds if isinstance(a, DArray)]
    if not d_arrs:
        return False
    d0 = d_arrs[0]
    if not all(a.sharding == d0.sharding for a in d_arrs):
        return False
    for cuts in d0.cuts:
        sizes = np.diff(cuts)
        if len(set(sizes.tolist())) > 1:
            return False
        if sizes.size and sizes[0] == 0:
            return False
    return True


def samedist(d: DArray, like: DArray) -> DArray:
    """Re-distribute ``d`` onto ``like``'s layout (reference samedist,
    mapreduce.jl:172-178) — planner-routed: divisible repartitions run as
    one compiled chunked collective, and an ALIGNED samedist is free: the
    result co-owns ``d``'s buffer (shared-ownership token, so ``close()``
    on either side cannot invalidate the other) instead of paying a
    full-array copy."""
    if d.dims != like.dims:
        raise ValueError(f"dims mismatch: {d.dims} vs {like.dims}")
    from ..darray import _fresh, _share_buffer
    g = d.garray
    if g.sharding == like.sharding:
        if not d._padded and not like._padded and g is d._data:
            # aligned fast path: rebind the existing buffer (no
            # device_put, no copy); buffer deletion deferred to the last
            # co-owner via the share token
            out = like.with_data(g)
            _share_buffer(d, out)
            return out
        # padded source: g is the transient unpadded view — already a
        # fresh buffer, safe to hand over without another copy
        return like.with_data(g)
    from ..parallel import reshard as _rs
    return like.with_data(
        _fresh(_rs.reshard(g, like.sharding, op="samedist"), g))


# ---------------------------------------------------------------------------
# mapslices / ppeval
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=512)
def _mapslices_jit(f, dims, ndim):
    """Traced mapslices: move batch dims to the front, flatten them, vmap
    once, and restore.  ``f`` must return an array of the same rank as its
    input slice (the dims it spans); sizes at those positions may change."""
    batch = tuple(i for i in range(ndim) if i not in dims)
    perm = batch + dims

    def fn(x):
        xt = jnp.transpose(x, perm)
        bshape = xt.shape[:len(batch)]
        sshape = xt.shape[len(batch):]
        flat = xt.reshape((int(np.prod(bshape)),) + sshape) if batch else \
            xt.reshape((1,) + sshape)
        resflat = jax.vmap(f)(flat)
        if resflat.ndim - 1 != len(dims):
            raise ValueError(
                f"mapslices: f must keep the slice rank ({len(dims)}), "
                f"got result rank {resflat.ndim - 1}")
        res = resflat.reshape(tuple(bshape) + resflat.shape[1:])
        inv = tuple(int(i) for i in np.argsort(perm))
        return jnp.transpose(res, inv)

    return jax.jit(fn)


def mapslices(f: Callable, d: DArray, dims) -> DArray:
    """Apply ``f`` to each slice spanning ``dims`` (reference mapslices,
    mapreduce.jl:191-208).

    The reference re-distributes so slice dims are whole per worker
    (mapreduce.jl:195-203); the XLA analog is to keep slice dims unsharded
    and vmap over the rest — GSPMD shards the batch dims across the mesh.
    Falls back to a host loop for untraceable ``f``.
    """
    dims = _norm_dims(dims, d.ndim)
    try:
        res = _mapslices_jit(f, dims, d.ndim)(d.garray)
        return _wrap_global(res, procs=[int(p) for p in d.pids.flat])
    except (jax.errors.TracerArrayConversionError, jax.errors.ConcretizationTypeError,
            TypeError):
        from ..utils.debug import warn_once
        warn_once(f"mapslices-host-{_fn_site(f)}",
                  f"mapslices: {_fn_site(f)} cannot "
                  "be jax-traced; gathering to host for a python slice "
                  "loop")
        host = np.asarray(d)
        res = _np_mapslices(f, host, dims)
        return distribute(res, procs=[int(p) for p in d.pids.flat])


def _np_mapslices(f, a, dims):
    batch = [i for i in range(a.ndim) if i not in dims]
    if not batch:
        return np.asarray(f(a))
    moved = np.moveaxis(a, batch, range(len(batch)))
    bshape = moved.shape[:len(batch)]
    first = None
    parts = {}
    for bi in np.ndindex(*bshape):
        r = np.asarray(f(moved[bi]))
        parts[bi] = r
        if first is None:
            first = r
    out = np.empty(bshape + first.shape, dtype=first.dtype)
    for bi, r in parts.items():
        out[bi] = r
    # move batch axes back, keeping slice-result axes in the slice positions
    return np.moveaxis(out, range(len(batch)), batch) \
        if first.shape == tuple(a.shape[i] for i in dims) else out


def ppeval(f: Callable, *ds, dim: int | None = None):
    """Evaluate ``f`` slicewise along ``dim`` (default: last), stacking
    results (reference ppeval, mapreduce.jl:210-323: validates each
    distributed arg is whole in non-slice dims, evaluates per worker).

    TPU-native: ``jax.vmap`` over the slice axis of every argument — the
    per-slice evals are batched into one XLA program and sharded over the
    mesh along the batch axis.
    """
    raw = [_unwrap(a) for a in ds]
    nd = [np.ndim(r) for r in raw]
    axes = [(np.ndim(r) - 1 if dim is None else dim) for r in raw]
    n = {int(np.shape(r)[ax]) for r, ax in zip(raw, axes)}
    if len(n) != 1:
        raise ValueError(f"slice-dim extents differ: {sorted(n)} "
                         "(reference mapreduce.jl:300-313)")
    res = _ppeval_jit(f, tuple(axes))(*raw)
    return _wrap_global(res)


@functools.lru_cache(maxsize=512)
def _ppeval_jit(f, axes):
    return jax.jit(jax.vmap(f, in_axes=axes, out_axes=-1))
