"""The array-program driver: a step is a short list of calls into the
``dat.*`` entry points on resident DArrays, ending in the scalars read to
the host.  The list, the arrays and their layouts come from the traffic
file; each operation is a file under ``array_ops/``."""

from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

import counts
import datagen


def _op_module(name):
    return importlib.import_module(f"array_ops.{name}")


class LazyRef:
    """A reference array that is never held whole: ``rows(r0, r1)`` makes a
    row range (block-aligned) as float32 on the first device."""

    def __init__(self, shape, rows_fn, block_rows, f64=None):
        self.shape = tuple(int(s) for s in shape)
        self._rows, self._f64, self.block_rows = rows_fn, f64, block_rows
        self._whole = self._moments = None

    def rows(self, r0, r1):
        return self._rows(r0, r1)

    def whole(self):
        if self._whole is None:
            self._whole = self._rows(0, self.shape[0])
        return self._whole

    def has_f64(self):
        return self._f64 is not None

    def f64(self, rows):
        """The given rows (global ids inside ONE block) in numpy float64."""
        return self._f64(rows)

    def moments(self):
        """(count, sum, sum of squares) in float64 on the host, from
        float32 sums of each row."""
        if self._moments is None:
            s1 = s2 = 0.0
            for r0 in range(0, self.shape[0], self.block_rows):
                blk = self.rows(r0, min(r0 + self.block_rows, self.shape[0]))
                s1 += float(np.sum(np.asarray(jnp.sum(blk, axis=1),
                                              np.float64)))
                s2 += float(np.sum(np.asarray(jnp.sum(blk * blk, axis=1),
                                              np.float64)))
            n = float(self.shape[0]) * float(self.shape[1])
            self._moments = (n, s1, s2)
        return self._moments


class RefEnv:
    """The plain reference of a whole step, built lazily from the seed."""

    def __init__(self, lowp, block_rows):
        self.lowp, self.block_rows = lowp, block_rows
        self.arrays, self.scalars = {}, {}

    def lazy(self, shape, rows_fn, f64=None):
        return LazyRef(shape, rows_fn, self.block_rows, f64)


class Env:
    """The program's side of a step: live DArrays and pending scalars."""

    def __init__(self, devices, itemsize):
        self.devices = list(devices)
        self.ranks = list(range(len(self.devices)))
        self.itemsize = itemsize
        self.arrays, self.scalars, self.layout = {}, {}, {}
        self.audit = False
        self.misplaced = 0

    def put(self, name, darr):
        """Bind ``name`` to a new array; the one it replaces is released
        only now, after its successor exists."""
        old = self.arrays.get(name)
        self.arrays[name] = darr
        if old is not None:
            old.close()

    def count_misplaced(self, darr, grid):
        """Shards of ``darr`` that are not the block its layout gives the
        device's rank (rank r owns block r of ``grid``, row-major)."""
        rows, cols = darr.garray.shape
        gr, gc = int(grid[0]), int(grid[1])
        rank_of = {d: r for r, d in enumerate(self.devices)}
        bad, seen = 0, 0
        for sh in darr.garray.addressable_shards:
            r = rank_of.get(sh.device)
            if r is None or r >= gr * gc:
                bad += 1
                continue
            i, j = divmod(r, gc)
            want = ((i * rows // gr, (i + 1) * rows // gr),
                    (j * cols // gc, (j + 1) * cols // gc))
            got = tuple(s.indices(n)[:2] for s, n in zip(sh.index,
                                                         (rows, cols)))
            bad += got != want
            seen += 1
        return bad + (gr * gc - seen if seen < gr * gc else 0)


class Driver:
    tokens_per_step = 0

    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.sizes = dict(t["sizes"])
        if ctx.tiny:
            self.sizes.update(t.get("tiny", {}))
        self.block_rows = int(self.sizes["block_rows"])
        self.dtype = np.dtype(ctx.config.get("dtype", "float32"))
        # the nearest precision below the stated one: what the control runs in
        self.control_lowp = {"float32": "bfloat16"}[self.dtype.name]
        self.steps_spec = [dict(s) for s in t["step"]]
        self.ops = [_op_module(s["op"]) for s in self.steps_spec]
        self.keep = list(t.get("keep", []))
        self.audit_keep = list(t.get("audit_keep", []))
        self.env = Env(ctx.devices, self.dtype.itemsize)
        self.history = {}
        self.array_specs = {
            name: (tuple(self._dim(d) for d in a["shape"]), tuple(a["grid"]))
            for name, a in t["arrays"].items()}
        self.env.layout.update(self.array_specs)
        for op, spec in zip(self.ops, self.steps_spec):
            self.env.layout.update(op.out_layout(self.env, spec))

    def _dim(self, d):
        return int(self.sizes[d]) if isinstance(d, str) else int(d)

    def _key(self, name):
        return datagen.named_key(self.ctx.seed, f"array.{name}")

    # -- set-up -------------------------------------------------------------

    def setup(self):
        import distributedarrays_tpu as dat
        for name, (shape, grid) in self.array_specs.items():
            arr = datagen.make_array(self._key(name), shape,
                                     jnp.dtype(self.dtype), self.block_rows,
                                     self.env.devices, grid)
            self.env.arrays[name] = dat.distribute(
                arr, procs=self.env.ranks[:grid[0] * grid[1]], dist=grid)
            del arr
        jax.block_until_ready([a.garray for a in self.env.arrays.values()])
        self.ctx.mark("program imported, arrays made and distributed")
        for op, spec in zip(self.ops, self.steps_spec):
            op.prepare(self.env, spec)
        for _ in range(2):                      # every shape, twice
            self.step()
        jax.block_until_ready([a.garray for a in self.env.arrays.values()])
        self.ctx.mark("two warm-up steps done")
        self.begin_window()

    def begin_window(self):
        self.history = {}
        self.env.misplaced = 0

    # -- one step -------------------------------------------------------------

    def step(self, span=None):
        env = self.env
        env.scalars = {}
        if span is None:
            for op, spec in zip(self.ops, self.steps_spec):
                op.run(env, spec)
            t_dispatched = time.perf_counter()
            vals = {k: float(v) for k, v in env.scalars.items()}
        else:
            with span("bench.dispatch"):
                for op, spec in zip(self.ops, self.steps_spec):
                    with span("bench.op." + spec["op"]):
                        op.run(env, spec)
            t_dispatched = time.perf_counter()
            with span("bench.read"):
                vals = {k: float(v) for k, v in env.scalars.items()}
        for k, v in vals.items():
            self.history.setdefault(k, []).append(v)
        return t_dispatched, all(np.isfinite(v) for v in vals.values())

    def cost(self):
        """The step's least work.  With ``fold_reductions`` a reduction's
        read is left out: it could ride on the producer of its array, and
        how often an implementation re-reads is its own affair."""
        fold = bool(self.ctx.traffic.get("fold_reductions"))
        total = counts.Cost()
        for op, spec in zip(self.ops, self.steps_spec):
            c = op.cost(self.env, spec)
            if fold and spec["op"] == "reduce":
                c = counts.Cost(c.flops, 0.0, c.ici_bytes)
            total = total + c
        return total

    # -- after the window -----------------------------------------------------

    def finish(self):
        """One audit step that keeps its intermediates (same entries, same
        shapes, nothing compiles), then hand over what the path produced."""
        if self.audit_keep:
            self.env.audit = True
            saved = {k: list(v) for k, v in self.history.items()}
            self.step()
            self.history = saved
            self.env.audit = False
        names = self.keep + self.audit_keep
        out = ProgramOutputs({n: self.env.arrays[n] for n in names},
                             self.history, self.env.misplaced,
                             self.env.devices[0])
        for n in list(self.env.arrays):
            if n not in names:
                self.env.arrays.pop(n).close()
        return out

    def release(self, outputs):
        for d in outputs.darrays.values():
            d.close()
        self.env.arrays.clear()

    def reference(self, lowp=None):
        refenv = RefEnv(lowp, self.block_rows)
        dev0 = self.env.devices[0]
        br = self.block_rows
        for name, (shape, _) in self.array_specs.items():
            key = jax.device_put(self._key(name), dev0)
            cols, dt = shape[1], jnp.dtype(self.dtype)

            def rows(r0, r1, key=key, cols=cols, dt=dt):
                if r0 % br or (r1 - r0) % br:
                    raise ValueError("reference rows must be block-aligned")
                with jax.default_device(dev0):
                    return datagen._rows_jit(key, r0 // br, (r1 - r0) // br,
                                             br, cols, dt)

            def f64(ids, rows=rows):
                b0 = (ids[0] // br) * br
                picked = rows(b0, b0 + br)[np.asarray(ids) - b0]
                return np.asarray(picked).astype(np.float64)

            refenv.arrays[name] = refenv.lazy(shape, rows, f64)
        for op, spec in zip(self.ops, self.steps_spec):
            op.ref(refenv, spec)
        return refenv

    def control_outputs(self, lowp):
        """The reference computed in ``lowp``, put in the program's place."""
        ctl = self.reference(lowp)
        hist = {k: [f()] for k, f in ctl.scalars.items()}
        return RefOutputs(ctl, hist)

    def compare(self, outputs, refenv):
        """Every number compared, as {name: value}: each kept array against
        the reference over all its elements (and sampled rows against numpy
        float64 where the chain has one), every step's scalars, and the
        shards found off their device."""
        rng = np.random.default_rng(self.ctx.seed % (2 ** 32))
        n_sample = int(self.ctx.traffic.get("sample_rows", 32))
        numbers = {}
        br = self.block_rows
        for name in self.keep + self.audit_keep:
            ref = refenv.arrays[name]
            n_rows = ref.shape[0]
            sample = np.sort(rng.choice(n_rows, min(n_sample, n_rows),
                                        replace=False))
            dmax = rmax = 0.0
            d64 = r64 = 0.0
            for r0 in range(0, n_rows, br):
                r1 = min(r0 + br, n_rows)
                got = outputs.rows(name, r0, r1)
                want = ref.rows(r0, r1)
                d, m = _max_diff(got, want)
                d, m = float(d), float(m)
                dmax = d if not d <= dmax else dmax     # NaN sticks
                rmax = max(rmax, m)
                ids = sample[(sample >= r0) & (sample < r1)]
                if ref.has_f64() and len(ids):
                    w = ref.f64([int(i) for i in ids])
                    g = np.asarray(got[ids - r0], np.float64)
                    dd = float(np.max(np.abs(g - w))) if np.all(
                        np.isfinite(g)) else float("nan")
                    d64 = dd if not dd <= d64 else d64
                    r64 = max(r64, float(np.max(np.abs(w))))
            numbers[f"{name}_max_rel"] = dmax / max(rmax, 1e-30)
            if ref.has_f64():
                numbers[f"{name}_rows_f64_max_rel"] = d64 / max(r64, 1e-30)
        for sname, fn in refenv.scalars.items():
            want = fn()
            hist = np.asarray(outputs.history.get(sname, [np.nan]),
                              np.float64)
            err = np.abs(hist - want) / max(abs(want), 1e-30)
            numbers[f"{sname}_rel"] = (float(np.max(err)) if np.all(
                np.isfinite(err)) and len(err) else float("nan"))
        if any(s["op"] == "redistribute" for s in self.steps_spec):
            numbers["misplaced"] = float(outputs.misplaced)
        return numbers


@jax.jit
def _max_diff(got, want):
    return (jnp.max(jnp.abs(got.astype(jnp.float32) - want)),
            jnp.max(jnp.abs(want)))


class ProgramOutputs:
    """What the timed path left: its kept DArrays, every step's scalars."""

    def __init__(self, darrays, history, misplaced, dev0):
        self.darrays, self.history = darrays, history
        self.misplaced, self.dev0 = misplaced, dev0

    def rows(self, name, r0, r1):
        """Rows [r0, r1) of a kept array on the first device, put together
        from the shards that hold them (a row block never straddles a shard:
        block rows divide every layout's row cuts)."""
        g = self.darrays[name].garray
        n_rows, n_cols = g.shape
        pieces = []
        for sh in g.addressable_shards:
            (a, b, _), (c, _, _) = (s.indices(n) for s, n in
                                    zip(sh.index, (n_rows, n_cols)))
            if a <= r0 and r1 <= b:
                part = _slice_rows(sh.data, r0 - a, r1 - r0)
                pieces.append((c, jax.device_put(part, self.dev0)))
            elif not (r1 <= a or b <= r0):
                raise ValueError(f"rows {r0}:{r1} straddle a shard of {name}")
        pieces.sort(key=lambda p: p[0])
        if len(pieces) == 1:
            return pieces[0][1]
        return jnp.concatenate([p for _, p in pieces], axis=1)


_slice_rows = jax.jit(jax.lax.dynamic_slice_in_dim, static_argnums=(2,))


class RefOutputs:
    """A reference (the control) standing where the program's outputs do."""

    def __init__(self, refenv, history):
        self.refenv, self.history, self.misplaced = refenv, history, 0

    def rows(self, name, r0, r1):
        return self.refenv.arrays[name].rows(r0, r1)
