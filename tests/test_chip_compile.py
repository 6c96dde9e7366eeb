"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``v5e:2x2``).  These cases keep the answers of
the PR 21 bring-up as tests: the kernels of ``chip_smoke.py``'s main path
at their real widths, the ring kernels on a four-chip mesh, and the whole
transformer ``train_step`` — each must lower through Mosaic
(``tpu_custom_call`` in the compiled text), so a later PR that breaks a
kernel's tiling, VMEM budget or partitioning is refused here at no chip
time.  A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (never while a
module is imported: only one process at a time may load the TPU's
library, and every xdist worker imports every test file).  Code that asks
``_on_tpu()`` still sees the CPU here, so the tests steer it with
``monkeypatch``.
"""

import json
import re
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from distributedarrays_tpu import parallel
from distributedarrays_tpu.models import transformer as T
from distributedarrays_tpu.ops import pallas_attention as PA
from distributedarrays_tpu.ops import pallas_collectives as PC
from distributedarrays_tpu.ops import pallas_gemm as PG
from distributedarrays_tpu.ops import pallas_stencil as PS
from distributedarrays_tpu.telemetry import programs

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ring_mesh(topo):
    return Mesh(np.asarray(topo.devices, dtype=object).reshape(4), ("d0",))


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer every module's platform test to the chip's branch."""
    for mod in (PG, PA, PC, PS):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)


def _placed(step, text):
    """({kernel: (phase, pass)}, {fusion: (phase, pass)}) of a registered
    step: every ``tpu_custom_call`` and every fusion instruction of its
    compiled text must have an entry in its phase map."""
    pmap = programs.phase_map(step)
    kernels = re.findall(
        r"^\s+(?:ROOT )?%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
        text, re.M)
    fusions = re.findall(r"^\s+(?:ROOT )?%(\S+) = .*? fusion\(", text, re.M)
    assert kernels and len(fusions) > 100
    missing = [n for n in kernels + fusions if n not in pmap]
    assert not missing, missing[:5]
    return ({n: pmap[n][1:] for n in kernels},
            {n: pmap[n][1:] for n in fusions})


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _assert_kernel(fn, *shapes):
    assert "tpu_custom_call" in _compiled_text(fn, *shapes)


# ---------------------------------------------------------------------------
# one-chip kernels at chip_smoke.py's widths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_pallas_matmul_4096(one_chip, dtype):
    a = jax.ShapeDtypeStruct((4096, 4096), dtype, sharding=one_chip)
    _assert_kernel(lambda a, b: PG.pallas_matmul(a, b, interpret=False),
                   a, a)


def test_pallas_matmul_int8_4096(one_chip):
    q = jax.ShapeDtypeStruct((4096, 4096), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    _assert_kernel(lambda qa, qb, sa, sb: PG.pallas_matmul_int8(
        qa, qb, sa, sb, interpret=False), q, q, s, s)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("heads,d", [(8, 64), (4, 128)], ids=["d64", "d128"])
def test_flash_forward_8192_default_blocks(one_chip, heads, d, causal):
    q = jax.ShapeDtypeStruct((8192, heads, d), jnp.bfloat16,
                             sharding=one_chip)
    _assert_kernel(lambda q, k, v: PA.flash_attention(
        q, k, v, causal=causal, interpret=False), q, q, q)


def _seed_flash_entries():
    seed = json.loads((REPO / "AUTOTUNE_SEED.json").read_text())
    return sorted(seed["flash_attention"].items())


@pytest.mark.parametrize("idx", range(3))
def test_flash_forward_with_seeded_blocks(one_chip, idx):
    # device_key_for sees the CPU here and never matches the seed, so the
    # blocks AUTOTUNE_SEED.json would select on the chip are passed in
    entries = _seed_flash_entries()
    assert len(entries) == 3, "AUTOTUNE_SEED.json flash entries changed"
    key, (bq, bk) = entries[idx]
    s, h, d, dtype, causal = key.split("|")[:5]
    q = jax.ShapeDtypeStruct((int(s), int(h), int(d)), jnp.dtype(dtype),
                             sharding=one_chip)
    _assert_kernel(lambda q, k, v: PA.flash_attention(
        q, k, v, causal=causal == "True", block_q=bq, block_k=bk,
        interpret=False), q, q, q)


@pytest.mark.parametrize("heads,d", [(16, 64), (8, 128)], ids=["d64", "d128"])
def test_flash_backward_2048(one_chip, heads, d):
    q = jax.ShapeDtypeStruct((2048, heads, d), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(PA.flash_attention(q, k, v, causal=True,
                                          interpret=False)
                       .astype(jnp.float32))

    txt = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    # forward and the one-sweep backward (a head's dQ fits VMEM here)
    assert txt.count("tpu_custom_call") >= 2
    assert "flash_fwd" in txt and "flash_bwd_dkv" in txt


@pytest.mark.parametrize(
    "s,heads,d,backward",
    [(1024, 128, 64, ("flash_bwd_dkv",)),
     (8192, 32, 128, ("flash_bwd_dkv",)),
     (16384, 8, 128, ("flash_bwd_dkv",)),
     (8192, 20, 256, ("flash_bwd_dkv",)),
     (16384, 4, 256, ("flash_bwd_dkv",)),
     (32768, 4, 256, ("flash_bwd_dq", "flash_bwd_dkv"))],
    ids=["gpt2m_cell_d64", "s8k_d128", "s16k_d128", "glm_cell_d256",
         "s16k_d256", "s32k_d256_past_the_cap"])
def test_flash_kernels_at_the_benchmark_shapes(one_chip, s, heads, d,
                                               backward):
    # the shape gpt2m_train calls and the long wide-head shape (one fused
    # backward sweep each under the default VMEM limit), the shapes whose
    # resident dQ asks for a limit of its own (16 MiB of dQ a head at
    # s16k_d128 and at glm47f_train_s8k's width 256, 32 MiB at s16k_d256:
    # the chip's compiler has to take the kernel under the limit the
    # shapes reckon), and a sequence whose need passes the cap (two
    # passes), blocks and fold as flash_attention picks them
    q = jax.ShapeDtypeStruct((s, heads, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(PA.flash_attention(q, k, v, causal=True,
                                          interpret=False)
                       .astype(jnp.float32))

    txt = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert txt.count("tpu_custom_call") >= 1 + len(backward)
    for name in ("flash_fwd",) + backward:
        assert name in txt, name
    assert ("flash_bwd_dq" in txt) == ("flash_bwd_dq" in backward)


@pytest.mark.parametrize("window", [512, None], ids=["window", "full"])
def test_flash_kernels_at_the_hybrid_cell_shapes(one_chip, window):
    # what phi4mf_train_s8k calls: 40 query heads of 64 on 20 key heads and
    # 10 value heads of 128, 8192 positions, windowed and not
    sds = lambda h, d: jax.ShapeDtypeStruct((8192, h, d), jnp.bfloat16,
                                            sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(PA.flash_attention(q, k, v, causal=True, window=window,
                                          interpret=False)
                       .astype(jnp.float32))

    txt = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), sds(40, 64),
                         sds(20, 64), sds(10, 128))
    for name in ("flash_fwd", "flash_bwd_dkv"):
        assert name in txt, name


def test_selective_scan_kernels_at_the_hybrid_cell_shapes(one_chip):
    from distributedarrays_tpu.ops import pallas_selective_scan as PS
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    def loss(x, dt, a, b, c):
        return jnp.sum(PS.selective_scan(x, dt, a, b, c, interpret=False))

    txt = _compiled_text(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                         sds(8192, 5120), sds(8192, 5120), sds(5120, 16),
                         sds(8192, 16), sds(8192, 16))
    for name in ("selective_scan_fwd", "selective_scan_bwd"):
        assert name in txt, name


def test_ssd_kernels_at_the_granite_cell_shapes(one_chip):
    # what granite4h_train_s8k calls: 64 heads of 64, one group of B and C
    # with 128 states, 8192 positions in chunks of 256; both kernels name
    # the VMEM limit their plan reckons
    from distributedarrays_tpu.ops import pallas_ssd as SSD
    sds = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(s, d,
                                                        sharding=one_chip)

    def loss(x, dt, a, b, c):
        return jnp.sum(SSD.ssd(x, dt, a, b, c, interpret=False))

    txt = _compiled_text(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                         sds((8192, 64, 64), jnp.bfloat16), sds((8192, 64)),
                         sds((64,)), sds((8192, 1, 128), jnp.bfloat16),
                         sds((8192, 1, 128), jnp.bfloat16))
    for name in ("ssd_fwd", "ssd_bwd"):
        assert name in txt, name
    limit = SSD.ssd_plan(8192, 64, 64, 1, 128)["vmem_bytes"]
    assert txt.count(f'"size":"{limit}"') >= 2


_INSTR = re.compile(r"^(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w\-]+)\((.*?)\)")
# instructions that hand a buffer on unchanged (a view, a tuple's part, a
# move between memory spaces): what lies between two computations
_PLUMBING = ("bitcast", "get-tuple-element", "copy-start", "copy-done")


def _entry(text):
    """{name: (opcode, operand names, line)} of the entry computation."""
    name = re.search(r"^ENTRY %(\S+) ", text, re.M).group(1)
    out = {}
    for line in _computations(text)[name]:
        m = _INSTR.match(line)
        if m:
            ops = [o.strip().split(" ")[-1].lstrip("%")
                   for o in m.group(4).split(",") if o.strip()]
            out[m.group(1)] = (m.group(3), ops, line)
    return out


def _is_product(comps, entry, name):
    """Whether ``name`` is, past plumbing, a fusion that holds a product."""
    while entry[name][0] in _PLUMBING:
        name = entry[name][1][0]
    op, _, line = entry[name]
    called = re.search(r"calls=%([\w.\-]+)", line)
    return op == "fusion" and any(
        _INSTR.match(l) and _INSTR.match(l).group(3) in ("convolution", "dot")
        for l in comps[called.group(1)])


def _readers(entry, name):
    """The instructions that read ``name``'s buffer, past plumbing."""
    out, todo = [], [name]
    while todo:
        n = todo.pop()
        for u, (op, ops, _) in entry.items():
            if n in ops:
                (todo if op in _PLUMBING else out).append(u)
    return out


def test_gpt2m_attention_block_reads_and_writes_in_place(one_chip, on_tpu):
    # gpt2m_train's attention block, forward and backward, at (8, 1024, 16
    # heads of 64): the q, k, v products feed the flash kernels and the
    # kernels' results feed the output projection and the weight
    # gradients as they lie; no copy or transpose anywhere in the program
    # (copy-start/-done move a buffer between memory spaces, not layouts):
    # one (B, S, 3E) product in, one (B, S, 3E) gradient out
    from distributedarrays_tpu import telemetry as tm
    B, S, H, D = 8, 1024, 16, 64
    E = H * D
    sd = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)

    def block(x, blk, g):
        y, vjp = jax.vjp(lambda x, blk: T._attention(x, blk, H), x, blk)
        return y, vjp(g)

    PA._build.cache_clear()
    PA._build_bwd.cache_clear()
    txt = _compiled_text(block, sd(B, S, E),
                         {"qkv": sd(E, 3 * E), "proj": sd(E, E)}, sd(B, S, E))
    comps, entry = _computations(txt), _entry(txt)
    moved = [l for lines in comps.values() for l in lines
             if _INSTR.match(l) and _INSTR.match(l).group(3) in (
                 "copy", "transpose")]
    assert not moved, moved[:3]
    kernels = {n: v for n, v in entry.items() if n.startswith("flash_")}
    assert sorted(n.split(".")[0] for n in kernels) == [
        "flash_bwd_dkv", "flash_fwd"]
    fwd = next(v for n, v in kernels.items() if n.startswith("flash_fwd"))
    # q, k and v: the one product's result, read three times
    assert len(set(fwd[1])) == 1 and _is_product(comps, entry, fwd[1][0])
    # the output, and the packed gradient of q, k and v: read by products
    # (the projection; the weight gradient and dX), and O by the backward
    # kernel
    outputs = [part for kname in kernels for part, (op, ops, line)
               in entry.items() if re.match(r"\S+ = bf16", line) and (
                   part == kname or op == "get-tuple-element"
                   and ops == [kname])]
    assert len(outputs) == 2
    for part in outputs:
        users = _readers(entry, part)
        assert users and all(_is_product(comps, entry, u) or u in kernels
                             for u in users), (part, users)

    def read(s, d, what):
        return tm.gauge_value("pallas.flash_attention.plan", kernel="flash_fwd",
                              s=s, d=d, causal=True, what=what)

    assert read(S, D, "lane_heads") == 2 and read(S, D, "fold") == 1
    # glm47f_train_s8k's width: a head of 256 goes head-major (its q and k
    # are built a head at a time, so the (B, S, H x D) view would be a copy)
    q = jax.ShapeDtypeStruct((1, 8192, 20, 256), jnp.bfloat16)
    jax.eval_shape(lambda q: PA.flash_attention(q, q, q, causal=True), q)
    assert read(8192, 256, "lane_heads") == 0


def test_flash_kernels_at_the_latent_cell_shape(one_chip):
    # what glm47f_train_s8k calls: 20 heads of 256 (192 + 64 rotary) on
    # values of 256, 8192 positions; a head's dQ (16 MiB resident) fits
    # VMEM under the limit the fused kernel names for itself, so the
    # backward is one sweep and nothing reaches it replicated over lanes
    q = jax.ShapeDtypeStruct((8192, 20, 256), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(PA.flash_attention(q, k, v, causal=True,
                                          interpret=False)
                       .astype(jnp.float32))

    limit = PA._fused_backward(8192, 256, "bfloat16", 1, False)[1]
    txt = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    for name in ("flash_fwd", "flash_bwd_dkv"):
        assert name in txt, name
    assert "flash_bwd_dq" not in txt
    # the kernel's scoped VMEM is the limit the shapes reckoned
    assert f'"size":"{limit}"' in txt
    assert "f32[20,8192,128]" not in txt


def _computations(text):
    """{name: [instruction lines]} of a compiled module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%(\S+) \(.*\{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            cur.append(line.strip())
    return comps


def _readers_of_the_whole(text, shape):
    """The instructions outside a conditional's branches that read a whole
    ``shape`` operand: not the plumbing that hands it on by reference, and
    not a ``slice`` (bare, or the only use a fusion makes of it)."""
    comps = _computations(text)
    called = {n: set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)",
                                " ".join(ls))) for n, ls in comps.items()}
    inside = set()
    for names in re.findall(r"branch_computations=\{([^}]*)\}", text):
        todo = [n.strip().lstrip("%") for n in names.split(",")]
        while todo:
            n = todo.pop()
            if n not in inside:
                inside.add(n)
                todo.extend(called.get(n, ()))
    fused = set().union(*called.values())
    head = re.compile(r"^(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w\-]+)\((.*?)\)")

    def slices_only(comp, index):
        lines = comps[comp]
        param = next(head.match(l).group(1) for l in lines
                     if f" parameter({index})" in l)
        users = [head.match(l) for l in lines
                 if re.search(rf"%{re.escape(param)}\b", l.split(" = ", 1)[1])]
        return users and all(u.group(3) == "slice" for u in users)

    readers = []
    for name, lines in comps.items():
        if name in inside or name in fused:
            continue
        whole = {head.match(l).group(1) for l in lines
                 if head.match(l) and head.match(l).group(2).startswith(shape)}
        for l in lines:
            m = head.match(l)
            if not m or m.group(3) in ("tuple", "get-tuple-element", "bitcast",
                                       "conditional", "slice", "parameter"):
                continue
            ops = [o.strip().split(" ")[-1].lstrip("%")
                   for o in m.group(4).split(",")]
            for i, o in enumerate(ops):
                if o in whole and not (
                        m.group(3) == "fusion" and slices_only(
                            re.search(r"calls=%([\w.\-]+)", l).group(1), i)):
                    readers.append(l)
    return readers


@pytest.mark.parametrize("cell", ["stream_one_chip", "stream_mapped",
                                  "reshard_2x2"])
def test_deviation_reads_the_array_once_at_the_cell_shapes(topo, one_chip,
                                                            cell):
    """``dstd`` as the chip's compiler writes it: ONE instruction over the
    whole array outside the second-pass branch (the fusion that yields both
    shifted sums, a mapper inside it), the conditional taking the array
    by reference (no ``copy`` of its shape, temporaries of kilobytes), and
    on a (2,2) mesh no ``all-gather`` and only scalar ``all-reduce``s."""
    from distributedarrays_tpu.ops import mapreduce as MR
    if cell.startswith("stream"):
        local, x = (20480, 20480), jax.ShapeDtypeStruct(
            (20480, 20480), jnp.float32, sharding=one_chip)
    else:
        mesh = Mesh(np.asarray(topo.devices, dtype=object).reshape(2, 2),
                    ("d0", "d1"))
        local, x = (16384, 24576), jax.ShapeDtypeStruct(
            (32768, 49152), jnp.float32,
            sharding=NamedSharding(mesh, P("d0", "d1")))
    mapper = jnp.square if cell == "stream_mapped" else None
    compiled = MR._reduction_jit(mapper, MR._std, None,
                                 (("ddof", 1),)).lower(x).compile()
    text = compiled.as_text()
    shape = f"f32[{local[0]},{local[1]}]"
    readers = _readers_of_the_whole(text, shape)
    assert len(readers) == 1, readers
    assert re.match(r"%\S+ = \(f32\[\]\S*, f32\[\]\S*\) fusion\(",
                    readers[0]), readers[0]
    assert len(re.findall(r" conditional\(", text)) == 1
    assert not re.findall(rf"= {re.escape(shape)}\S* copy(?:-start)?\(",
                          text)
    assert "all-gather" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    for shapes in re.findall(r" = (\(.*?\)|\S+) all-reduce(?:-start)?\(",
                             text):
        for dims in re.findall(r"\[([\d,]*)\]", shapes):
            assert int(np.prod([int(n) for n in dims.split(",") if n])) == 1
    print(f"{cell}: temp {compiled.memory_analysis().temp_size_in_bytes} "
          f"bytes; the reader: {readers[0][:120]}")


def test_stencil5_block_8192(one_chip):
    x = jax.ShapeDtypeStruct((8192, 8192), jnp.float32, sharding=one_chip)
    h = jax.ShapeDtypeStruct((1, 8192), jnp.float32, sharding=one_chip)
    _assert_kernel(lambda x, lo, hi: PS.stencil5_block(
        x, lo, hi, interpret=False), x, h, h)


def test_stencil5_multistep_8192_k8(one_chip):
    x = jax.ShapeDtypeStruct((8192, 8192), jnp.float32, sharding=one_chip)
    h = jax.ShapeDtypeStruct((8, 8192), jnp.float32, sharding=one_chip)
    _assert_kernel(lambda x, lo, hi: PS.stencil5_multistep(
        x, lo, hi, 8, True, True, interpret=False), x, h, h)


# ---------------------------------------------------------------------------
# ring kernels on the four-chip mesh
# ---------------------------------------------------------------------------


def _ring_text(mesh, f, in_specs, out_spec, *shapes):
    fn = parallel.run_spmd(f, mesh, in_specs=in_specs, out_specs=out_spec)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=NamedSharding(mesh, sp))
            for (s, dt), sp in zip(shapes, in_specs)]
    return fn.lower(*args).compile().as_text()


def test_ring_all_gather_4chips(ring_mesh, on_tpu):
    txt = _ring_text(
        ring_mesh,
        lambda x: PC.ring_all_gather(x, "d0", dim=0, interpret=False),
        (P("d0", None),), P("d0", None), ((4 * 4096, 4096), jnp.float32))
    assert "tpu_custom_call" in txt


def test_ring_all_to_all_4chips(ring_mesh, on_tpu):
    txt = _ring_text(
        ring_mesh,
        lambda x: PC.ring_all_to_all(x, "d0", split_dim=1, concat_dim=0,
                                     interpret=False),
        (P("d0", None),), P("d0", None), ((4 * 4096, 4096), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("leg", ["leg1-p4-single-axis", "leg3-p2-mesh-axis"])
def test_ring_all_to_all_at_the_reshard_cell_shapes(topo, ring_mesh, on_tpu,
                                                    leg):
    # the two owned kernels of the benchmark's 2x2 cycle at X 32768x49152
    # f32, with the send window they run there: leg 1 is (4,1)->(1,4) on
    # the 1-D mesh, three destinations in flight behind a slot pair each
    # (4 chunks of 96 MiB a destination); leg 3's a2a step is the p=2
    # sub-ring along d1 of the (2,2) mesh, MESH device ids, 12 chunks
    from distributedarrays_tpu import telemetry as tm
    if leg.startswith("leg1"):
        mesh, p, local = ring_mesh, 4, (8192, 49152)
        f = lambda x: PC.ring_all_to_all(  # noqa: E731
            x, "d0", split_dim=1, concat_dim=0, interpret=False)
        spec, ospec, shape = P("d0", None), P(None, "d0"), (32768, 49152)
        window = "3x2"
    else:
        mesh = Mesh(np.asarray(topo.devices, dtype=object).reshape(2, 2),
                    ("d0", "d1"))
        p, local = 2, (16384, 24576)
        f = lambda x: PC.ring_all_to_all(  # noqa: E731
            x, "d1", split_dim=0, concat_dim=1, interpret=False,
            mesh_axes=("d0", "d1"))
        spec, ospec, shape = P("d0", "d1"), P(("d0", "d1"), None), \
            (32768, 49152)
        window = "1x2"
    nc, _ = PC.a2a_chunks_for(local, "float32", p,
                              0 if leg.startswith("leg1") else 1)
    assert nc > 2 and PC.a2a_inflight(p, nc) == window
    before = tm.counter_value("pallas_collectives.dispatch",
                              op="ring_all_to_all", path="rdma",
                              inflight=window)
    txt = _ring_text(mesh, f, (spec,), ospec, (shape, jnp.float32))
    assert "tpu_custom_call" in txt and "ring_all_to_all" in txt
    assert tm.counter_value("pallas_collectives.dispatch",
                            op="ring_all_to_all", path="rdma",
                            inflight=window) == before + 1


def test_block_exchange_at_the_reshard_cell_shape_relays_by_coords(topo):
    # leg 2 of the benchmark's 2x2 cycle, (1,4)->(2,2) of X 32768x49152
    # f32, built on the described chips, whose coords are the real ones:
    # the two diagonal pieces go 1->3->2 and 2->0->1, a ppermute a hop,
    # and a tick's three permutes (hop 1, hop 2 of the chunk before, the
    # neighbours' round) are in flight together, start start start, done
    # done done; the temporaries are a few chunks, not the pieces
    import re
    from distributedarrays_tpu import layout as L
    from distributedarrays_tpu.parallel import reshard as R
    shape = (32768, 49152)
    plan = R.plan_reshard(
        shape, L.sharding_for(list(range(4)), (2, 2), shape),
        src_sharding=L.sharding_for(list(range(4)), (1, 4), shape),
        itemsize=4)
    assert [s[0] for s in plan.steps] == ["exchange"] and plan.nchunks == 16
    mesh = Mesh(np.asarray(topo.devices, dtype=object).reshape(2, 2),
                ("d0", "d1"))
    assert R._device_coords(mesh) == ((0, 0, 0), (1, 0, 0), (0, 1, 0),
                                      (1, 1, 0))
    fn = R._chain_jit(mesh, 2, plan.src_comp, plan.dst_comp, plan.steps,
                      None)
    assert R._chain_routes(mesh, plan.steps)[1:] == (2, 2, 1)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=NamedSharding(
        mesh, R._comp_spec(plan.src_comp, 2)))
    compiled = fn.lower(x).compile()
    txt = compiled.as_text()
    pairs = re.findall(
        r"collective-permute-start\(.*source_target_pairs=(\{[^ ]*\}),", txt)
    assert len(pairs) == 3 * plan.nchunks
    assert set(pairs) == {"{{1,3},{2,0}}", "{{3,2},{0,1}}",
                          "{{0,2},{1,0},{2,3},{3,1}}"}
    flying, most, full = 0, 0, 0
    for kind in re.findall(r"= .* collective-permute-(start|done)\(", txt):
        flying += 1 if kind == "start" else -1
        most = max(most, flying)
        full += flying == 3 and kind == "start"
    assert most == 3 and full >= plan.nchunks - 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_ring_reduce_scatter_4chips(ring_mesh, on_tpu):
    # the derived chunk depth is 1 at this size and the p-1 receive slots
    # then exceed scoped VMEM (the kernel would give way, and say so);
    # chunks=16 is the depth chip_smoke.py's four-chip phase runs
    txt = _ring_text(
        ring_mesh,
        lambda x: PC.ring_reduce_scatter(x, "d0", dim=0, chunks=16,
                                         interpret=False),
        (P("d0", None),), P("d0", None), ((4 * 4096, 4096), jnp.float32))
    assert "tpu_custom_call" in txt


def test_ring_allgather_matmul_4chips(ring_mesh, on_tpu):
    # 1792^2 bf16 is the largest square (in steps of 128) the scoped-VMEM
    # gate admits: x (4*448, 1792) row-sharded, w (1792, 1792) resident;
    # 2048^2 and up are refused by gemm_ring_eligible
    txt = _ring_text(
        ring_mesh,
        lambda x, w: PC.ring_allgather_matmul(x, w, "d0", interpret=False),
        (P("d0", None), P()), P("d0", None),
        ((1792, 1792), jnp.bfloat16), ((1792, 1792), jnp.bfloat16))
    assert "tpu_custom_call" in txt
    assert not PC.gemm_ring_eligible("ag", (512, 2048), (2048, 2048), 4, 2,
                                     2)


def test_refused_compiled_ring_is_counted(ring_mesh, on_tpu):
    # interpret=False asks for the compiled kernel; where eligibility
    # refuses it (at 4096^2 f32 the derived chunk depth is 1 and the p-1
    # receive slots exceed scoped VMEM) the kernel gives way to the lax
    # collective — counted under fallback.hits and warned, not silent
    from distributedarrays_tpu import telemetry as tm
    from distributedarrays_tpu.utils import debug as dbg
    key = ("pallas_collectives:ring_reduce_scatter:"
           "receive slots exceed scoped VMEM")
    dbg._warned.discard(key)
    before = tm.counter_value("fallback.hits", key=key)
    with pytest.warns(RuntimeWarning, match="compiled RDMA kernel demanded"):
        txt = _ring_text(
            ring_mesh,
            lambda x: PC.ring_reduce_scatter(x, "d0", dim=0,
                                             interpret=False),
            (P("d0", None),), P("d0", None),
            ((4 * 4096, 4096), jnp.float32))
    assert "tpu_custom_call" not in txt
    assert tm.counter_value("fallback.hits", key=key) == before + 1


# ---------------------------------------------------------------------------
# the whole train step of chip_smoke.py's train phase (the one long case)
# ---------------------------------------------------------------------------


def test_transformer_train_step_full_width(one_chip, on_tpu):
    cfg = T.Config(vocab=8192, dim=1024, heads=16, layers=8, ffn_mult=4,
                   max_seq=2048, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    on = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(on, shapes)
    tokens = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_chip)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    # compiled through the registry (telemetry/programs.py), which then
    # says what the program needs and where each instruction belongs
    step = programs.register("train.sgd_step", T.train_step, T.SCOPES)
    step.note(params, tokens, lr, cfg)
    compiled = programs.compiled(step)
    # 8 layers x (flash forward + the one-sweep backward)
    assert compiled.as_text().count("tpu_custom_call") >= 16
    mem = programs.memory(step)
    assert mem["temp"] + mem["argument"] < 14 * 2**30
    kernels, fusions = _placed(step, compiled.as_text())
    assert set(kernels.values()) == {("block/attn", "forward"),
                                     ("block/attn", "backward")}
    # most fusions lie under a scope of the model's; the SGD update has
    # none of its own, and XLA gives some fusions no op_name at all
    assert sum(v[0] is not None for v in fusions.values()) \
        > 0.6 * len(fusions)


def test_mla_moe_train_step_at_the_benchmark_size(one_chip, monkeypatch):
    # the step glm47f_train_s8k times, at its size: published widths, the
    # dense layer, four expert layers with 8 of 64 experts and the MTP
    # block, 19360 vocabulary rows, one row of 8194 ids, AdamW
    import optax
    from distributedarrays_tpu.models import mla_moe as M
    monkeypatch.setattr(PA, "_on_tpu", lambda: True)
    cfg = M.Config(vocab=19360, dim=2048, heads=20,
                   q_rank=768, kv_rank=512, nope=192, rope=64, v_dim=256,
                   ffn=10240, moe_ffn=1536, n_experts=64, held=(0, 8),
                   top_k=4, route_scale=1.8,
                   layers=M.published_layers(5), mtp=47)
    step, init = M.make_optax_train_step(
        cfg, optax.adamw(1e-3, weight_decay=0.1))
    on = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 706_518_848
    params = jax.tree_util.tree_map(on, shapes)
    state = jax.tree_util.tree_map(on, jax.eval_shape(init, shapes))
    tokens = jax.ShapeDtypeStruct((1, 8194), jnp.int32, sharding=one_chip)
    step.note(params, state, tokens)
    compiled = programs.compiled(step)
    mem = programs.memory(step)
    print(f"mla_moe step for v5e:2x2: arguments "
          f"{mem['argument'] / 1e9:.2f} GB, scratch "
          f"{mem['temp'] / 1e9:.2f} GB, in all {mem['total'] / 1e9:.2f} GB; "
          f"{mem}")
    assert mem["total"] == (mem["argument"] + mem["output"] - mem["alias"]
                            + mem["temp"] + mem["generated_code"])
    txt = compiled.as_text()
    kernels, fusions = _placed(step, txt)
    # the flash kernels lie under block/mla (the MTP block's among them);
    # libtpu's grouped products carry its own op_name and no scope
    flash = {k: v for k, v in kernels.items() if k.startswith("flash")}
    assert len(flash) == 12
    assert set(flash.values()) == {("block/mla", "forward"),
                                   ("block/mla", "backward")}
    assert {v for k, v in kernels.items() if not k.startswith("flash")} \
        <= {(None, "forward")}
    # a third of the fusions carry no op_name at all (multi-output ones
    # whose root is a tuple among them: 1.7% of the step's device time)
    assert sum(v[0] is not None for v in fusions.values()) \
        > 0.6 * len(fusions)
    # the FFN half is computed again in the backward, attention is not
    again = {v[0] for v in fusions.values() if v[1] == "recompute"}
    assert "block/moe/experts" in again and "block/mla" not in again
    count = lambda name: len(re.findall(rf"%{name}[.\d]* = ", txt))
    # six attention layers: a forward and a backward of one sweep each
    # (the fused kernel names the VMEM its resident dQ needs, PR 36)
    assert count("flash_fwd") == count("flash_bwd_dkv") == 6
    assert count("flash_bwd_dq") == 0
    # five expert blocks: two grouped products forward, four backward, and
    # none computed again (the recomputed FFN half keeps their results)
    print("grouped products:", count("ragged-dot-none"))
    assert count("ragged-dot-none") == 30
    assert mem["temp"] + mem["argument"] < 14 * 2**30


def test_mamba2_hybrid_train_step_at_the_benchmark_size(one_chip, monkeypatch):
    # the step granite4h_train_s8k times, at its size: published widths,
    # layers 0..9 (nine Mamba-2, attention at 5), 12544 vocabulary rows,
    # one row of 8193 ids, AdamW
    import optax
    from distributedarrays_tpu.models import mamba2_hybrid as M
    from distributedarrays_tpu.ops import pallas_ssd as SSD
    monkeypatch.setattr(PA, "_on_tpu", lambda: True)
    monkeypatch.setattr(SSD, "_on_tpu", lambda: True)
    layers = tuple((i, "attention" if i == 5 else "mamba") for i in range(10))
    cfg = M.Config(vocab=12544, dim=2048, ffn=8192, heads=32, kv_heads=8,
                   head_dim=64, ssm_heads=64, ssm_head_dim=64, d_state=128,
                   n_groups=1, chunk=256, layers=layers,
                   attention_mult=0.015625)
    step, init = M.make_optax_train_step(
        cfg, optax.adamw(1e-3, weight_decay=0.1))
    on = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 772_160_448
    params = jax.tree_util.tree_map(on, shapes)
    state = jax.tree_util.tree_map(on, jax.eval_shape(init, shapes))
    tokens = jax.ShapeDtypeStruct((1, 8193), jnp.int32, sharding=one_chip)
    step.note(params, state, tokens)
    compiled = programs.compiled(step)
    mem = programs.memory(step)
    print(f"mamba2_hybrid step for v5e:2x2: arguments "
          f"{mem['argument'] / 1e9:.2f} GB, scratch "
          f"{mem['temp'] / 1e9:.2f} GB, in all {mem['total'] / 1e9:.2f} GB; "
          f"{mem}")
    # under the 14.5 GB the cell may need of the chip's 16
    assert mem["total"] < 14.5e9
    txt = compiled.as_text()
    kernels, fusions = _placed(step, txt)
    count = lambda name: len(re.findall(rf"%{name}[.\d]* = ", txt))
    # nine Mamba-2 layers: the forward kernel twice (recomputed), the
    # backward once; one attention layer likewise
    assert count("ssd_fwd") == 18 and count("ssd_bwd") == 9
    assert count("flash_fwd") == 2 and count("flash_bwd_dkv") == 1
    placed = {k.split(".")[0]: set() for k in kernels}
    for k, v in kernels.items():
        placed[k.split(".")[0]].add(v)
    assert placed["ssd_fwd"] == {("block/mamba", "forward"),
                                 ("block/mamba", "recompute")}
    assert placed["ssd_bwd"] == {("block/mamba", "backward")}
    assert placed["flash_fwd"] == {("block/attn", "forward"),
                                   ("block/attn", "recompute")}
    assert sum(v[0] is not None for v in fusions.values()) \
        > 0.6 * len(fusions)


def test_olmo_hybrid_train_step_at_the_benchmark_size(one_chip, monkeypatch):
    # the step olmohyb_train_s8k times, at its size: published widths,
    # layers 0..3 (three gated delta-rule layers, full attention at 3),
    # 12544 vocabulary rows, one row of 8193 ids, AdamW
    import optax
    from distributedarrays_tpu.models import olmo_hybrid as M
    from distributedarrays_tpu.ops import pallas_gated_delta as GD
    from distributedarrays_tpu import telemetry as tm
    monkeypatch.setattr(PA, "_on_tpu", lambda: True)
    monkeypatch.setattr(GD, "_on_tpu", lambda: True)
    layers = tuple((i, "full_attention" if i == 3 else "linear_attention")
                   for i in range(4))
    cfg = M.Config(vocab=12544, dim=3840, ffn=11008, heads=30, head_dim=128,
                   lin_heads=30, key_dim=96, value_dim=192, layers=layers)
    step, init = M.make_optax_train_step(
        cfg, optax.adamw(1e-3, weight_decay=0.1))
    on = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 928_862_196
    params = jax.tree_util.tree_map(on, shapes)
    state = jax.tree_util.tree_map(on, jax.eval_shape(init, shapes))
    tokens = jax.ShapeDtypeStruct((1, 8193), jnp.int32, sharding=one_chip)
    step.note(params, state, tokens)
    compiled = programs.compiled(step)
    mem = programs.memory(step)
    print(f"olmo_hybrid step for v5e:2x2: arguments "
          f"{mem['argument'] / 1e9:.2f} GB, scratch "
          f"{mem['temp'] / 1e9:.2f} GB, in all {mem['total'] / 1e9:.2f} GB; "
          f"{mem}")
    # under the 14.7 GB the cell may need of the chip's 16: 14.416 GB when
    # one layer's solves lived from its replayed forward to its backward,
    # 14.541 since every layer's live from the forward (2 x 62,914,560 more)
    assert mem["total"] < 14.7e9
    txt = compiled.as_text()
    kernels, fusions = _placed(step, txt)
    count = lambda name: len(re.findall(rf"%{name}[.\d]* = ", txt))
    # three linear-attention layers: the solve once a layer (the policy
    # keeps its result), the recurrence twice (recomputed), the backward
    # once; both forward kernels are named gdn_fwd, the solve's result
    # alone is the saved X, f32[30,64,64,128]
    solves = set(re.findall(
        r"%(gdn_fwd[.\d]*) = f32\[30,64,64,128\]\S* custom-call\(", txt))
    assert count("gdn_fwd") == 9 and len(solves) == 3
    assert count("gdn_bwd") == 3
    assert count("flash_fwd") == 2 and count("flash_bwd_dkv") == 1
    placed = {}
    for k, v in kernels.items():
        placed.setdefault("gdn_solve" if k in solves else k.split(".")[0],
                          set()).add(v)
    assert placed["gdn_solve"] == {("block/linear", "forward")}
    assert placed["gdn_fwd"] == {("block/linear", "forward"),
                                 ("block/linear", "recompute")}
    assert placed["gdn_bwd"] == {("block/linear", "backward")}
    assert placed["flash_fwd"] == {("block/attn", "forward"),
                                   ("block/attn", "recompute")}
    # heads of 128 go through head-major copies (PERF.md section 7)
    assert tm.gauge_value("pallas.flash_attention.plan", kernel="flash_fwd",
                          s=8192, d=128, causal=True, what="lane_heads") == 0
    plan = lambda what: tm.gauge_value("pallas.gated_delta.plan", L=8192,
                                       H=30, dk=96, dv=192, what=what)
    # the backward is fed the states and each chunk's solve: a layer's
    # states live from its replayed forward to its backward, every layer's
    # solves from the forward to its backward
    assert plan("checkpoint_bytes") == 283_115_520
    assert plan("solve_bytes") == 62_914_560
    assert plan("vmem_bytes") < 16 * 2**20
    assert sum(v[0] is not None for v in fusions.values()) \
        > 0.6 * len(fusions)
