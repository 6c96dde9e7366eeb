"""The tracked AUTOTUNE_SEED.json must be loaded under the live cache.

The seed ships hardware-measured winners with device-fenced keys
(VERDICT round-4 weak 3: without it, the GEMM/flash dispatch is inert on
a fresh checkout until the user's first tune).  Pin that the seed file
exists, parses, carries only device-fenced keys, and is visible through
``autotune.get`` after a registry reset — with the live cache taking
precedence on collision.
"""

import json
import os

_SEED_REFRESH_TOOL = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "seed_refresh.py")

from distributedarrays_tpu.utils import autotune


def _reload_fresh(monkeypatch):
    autotune.clear()
    monkeypatch.setattr(autotune, "_LOADED_ENV", False)


def test_seed_file_parses_and_is_device_fenced():
    with open(autotune.seed_path()) as f:
        data = json.load(f)
    assert isinstance(data, dict) and data
    for kernel, entries in data.items():
        for key in entries:
            # device_key_for appends "<platform>|<device_kind>"; the
            # shipped seed may hold HARDWARE winners only — a cpu/
            # interpret-mode winner in the tracked file would be exactly
            # the foreign-platform leakage the fence exists to stop
            assert len(key.split("|")) >= 2, (kernel, key)
            platform = key.split("|")[-2]
            assert platform in ("tpu", "gpu"), (kernel, key)


def test_seed_refresh_allowlist_matches_this_fence():
    # tools/seed_refresh.py promotes live-cache entries into the seed;
    # its hardware allowlist and this test's fence must be the same set
    # or the tool can write a seed this suite rejects
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "seed_refresh", _SEED_REFRESH_TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert set(mod._HW_PLATFORMS) == {"tpu", "gpu"}


def _load_tool():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "seed_refresh", _SEED_REFRESH_TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_seed_refresh_gemm_gate_matches_kernel_owner():
    # the tool's _GEMM_KERNELS gate (which kernels go through the
    # dispatch-validity filter) must agree with the predicate's own
    # kernel set, or a new GEMM kernel's winners would promote
    # unvalidated (or a non-GEMM kernel would be import-gated for
    # nothing)
    from distributedarrays_tpu.ops.pallas_gemm import entry_valid_for_seed
    mod = _load_tool()
    probe = "256|256|256|float32|float32|tpu|x"
    for k in mod._GEMM_KERNELS:
        assert entry_valid_for_seed(k, probe, [128, 128, 128]) is not None, k
    assert entry_valid_for_seed("flash_attention", probe, [128, 128]) is None
    assert mod._dispatch_valid("flash_attention", probe, [128, 128]) is None


def test_seed_refresh_filters_dispatch_invalid_gemm_winners(tmp_path):
    # a winner that _resolve_block would reject at dispatch (over-VMEM
    # tiling, broken alignment) must not ship into the tracked seed
    # (ADVICE round-5: pre-VMEM-fix winners were dead entries)
    mod = _load_tool()
    mod.CACHE = tmp_path / "AUTOTUNE_CACHE.json"
    mod.SEED = tmp_path / "AUTOTUNE_SEED.json"
    # an already-shipped dead entry (committed pre-predicate) must be
    # PRUNED, not just blocked at promotion — otherwise --dry-run keeps
    # reporting the seed current while dispatch rejects it forever
    mod.SEED.write_text(json.dumps({
        "pallas_matmul_int8": {
            "4096|4096|4096|int8|tpu|TPU v5 lite": [8, 128, 128]},
    }))
    mod.CACHE.write_text(json.dumps({
        "pallas_matmul": {
            # valid: fits VMEM, aligned, divides
            "4096|4096|4096|float32|float32|tpu|TPU v5 lite":
                [512, 512, 512],
            # over the scoped-VMEM budget at bf16 2048^2 blocks
            "4096|4096|4096|bfloat16|bfloat16|tpu|TPU v5 lite":
                [2048, 2048, 1024],
        },
        "pallas_matmul_int8": {
            # m block % 32 != 0 — Mosaic int8 alignment violation
            "4096|4096|4096|int8|tpu|TPU v5 lite": [8, 128, 128],
        },
    }))
    assert mod.main() == 0
    seed = json.loads(mod.SEED.read_text())
    assert seed == {"pallas_matmul": {
        "4096|4096|4096|float32|float32|tpu|TPU v5 lite": [512, 512, 512]}}


def test_seed_entries_visible_after_registry_reset(monkeypatch):
    with open(autotune.seed_path()) as f:
        data = json.load(f)
    kernel = next(iter(data))
    key = next(iter(data[kernel]))
    _reload_fresh(monkeypatch)
    got = autotune.get(kernel, key)
    assert got is not None
    autotune.clear()
    monkeypatch.setattr(autotune, "_LOADED_ENV", False)


def test_live_cache_overrides_seed(monkeypatch, tmp_path):
    with open(autotune.seed_path()) as f:
        data = json.load(f)
    kernel = next(iter(data))
    key = next(iter(data[kernel]))
    live = tmp_path / "live.json"
    live.write_text(json.dumps({kernel: {key: [7, 7]}}))
    monkeypatch.setenv("DAT_AUTOTUNE_CACHE", str(live))
    _reload_fresh(monkeypatch)
    assert autotune.get(kernel, key) == [7, 7]
    autotune.clear()
    monkeypatch.setattr(autotune, "_LOADED_ENV", False)


def test_seed_refresh_rc_contract(tmp_path):
    # the tool's exit codes are a CI contract: 0 = current/merged,
    # 1 = --dry-run found stale entries, 2 = unreadable input (must be
    # a diagnostic, not a traceback)
    import json as _json
    import subprocess
    import sys as _sys
    tool = _SEED_REFRESH_TOOL

    def run_in(workdir, *args):
        # run a COPY of the tool from a sandbox repo root so the real
        # AUTOTUNE_SEED.json is never touched
        import shutil
        tooldir = workdir / "tools"
        tooldir.mkdir(exist_ok=True)
        shutil.copyfile(tool, tooldir / "seed_refresh.py")
        return subprocess.run(
            [_sys.executable, str(tooldir / "seed_refresh.py"), *args],
            capture_output=True, text=True, cwd=workdir)

    # no cache at all -> rc 0
    r = run_in(tmp_path)
    assert r.returncode == 0 and "nothing to merge" in r.stdout

    # corrupt cache -> rc 2 with a clean diagnostic
    (tmp_path / "AUTOTUNE_CACHE.json").write_text("{truncated")
    r = run_in(tmp_path)
    assert r.returncode == 2 and "unreadable" in r.stdout
    assert "Traceback" not in r.stderr

    # stale seed + --dry-run -> rc 1 and no write
    (tmp_path / "AUTOTUNE_CACHE.json").write_text(_json.dumps(
        {"k": {"1|2|tpu|TPU v5 lite": [8, 8]}}))
    r = run_in(tmp_path, "--dry-run")
    assert r.returncode == 1 and not (tmp_path / "AUTOTUNE_SEED.json").exists()

    # corrupt SEED next to a valid cache -> the other rc-2 branch
    (tmp_path / "AUTOTUNE_SEED.json").write_text("{truncated")
    r = run_in(tmp_path)
    assert r.returncode == 2 and "unreadable" in r.stdout
    assert "Traceback" not in r.stderr
    (tmp_path / "AUTOTUNE_SEED.json").unlink()

    # real merge -> rc 0, hardware entry written, cpu entry excluded
    (tmp_path / "AUTOTUNE_CACHE.json").write_text(_json.dumps(
        {"k": {"1|2|tpu|TPU v5 lite": [8, 8],
               "1|2|cpu|cpu": [4, 4]}}))
    r = run_in(tmp_path)
    assert r.returncode == 0
    seed = _json.loads((tmp_path / "AUTOTUNE_SEED.json").read_text())
    assert seed == {"k": {"1|2|tpu|TPU v5 lite": [8, 8]}}
