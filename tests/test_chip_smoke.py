"""chip_smoke.py, rehearsed in-process at the tiny size on the CPU mesh.

The driver runs ``python chip_smoke.py`` on the machine with the chip; here
the same phases run on virtual CPU devices with the kernels in interpret
mode, and the contract of the script's last line is pinned: exact shape,
``"ok": false`` with a non-zero exit without a TPU, on a phase that raises,
and on a ``warn_once`` fired inside a phase.
"""

import importlib.util
import json
from pathlib import Path

import pytest

import jax

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture()
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke_under_test",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the suite never turns the persistent compile cache on
    from distributedarrays_tpu.utils import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "off-in-tests")
    return mod


def _run(smoke, capsys, *argv):
    rc = smoke.main(list(argv))
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return rc, lines


def _cpu_device():
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def test_one_chip_phases_tiny_on_cpu(smoke, capsys):
    rc, lines = _run(smoke, capsys, "--platform", "cpu", "--tiny")
    phases = [ln for ln in lines if "phase" in ln]
    assert [p["phase"] for p in phases] == ["arrays", "kernels", "train",
                                            "serve"]
    for p in phases:
        assert p["ok"], p.get("error")
        assert p["checks"] and all(c["ok"] for c in p["checks"])
        assert {"seconds", "compile_seconds", "max_err"} <= set(p)
    assert "native_tier" in lines[0] and "compile_cache" in lines[0]
    # the last line is exactly the contract's, and names the CPU truthfully
    assert lines[-1] == {"ok": True, "device": _cpu_device()}
    assert list(lines[-1]) == ["ok", "device"]
    assert list(lines[-1]["device"]) == ["platform", "kind", "count"]
    assert rc == 0


def test_chips_4_runs_the_multichip_phase_and_no_other(smoke, capsys):
    rc, lines = _run(smoke, capsys, "--platform", "cpu", "--tiny",
                     "--chips", "4")
    phases = [ln for ln in lines if "phase" in ln]
    assert [p["phase"] for p in phases] == ["multichip"]
    assert phases[0]["ok"], phases[0].get("error")
    whats = [c["what"] for c in phases[0]["checks"]]
    assert any("4 distinct devices" in w for w in whats)
    assert any("bit-equal to device_put" in w for w in whats)
    assert any("bit-equal to the lax collective" in w for w in whats)
    assert lines[-1]["ok"] is True and rc == 0


def test_no_tpu_means_ok_false_and_nonzero_exit(smoke, capsys):
    # as the driver runs it: no arguments, and JAX here finds only the CPU
    rc, lines = _run(smoke, capsys)
    assert rc != 0
    assert not any("phase" in ln for ln in lines)      # fails at once
    assert lines[-1] == {"ok": False, "device": _cpu_device()}


def test_a_phase_that_raises_fails_the_run(smoke, capsys, monkeypatch):
    def boom(ctx):
        raise ValueError("made to raise")

    monkeypatch.setitem(smoke.PHASES, "arrays", boom)
    rc, lines = _run(smoke, capsys, "--platform", "cpu", "--tiny",
                     "--phases", "arrays")
    assert rc != 0
    assert lines[-2]["phase"] == "arrays" and not lines[-2]["ok"]
    assert "made to raise" in lines[-2]["error"]
    assert lines[-1] == {"ok": False, "device": _cpu_device()}


def test_a_fallback_warning_inside_a_phase_fails_the_run(smoke, capsys,
                                                         monkeypatch):
    from distributedarrays_tpu.utils import debug

    def degrades(ctx):
        debug.warn_once("smoke-test:degraded", "took a fallback path")
        ctx.require("reached the end", True)

    debug._warned.discard("smoke-test:degraded")
    monkeypatch.setitem(smoke.PHASES, "arrays", degrades)
    rc, lines = _run(smoke, capsys, "--platform", "cpu", "--tiny",
                     "--phases", "arrays")
    assert rc != 0 and not lines[-2]["ok"]
    assert "RuntimeWarning" in lines[-2]["error"]
    # already warned once: no warning now, but the counter still moves
    rc, lines = _run(smoke, capsys, "--platform", "cpu", "--tiny",
                     "--phases", "arrays")
    assert rc != 0 and "fallback counters moved" in lines[-2]["error"]
    assert lines[-1] == {"ok": False, "device": _cpu_device()}


def test_a_failed_check_fails_the_phase_but_not_the_rest_of_it(
        smoke, capsys, monkeypatch):
    def half(ctx):
        ctx.check("first", 1.0, 0.5)
        ctx.check("second", 0.0, 0.5)

    monkeypatch.setitem(smoke.PHASES, "arrays", half)
    rc, lines = _run(smoke, capsys, "--platform", "cpu", "--tiny",
                     "--phases", "arrays")
    assert rc != 0
    assert [c["ok"] for c in lines[-2]["checks"]] == [False, True]


def test_multichip_phase_is_not_a_one_chip_phase(smoke, capsys):
    rc, lines = _run(smoke, capsys, "--platform", "cpu", "--tiny",
                     "--phases", "multichip")
    assert rc != 0 and lines[-1]["ok"] is False


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    import importlib
    cc = importlib.import_module(
        "distributedarrays_tpu.utils.compile_cache")
    set_to = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_to.append((k, v)))
    # set from outside: left alone, nothing set in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.enable_compile_cache() == str(tmp_path) and set_to == []
    # unset: the fixed path under the checkout, no temp name, pid or time
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cc.enable_compile_cache() == str(REPO / ".jax_cache")
    assert set_to == [("jax_compilation_cache_dir",
                       str(REPO / ".jax_cache"))]
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
