"""`chip_smoke.py` on the CPU: its phases at the `tiny` size, with the
Pallas kernels interpreted, so the script cannot rot between chip runs; its
refusal to run without a TPU; and the checks that decide its verdict."""
import importlib.util
import math
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phase_on_tiny(smoke):
    recs, pairs = smoke.one_chip_phase("tiny", {}, epochs=2, timed_steps=1,
                                       log=lambda _: None)
    assert [r["name"] for r in recs] == [
        "coo/vanilla", "coo/pipegcn-gf", "coo-rcm/pipegcn-gf",
        "blocksparse/pipegcn-gf", "fused/pipegcn-gf", "spmd-coo/pipegcn-gf",
        "spmd-fused/pipegcn-gf"]
    assert smoke.check(recs, pairs, on_tpu=False, log=lambda _: None) == []
    for r in recs:
        assert len(r["losses"]) == 2 and r["skipped_steps"] == 0, r
        assert r["compile_s"] > 0 and r["step_ms"] > 0
        assert not r["kernels"]       # interpreted on the CPU
    by = {r["name"]: r for r in recs}
    for got, ref in pairs:
        assert smoke.rel_gap(by[got]["losses"], by[ref]["losses"]) < 1e-5


MESH_SCRIPT = textwrap.dedent("""
    import importlib.util, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    recs, pairs = smoke.mesh_phase(4, "tiny", {}, epochs=2, timed_steps=1)
    assert len(recs) == 8 and len(pairs) == 4, (recs, pairs)
    for r in recs:
        if r["backend"] == "spmd/4":
            assert set(r["split_over"].values()) == {4}, r
    fails = smoke.check(recs, pairs, on_tpu=False, n_chips=4)
    assert not fails, fails
    print("MESH-PHASE-OK")
""")


def test_mesh_phase_on_four_host_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH-PHASE-OK" in proc.stdout


def test_main_refuses_a_platform_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""                  # no result line, no work done
    assert "no TPU" in err


def _rec(name, agg="coo", losses=(1.0, 0.5), **kw):
    return {"name": name, "agg": agg, "losses": list(losses),
            "skipped_steps": 0, "kernels": agg != "coo", **kw}


@pytest.mark.parametrize("rec,want", [
    (_rec("a", losses=(1.0, math.nan)), "non-finite"),
    (dict(_rec("a"), skipped_steps=1), "skipped"),
    (_rec("a", agg="fused", kernels=False), "tpu_custom_call"),
    (_rec("a", split_over={"topology": 4, "buffers": 1}), "not split"),
    (dict(name="a", agg="coo", error="ValueError: x"), "ValueError"),
])
def test_check_fails_each_broken_run(smoke, rec, want):
    fails = smoke.check([rec], [], on_tpu=True, n_chips=4,
                        log=lambda _: None)
    assert len(fails) == 1 and want in fails[0], fails


def test_check_fails_engines_that_disagree(smoke):
    ref = _rec("ref", losses=(1.0, 0.5))
    near = _rec("near", losses=(1.0, 0.5 * (1 + smoke.RTOL / 2)))
    far = _rec("far", losses=(1.0, 0.5 * (1 + 2 * smoke.RTOL)))
    recs = [ref, near, far]
    assert smoke.check(recs, [("near", "ref")], on_tpu=False,
                       log=lambda _: None) == []
    fails = smoke.check(recs, [("far", "ref")], on_tpu=False,
                        log=lambda _: None)
    assert len(fails) == 1 and "relative loss gap" in fails[0]
