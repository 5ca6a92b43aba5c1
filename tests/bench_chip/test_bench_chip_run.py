"""CPU tests of a whole benchmark run at a tiny size: the harness refuses a
host without a chip; past that look, a sound run comes out correct, and a
run with the timed path broken underneath, or with the bfloat16 control in
the program's place, comes out not correct under the cells' own limits."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, CHIP)

import run  # noqa: E402
from chipbench import compare, faults, graph, reference, spec  # noqa: E402

# The cell whose limits the tiny runs are held to.
ONE_CHIP = "reddit-sage.pipegcn-gf.1chip"
CPU_PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(backend: str = "sim") -> spec.Cell:
    """The one-chip reddit cell at a size a test run holds: its limits,
    variant and metrics, on a 512-node graph and a 4-layer SAGE model
    24 -> 16 -> 5. With `backend="mesh"` it is the same job on a mesh of
    four devices, one partition each, held also to the placement check."""
    cell = spec.load_cell(ONE_CHIP)
    config = dict(cell.config, feat_dim=24, hidden=16, num_classes=5,
                  avg_degree=8)
    traffic = dict(cell.traffic, num_nodes=512, partitions=4,
                   backend=backend, parts_per_device=1)
    if backend == "sim":
        return dataclasses.replace(cell, config=config, traffic=traffic)
    return dataclasses.replace(cell, chips=4, config=config, traffic=traffic,
                               limits=dict(cell.limits, placement=0))


def tiny_run(cell, fault=None, seed=2**31 + 11):
    wrap, patches = faults.plant(fault) if fault else (None, ())
    lines = []
    result = run.run_cell(cell, seed=seed, seconds=0.5, trace=False,
                          t_start=time.perf_counter(), out=lines.append,
                          peaks=CPU_PEAKS, wrap_step=wrap, patches=patches)
    return result, lines


def test_run_refuses_a_host_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         ONE_CHIP, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert "no TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.startswith("{")]


def test_peak_memory_adds_the_reserved_temporaries(monkeypatch):
    """On the TPU a program's temporaries are reserved apart from the
    arrays: a chip's peak is the sum of the two peaks, and the run reports
    the fullest chip of those it uses."""
    import jax

    class Device:
        def __init__(self, id, stats):
            self.id, self.stats = id, stats

        def memory_stats(self):
            return self.stats

    devs = [Device(0, {"peak_bytes_in_use": 100,
                       "peak_bytes_reserved": 900}),
            Device(1, {"peak_bytes_in_use": 400, "bytes_in_use": 5}),
            Device(2, {"peak_bytes_in_use": 10 ** 6})]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    assert run.peak_bytes(2) == (1000, {0: {"in_use": 100, "reserved": 900},
                                        1: {"in_use": 400, "reserved": 0}})
    assert run.peak_bytes(3)[0] == 10 ** 6
    monkeypatch.setattr(jax, "local_devices", lambda: [Device(0, None)])
    assert run.peak_bytes(1) == (None, {})


def test_sound_run_is_correct_and_reports_every_metric():
    cell = tiny_cell()
    result, lines = tiny_run(cell)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert any(ln.startswith("trainer: preempted") for ln in lines)
    assert "compiles inside the window 0" in "\n".join(lines)


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_timed_path_is_not_correct(fault):
    result, _ = tiny_run(tiny_cell(), fault=fault)
    assert not result["correct"], (fault, result["checks"])


def test_bf16_control_is_not_correct():
    """The reference in bfloat16, in the program's place, against the
    float32 reference."""
    cell = tiny_cell()
    seed = 2**31 + 12
    g = graph.from_cell(cell.config, cell.traffic, seed)
    part = np.arange(g.num_nodes) % cell.traffic["partitions"]
    args = (g, cell.config, cell.traffic["variant"], part,
            cell.traffic["partitions"])
    ref = reference.run(reference.problem(*args), seed, 3)
    ctl = reference.run(reference.problem(*args, dtype=jnp.bfloat16), seed, 3)
    read = compare.readings(ctl.losses, ctl.grads_1, ctl.params_0,
                            ctl.params_out, ctl.buffers, ref)
    limits = {k: v for k, v in cell.limits.items() if k in compare.NUMBERS}
    correct, checks = compare.verdict(read, limits)
    assert not correct, checks


_MESH_SCRIPT = r"""
import json, sys, time
sys.path.insert(0, {chip!r}); sys.path.insert(0, {tests!r})
from test_bench_chip_run import tiny_cell, tiny_run
out = {{}}
for fault in (None, "exchange_left_out"):
    r, _ = tiny_run(tiny_cell(backend="mesh"), fault=fault)
    out[str(fault)] = {{"correct": r["correct"], "checks": r["checks"]}}
print(json.dumps(out))
"""


def test_mesh_run_on_four_host_devices():
    """The mesh path (the training CLI's --spmd, one partition per chip) on
    four virtual CPU devices: sound, it is correct with every input split
    over the mesh; with the exchange between chips left out, it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = _MESH_SCRIPT.format(chip=CHIP, tests=HERE)
    proc = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    sound = out["None"]
    assert sound["correct"], sound
    assert sound["checks"]["placement"]["value"] == 0
    assert not out["exchange_left_out"]["correct"], out
