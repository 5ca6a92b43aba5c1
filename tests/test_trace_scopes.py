"""The trace vocabulary of `repro.core.trace_utils`: every op of the train
step and the eval forward carries its part of the step (`SCOPES`) in its
HLO metadata, and a profiled `train_pipegcn` records the trainer's host
spans (`HOST_SPANS`) in every epoch, nested and in order."""
from __future__ import annotations

import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import pytest

from repro.core import ModelConfig, PipeConfig, PipeGCN, trainer
from repro.core.health import HealthConfig
from repro.core.trace_utils import HOST_SPANS, SCOPES
from repro.data import GraphDataPipeline
from repro.optim import adam

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

from chipbench import hlo, spans  # noqa: E402
from chipbench import trace as chiptrace  # noqa: E402

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FRAME = re.compile(r"stack_frame_id=(\d+)")
# Emitted by these functions or files, an op belongs to this scope.
EXCHANGE_FNS = ("_gather_send", "_gather_send_tail", "_scatter_recv")
UPDATE_FILES = ("repro/core/health.py", "repro/optim/optimizers.py")
SPARSE = {"gather", "scatter", "custom-call"}
BODY = "xla.sdy.manual_computation_body"     # shard_map's, when lowered
CONSTANT = {"parameter", "constant", "broadcast", "iota"}


def test_vocabulary_matches_the_harness():
    """The benchmark keeps its own copy of the scope names, so that it
    reads programs that predate them; the two must not drift."""
    assert SCOPES == spans.SCOPES
    assert all(s.startswith("trainer/") for s in HOST_SPANS)
    assert HOST_SPANS[0] == "trainer/epoch"


def _frames(text: str) -> dict:
    """stack frame id -> (function names, source files) on its chain."""
    t = hlo._tables(text)
    names = {k: v.strip('"') for k, v in t.get("FunctionNames", {}).items()}
    files = {k: v.strip('"') for k, v in t.get("FileNames", {}).items()}
    loc = {}
    for k, v in t.get("FileLocations", {}).items():
        fn = re.search(r"function_name_id=(\d+)", v)
        fl = re.search(r"file_name_id=(\d+)", v)
        loc[k] = (names.get(int(fn.group(1)), "") if fn else "",
                  files.get(int(fl.group(1)), "") if fl else "")
    parent, at = {}, {}
    for k, v in t.get("StackFrames", {}).items():
        lo = re.search(r"file_location_id=(\d+)", v)
        pa = re.search(r"parent_frame_id=(\d+)", v)
        at[k] = loc.get(int(lo.group(1)), ("", "")) if lo else ("", "")
        parent[k] = int(pa.group(1)) if pa else 0
    out = {}
    for k in at:
        fns, fls, f, seen = set(), set(), k, set()
        while f in at and f not in seen:
            seen.add(f)
            fns.add(at[f][0])
            fls.add(at[f][1])
            f = parent[f]
        out[k] = (fns, fls)
    return out


def _ops(text: str):
    """(opcode, op_name, functions, files) of every instruction of a
    lowered module's entry computation and shard_map body. Helpers that
    JAX lowers apart (`jnp.where`, `jax.nn.relu`, ...) and reducers are
    left out: their ops carry a path relative to the helper, and the call
    into one carries the caller's path."""
    frames = _frames(text)
    out, keep = [], False
    for line in text.splitlines():
        if not line.startswith(" "):
            keep = line.startswith(("ENTRY", BODY, "%" + BODY)) or (
                keep and not line.startswith("}"))
            continue
        m, n = _INSTR.match(line), _OP_NAME.search(line)
        if not keep or not m or m.group(2) in ("parameter", "constant"):
            continue
        f = _FRAME.search(line)
        fns, fls = frames.get(int(f.group(1)), (set(), set())) if f else (
            set(), set())
        out.append((m.group(2), n.group(1) if n else "", fns, fls))
    return out


def _tree(ops) -> set:
    """(direction, layer, scope) of each op: the scope tree."""
    out = set()
    for _, name, _, _ in ops:
        parts = name.split("/")
        d = next((p for p in parts if p in ("fwd", "bwd")), None)
        layer = next((p for p in parts if re.fullmatch(r"L\d+", p)), None)
        out.add((d, layer, spans.scope_of(name)))
    return out


def _check_rules(ops):
    """The rules every step keeps: the exchange's functions and the
    all-to-all in `exchange`, the optimizer and the health guard in
    `update`, the engines' gathers, scatters and kernels in `aggregate`."""
    for opcode, name, fns, fls in ops:
        scope, where = spans.scope_of(name), (opcode, name)
        if opcode == "all-to-all" or fns & set(EXCHANGE_FNS):
            assert scope == "exchange", where
        if any(f.endswith(UPDATE_FILES) for f in fls) or (
                "PipeGCN._reduce_grad" in fns):
            assert scope == "update", where
        if opcode in SPARSE and any(f.endswith(hlo.AGGREGATION_FILES)
                                    for f in fls):
            assert scope == "aggregate", where


def _check_step_scopes(ops):
    """`_check_rules`, and all but 1 % of the ops JAX emitted under one of
    the five scopes."""
    ops = [o for o in ops if o[1]]
    _check_rules(ops)
    scoped = sum(bool(spans.scope_of(name)) for _, name, _, _ in ops)
    assert scoped >= 0.99 * len(ops), (scoped, len(ops))


def _scoped_share(text: str) -> float:
    """The share of a compiled program's instructions with a scope, of
    those that compute (not a parameter, nor a constant or a broadcast or
    iota of one, which attribute nothing)."""
    scope = spans.scopes(text)
    run = [n for n, f in hlo.facts(text).items()
           if not f[0] - {"fusion"} <= CONSTANT]
    return sum(bool(scope[n]) for n in run) / len(run)


@pytest.fixture(scope="module")
def grid():
    """Lattice pipeline whose rcm layout has a boundary tail, so that the
    split-phase schedule can run (as in test_collective_count)."""
    return GraphDataPipeline.build("grid-tiny", 4, kind="sage",
                                   agg="blocksparse", layout="rcm")


def _model(pipeline, overlap):
    mc = ModelConfig(kind="sage", feat_dim=pipeline.train_data.x.shape[-1],
                     hidden=8, num_layers=3, num_classes=4, dropout=0.0,
                     agg="coo", layout="rcm", matmul_order="auto")
    pc = dataclasses.replace(PipeConfig.named("pipegcn-gf"), overlap=overlap)
    return PipeGCN(mc, pc, split=pipeline.split_spec())


def _train_args(model, pipeline):
    opt = adam(0.01)
    params = model.init_params(jax.random.PRNGKey(0))
    return opt, (pipeline.topo, params, opt.init(params),
                 model.init_buffers(pipeline.topo), pipeline.train_data,
                 jax.random.PRNGKey(1))


def _step_text(model, pipeline, health, compiled=False):
    opt, args = _train_args(model, pipeline)
    lowered = trainer.make_jitted_train_step(model, opt,
                                             health).lower(*args)
    if compiled:
        return lowered.compile().as_text()
    return lowered.as_text(dialect="hlo", debug_info=True)


@pytest.mark.parametrize("overlap", ["none", "split-phase"])
@pytest.mark.parametrize("guarded", [True, False])
def test_train_step_ops_carry_their_scope(grid, overlap, guarded):
    model = _model(grid, overlap)
    assert (model._split_active() is not None) == (overlap != "none")
    health = HealthConfig() if guarded else None
    ops = _ops(_step_text(model, grid, health))
    _check_step_scopes(ops)
    names = " ".join(name for _, name, _, _ in ops)
    assert ("/update/" in names) and ("/loss/" in names)
    assert guarded == any("health_check" in fns for _, _, fns, _ in ops)
    # the compiled program, as the harness reads it
    assert _scoped_share(
        _step_text(model, grid, health, compiled=True)) >= 0.99


@pytest.mark.parametrize("guarded", [True, False])
def test_split_and_unsplit_steps_emit_one_scope_tree(grid, guarded):
    """The split-phase schedule issues layer l+1's exchange inside layer
    l's phases; its ops still carry `fwd/L{l+1}/exchange`, so both bodies
    emit the same tree: every layer's transform, aggregate and exchange in
    both directions, the packed collectives with no layer, the loss and the
    update."""
    health = HealthConfig() if guarded else None
    trees = [_tree(_ops(_step_text(_model(grid, o), grid, health)))
             for o in ("none", "split-phase")]
    assert trees[0] == trees[1]
    for d in ("fwd", "bwd"):
        assert (d, None, "exchange") in trees[0]
        for part in ("transform", "aggregate", "exchange"):
            # Alg. 1 stops the backward at layer 0: no gradient send, and
            # no transposed aggregation under aggregate-first
            layers = range(1 if (d, part) != ("fwd", "transform") and
                           d == "bwd" and part != "transform" else 0, 3)
            assert {(d, f"L{i}", part) for i in layers} <= trees[0]
    assert (None, None, "loss") in trees[0]
    assert ("bwd", "L0", "update") in trees[0]


def test_eval_forward_is_named_and_scoped(tiny_pipeline):
    mc = ModelConfig(kind="sage",
                     feat_dim=tiny_pipeline.train_data.x.shape[-1],
                     hidden=8, num_layers=2, num_classes=4, dropout=0.0)
    model = PipeGCN(mc, PipeConfig.named("pipegcn-gf"))
    fwd = trainer.make_eval_forward(model)
    params = model.init_params(jax.random.PRNGKey(0))
    text = fwd.lower(tiny_pipeline.topo, params,
                     tiny_pipeline.val_data).as_text(dialect="hlo",
                                                     debug_info=True)
    assert hlo.module_name(text) == "jit_eval_forward"
    ops = _ops(text)
    assert ops
    _check_step_scopes(ops)
    assert not any("/bwd/" in name or "/update/" in name
                   for _, name, _, _ in ops)


_MESH_SCRIPT = r"""
import json, sys
sys.path.insert(0, {tests!r})
import jax
import test_trace_scopes as t
from repro.core import ModelConfig, PipeConfig, PipeGCN, trainer
from repro.core.health import HealthConfig
from repro.data import GraphDataPipeline
from repro.launch.mesh import make_partition_mesh
p = GraphDataPipeline.build("tiny", 4, kind="sage")
mc = ModelConfig(kind="sage", feat_dim=p.train_data.x.shape[-1], hidden=8,
                 num_layers=3, num_classes=4, dropout=0.0)
model = PipeGCN(mc, PipeConfig.named("pipegcn-gf"))
opt, args = t._train_args(model, p)
step = trainer.make_spmd_train_step(model, opt, make_partition_mesh(4, 1),
                                    p.topo, health=HealthConfig())
lowered = step.lower(*args)
body = [o for o in t._ops(lowered.as_text(dialect="hlo", debug_info=True))
        if o[1] and "/" in o[1]]
t._check_rules(body)
share = t._scoped_share(lowered.compile().as_text())
print(json.dumps({{"coll": [[o[0], o[1]] for o in body
                           if o[0] in ("all-to-all", "all-reduce")],
                  "scoped_share": share}}))
"""


def test_mesh_step_collectives_carry_their_scope():
    """On four host devices the step runs under shard_map: the boundary
    all-to-alls sit in `exchange`, the weight-gradient all-reduces in
    `update`, the loss's normalising all-reduces in `loss`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT.format(tests=HERE)], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["scoped_share"] >= 0.99, got
    coll = got["coll"]
    scopes = {}
    for opcode, name in coll:
        scopes.setdefault(opcode, set()).add(spans.scope_of(name))
    assert scopes["all-to-all"] == {"exchange"}
    assert scopes["all-reduce"] == {"update", "loss"}
    # one all-reduce per weight and bias of the 3 layers
    assert sum(spans.scope_of(n) == "update" for o, n in coll
               if o == "all-reduce") == 6


# -- host spans ----------------------------------------------------------

def test_profiled_run_records_the_trainer_spans(tiny_pipeline, tmp_path):
    """Three epochs of `tiny` under `jax.profiler.trace`, eval at epochs 0
    and 2: each epoch's span carries its epoch as `step_num` and holds its
    children in the loop's order; the eval and the log only where the
    eval ran."""
    mc = ModelConfig(kind="sage",
                     feat_dim=tiny_pipeline.train_data.x.shape[-1],
                     hidden=8, num_layers=2, num_classes=4, dropout=0.0)
    pc = dataclasses.replace(PipeConfig.named("pipegcn-gf"),
                             guard_exchange=True)
    with jax.profiler.trace(str(tmp_path)):
        trainer.train_pipegcn(tiny_pipeline, mc, pc, epochs=3,
                              eval_every=2, log=lambda _: None)
    host = spans.read_host(chiptrace.find_xplane(str(tmp_path)))
    assert {s.name for s in host} <= set(HOST_SPANS)
    epochs = [s for s in host if s.name == "trainer/epoch"]
    assert [s.step_num for s in epochs] == [0, 1, 2]
    inside = [s for s in host if s.name != "trainer/epoch"]
    for k, epoch in enumerate(epochs):
        kids = [s for s in inside
                if epoch.start_ns <= s.start_ns and s.end_ns <= epoch.end_ns]
        want = ["trainer/rng", "trainer/dispatch", "trainer/wait",
                "trainer/guard", "trainer/wait", "trainer/guard"]
        want += ["trainer/eval", "trainer/log"] if k in (0, 2) else []
        assert [s.name for s in kids] == want, (k, kids)
        # children follow one another: one thread, no overlap
        assert all(x.end_ns <= y.start_ns for x, y in zip(kids, kids[1:]))
    assert len(inside) == 8 + 6 + 8


def test_epoch_rate_counts_the_epochs_that_ran(tiny_pipeline, monkeypatch):
    """Preempted during epoch k, the run finishes that epoch and stops:
    k + 1 epochs ran, and the rate divides those, not the planned
    count, by the elapsed time."""
    import signal
    mc = ModelConfig(kind="sage",
                     feat_dim=tiny_pipeline.train_data.x.shape[-1],
                     hidden=8, num_layers=2, num_classes=4, dropout=0.0)
    pc = PipeConfig.named("pipegcn-gf")
    k = 2
    clock = iter([100.0, 104.0])

    class Time:
        @staticmethod
        def perf_counter():
            return next(clock)

    monkeypatch.setattr(trainer, "time", Time)

    def stop_at_k(line):
        if line.startswith(f"epoch {k:5d}"):
            os.kill(os.getpid(), signal.SIGTERM)

    res = trainer.train_pipegcn(tiny_pipeline, mc, pc, epochs=10,
                                eval_every=1, log=stop_at_k)
    assert res.preempted
    assert res.history["epoch"] == list(range(k + 1))
    assert res.epochs_per_sec == pytest.approx((k + 1) / 4.0)
