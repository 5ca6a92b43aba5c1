"""Drive the program: build the training job a cell describes the way the
training CLI (`repro.launch.train.run_gcn`) builds it, and run its entry
point `train_pipegcn` once, from the seed, through a warm-up and a timed
window.

The window is taken inside that one call. Each call of the program's train
step is one epoch; the step factories that `train_pipegcn` looks up are
wrapped so that the benchmark sees each call as it is dispatched, without
changing what runs. The window opens as epoch `warm` is dispatched (every
earlier epoch has been synced by the trainer's own health check, and every
program has compiled) and closes when the trainer, told to stop by SIGTERM
after `seconds`, logs that it finished its epoch. The trainer's eval, at the
CLI's cadence, and its per-epoch host checks fall inside the window.

The first three steps, which the correctness check follows, run before the
window through the same call: the wrapper keeps their loss, the parameters
they were given and returned, and the optimizer state after the first.
"""
from __future__ import annotations

import dataclasses
import os
import re
import signal
import threading
import time

import numpy as np

RECORDED_STEPS = 3
# The first epoch dispatched inside the window: step 0 compiles the train
# step and the eval forward; steps 1-3 are warm and are the recorded ones.
WARM_EPOCHS = 4
# Far more epochs than any window holds: the window ends the run by
# SIGTERM, never by the epoch count.
EPOCHS = 10 ** 7
_PREEMPTED = re.compile(r"^preempted \(signal \d+\): epoch (\d+) finished")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def split_over(tree) -> int:
    """Fewest devices any array of `tree` is split over (1 for an array on
    one device or copied whole to every device)."""
    import jax
    return min((1 if x.sharding.is_fully_replicated
                else len(x.sharding.device_set))
               for x in jax.tree.leaves(tree))


@dataclasses.dataclass
class Recording:
    """What the first steps handed back, as device arrays until `to_host`."""

    losses: list = dataclasses.field(default_factory=list)
    params_in: object = None       # the parameters step 0 was given
    opt_state_1: object = None     # optimizer state after step 0
    params_out: object = None      # parameters after the last recorded step
    buffers: dict | None = None    # norm of each stale buffer after them
    placement: dict | None = None  # devices the step's inputs are split over
    # (jitted program, argument shapes) of the eval forward as first called
    # and of the train step as called just before the window: their
    # compiled HLO describes the operations the trace names
    programs: list = dataclasses.field(default_factory=list)

    def to_host(self) -> "Recording":
        import jax
        host = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
        return dataclasses.replace(
            self, losses=[float(x) for x in self.losses],
            params_in=host(self.params_in),
            opt_state_1=host(self.opt_state_1),
            params_out=host(self.params_out),
            buffers={k: float(v) for k, v in self.buffers.items()})

    def compiled(self) -> list:
        """Each recorded program, compiled (a compilation cache hit: these
        are the programs that ran)."""
        return [fn.lower(*shapes).compile() for fn, shapes in self.programs]


def _shapes(args):
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        args)


class Window:
    """The timed window of one `train_pipegcn` call: opened by the step
    wrapper as epoch `warm` is dispatched, closed by the trainer's own
    SIGTERM path. `on_open` and `on_close` run at its two ends (the traced
    run starts and stops the profiler there)."""

    def __init__(self, seconds: float, warm: int = WARM_EPOCHS,
                 on_open=None, on_close=None, log=None):
        self.seconds = seconds
        self.warm = warm
        self.on_open = on_open
        self.on_close = on_close
        self.echo = log
        self.t_open = self.t_close = None
        self.first_epoch = self.last_epoch = None
        self.compiles_in_window = 0
        self._timer = None

    # -- called by the step wrapper, before each epoch's step is dispatched
    def epoch_starting(self, epoch: int):
        if epoch != self.warm or self.t_open is not None:
            return
        if self.on_open:
            self.on_open()
        self.first_epoch = epoch
        self._timer = threading.Timer(
            self.seconds, os.kill, (os.getpid(), signal.SIGTERM))
        self._timer.daemon = True
        self.t_open = time.perf_counter()
        self._timer.start()

    # -- the trainer's log callback
    def log(self, line: str):
        now = time.perf_counter()
        m = _PREEMPTED.match(line)
        if m and self.t_open is not None and self.t_close is None:
            self.t_close = now
            self.last_epoch = int(m.group(1))
            if self.on_close:
                self.on_close()
        if self.echo:
            self.echo(line)

    def compile_event(self, event: str, duration: float, **_):
        if (event == _COMPILE_EVENT and self.t_open is not None
                and self.t_close is None):
            self.compiles_in_window += 1

    def cancel(self):
        if self._timer is not None:
            self._timer.cancel()
            self._timer.join(timeout=5)

    @property
    def epochs(self) -> int:
        return self.last_epoch - self.first_epoch + 1

    @property
    def elapsed(self) -> float:
        return self.t_close - self.t_open


def buffer_norms(buffers) -> dict:
    """The norm of each of the step's stale buffers (taken before the next
    step, which donates them), named as `reference.buffer_norms` names
    them: every layer's feature buffer, the gradient buffers of layers 1
    and up (layer 0 ships no gradient)."""
    import jax.numpy as jnp
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(  # noqa: E731
        x.astype(jnp.float32))))
    out = {f"feat{ell}": norm(x) for ell, x in enumerate(buffers["feat"])}
    out.update({f"grad{ell}": norm(x)
                for ell, x in enumerate(buffers["grad"]) if ell > 0})
    return out


def recording_factory(factory, rec: Recording, window: Window,
                      wrap_step=None):
    """Wrap a train-step factory of `repro.core.trainer` so that every step
    it builds reports each call to `window` and hands its first
    RECORDED_STEPS results to `rec`. `wrap_step` (tests and the calibration
    only) puts a planted fault around the program's own step."""

    def make(*args, **kwargs):
        jitted = step = factory(*args, **kwargs)
        if wrap_step is not None:
            step = wrap_step(step)
        calls = [0]

        def call(topo, params, opt_state, buffers, data, key, *rest):
            k = calls[0]
            calls[0] += 1
            window.epoch_starting(k)
            if k == window.warm - 1:
                rec.programs.append((jitted, _shapes(
                    (topo, params, opt_state, buffers, data, key) + rest)))
            if k == 0:
                rec.params_in = params
                rec.placement = {"topology": split_over(topo),
                                 "data": split_over(data),
                                 "buffers": split_over(buffers)}
            out = step(topo, params, opt_state, buffers, data, key, *rest)
            if k < RECORDED_STEPS:
                rec.losses.append(out[0])
                if k == 0:
                    rec.opt_state_1 = out[2]
                if k == RECORDED_STEPS - 1:
                    rec.params_out = out[1]
                    rec.buffers = buffer_norms(out[3])
            return out

        return call

    return make


def recording_eval(factory, rec: Recording):
    """Wrap `repro.core.trainer.make_eval_forward` so that the eval
    forward it builds is recorded, with its argument shapes, as first
    called."""

    def make(*args, **kwargs):
        fwd = factory(*args, **kwargs)
        first = [True]

        def call(*a):
            if first[0]:
                first[0] = False
                rec.programs.append((fwd, _shapes(a)))
            return fwd(*a)

        return call

    return make


def program_dataset(graph, name: str):
    """The benchmark's graph as the program's `GraphDataset`."""
    from repro.graph.csr import CSRGraph
    from repro.graph.synthetic import GraphDataset
    g = CSRGraph(indptr=graph.indptr, indices=graph.indices,
                 weights=np.ones(graph.num_edges, np.float32))
    return GraphDataset(name=name, graph=g, features=graph.features,
                        labels=graph.labels, train_mask=graph.train_mask,
                        val_mask=graph.val_mask, test_mask=graph.test_mask,
                        num_classes=graph.num_classes,
                        multilabel=graph.multilabel)


def build(config: dict, traffic: dict, graph):
    """The job as `run_gcn` builds it: the partitioned pipeline, the model
    and pipeline configurations and, on a mesh, the mesh. Fields whose
    dataclass default is the CLI's default are left to the program; only
    `matmul_order` differs today and is set as the CLI sets it. The
    partitioner is seeded by the traffic's `graph_seed`, like the edges, so
    that every run of a cell partitions the same graph the same way."""
    from repro.core import ModelConfig, PipeConfig
    from repro.data import GraphDataPipeline
    pipeline = GraphDataPipeline.build(
        program_dataset(graph, config["name"]), int(traffic["partitions"]),
        kind=config["kind"], seed=int(traffic["graph_seed"]))
    mc = ModelConfig(kind=config["kind"], feat_dim=int(config["feat_dim"]),
                     hidden=int(config["hidden"]),
                     num_layers=int(config["num_layers"]),
                     num_classes=int(config["num_classes"]),
                     dropout=float(config["dropout"]),
                     multilabel=bool(config["multilabel"]),
                     matmul_order="auto")
    pc = PipeConfig.named(traffic["variant"])
    mesh = None
    if traffic["backend"] == "mesh":
        from repro.launch.mesh import make_partition_mesh
        mesh = make_partition_mesh(int(traffic["partitions"]),
                                   int(traffic["parts_per_device"]))
    return pipeline, mc, pc, mesh


def train(config: dict, traffic: dict, pipeline, mc, pc, mesh, seed: int,
          window: Window, wrap_step=None, patches=()):
    """One `train_pipegcn` call through the warm-up and the window. Returns
    (TrainResult, Recording). `patches` are (object, attribute, value)
    triples set for the call and restored after it: tests and the
    calibration plant faults with them; the benchmark's runs pass none."""
    import jax
    from repro.core import trainer
    rec = Recording()
    saved = [(trainer, "make_jitted_train_step",
              trainer.make_jitted_train_step),
             (trainer, "make_spmd_train_step", trainer.make_spmd_train_step),
             (trainer, "make_eval_forward", trainer.make_eval_forward)]
    saved += [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    trainer.make_jitted_train_step = recording_factory(
        saved[0][2], rec, window, wrap_step)
    trainer.make_spmd_train_step = recording_factory(
        saved[1][2], rec, window, wrap_step)
    trainer.make_eval_forward = recording_eval(saved[2][2], rec)
    for obj, attr, value in patches:
        setattr(obj, attr, value)
    jax.monitoring.register_event_duration_secs_listener(
        window.compile_event)
    try:
        res = trainer.train_pipegcn(
            pipeline, mc, pc, epochs=EPOCHS, lr=float(config["lr"]),
            seed=seed, eval_every=int(traffic["eval_every"]), log=window.log,
            mesh=mesh)
    finally:
        window.cancel()
        jax.monitoring.unregister_event_duration_listener(
            window.compile_event)
        for obj, attr, value in reversed(saved):
            setattr(obj, attr, value)
    return res, rec
