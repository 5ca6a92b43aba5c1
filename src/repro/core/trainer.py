"""Full-graph training driver: PipeGCN step + optimizer + eval loop.

This is the reference trainer used by examples, accuracy benchmarks, and the
convergence experiments (paper Tab. 4 / Fig. 4/9 analogues).
"""
from __future__ import annotations

import dataclasses
import signal
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core import elastic as elastic_mod
from repro.core.config import ModelConfig, PipeConfig
from repro.core.elastic import ElasticConfig, ElasticPlan
from repro.core.faults import FaultPlan, StalenessExceededError
from repro.core.health import (HealthConfig, TrainingAnomalyError,
                               health_check, tree_select)
from repro.core.pipegcn import PipeGCN, Topology
from repro.core.trace_utils import UPDATE
from repro.optim import Optimizer, adam


@dataclasses.dataclass
class TrainResult:
    """Outcome of one `train_pipegcn` run: the eval-metric trajectory
    (`history` lists loss / val_acc / test_acc / epoch), the final
    parameters, the last metric dict, the wall-clock epoch rate, the
    health/guard anomaly counters (skipped_steps, max_consecutive,
    exchange_fallbacks, max_effective_staleness — the latter two only
    under `guard_exchange`; device_losses/rejoins under an enabled
    ElasticConfig), the checkpoint step the run resumed from (None for a
    fresh run), how many elastic device-loss recoveries ran, and whether
    the run exited early on a SIGTERM/SIGINT (`preempted`, after writing
    a final checkpoint)."""

    history: dict          # lists: loss, val_acc, test_acc, epoch (per eval)
    params: dict
    final_metrics: dict
    epochs_per_sec: float
    anomalies: dict = dataclasses.field(default_factory=dict)
    resumed_from: int | None = None
    recoveries: int = 0
    preempted: bool = False


def make_jitted_train_step(model: PipeGCN, opt: Optimizer,
                           health: HealthConfig | None = None):
    """(topo, params, opt_state, buffers, data, key[, step_idx, faults])
    -> (loss, params, opt_state, buffers[, report]).

    Topology and data are traced arguments (not closure constants) so XLA
    does not constant-fold the graph structure into the executable.

    With `health` (an enabled HealthConfig) the step health-checks the
    update (repro.core.health) and ROLLS BACK in-graph: a non-finite /
    out-of-bound step returns the previous params/opt_state/buffers
    bitwise (select semantics) plus a fifth element, the
    ``{"ok", "grad_norm"}`` report. `step_idx` + `faults` (compiled
    FaultTables) inject that step's exchange faults; both default to None
    which traces the historical fault-free step."""
    health = health if health is not None and health.enabled else None

    def step(topo, params, opt_state, buffers, data, key, step_idx=None,
             faults=None):
        loss, grads, new_buffers, _ = model.train_step(
            topo, params, buffers, data, key, step_idx=step_idx,
            faults=faults)
        return _update(opt, health, loss, grads, params, opt_state, buffers,
                       new_buffers)

    return jax.jit(step, donate_argnums=(3,))


def _update(opt: Optimizer, health: HealthConfig | None, loss, grads, params,
            opt_state, buffers, new_buffers):
    """The train step's `update` scope: the optimizer and, with an enabled
    `health`, the health check and the rollback to the previous state.
    Returns the step's outputs."""
    with jax.named_scope(UPDATE):
        new_params, new_opt_state = opt.apply(params, grads, opt_state)
        if health is None:
            return loss, new_params, new_opt_state, new_buffers
        rep = health_check(loss, grads, new_buffers,
                           grad_norm_limit=health.grad_norm_limit)
        ok = rep["ok"]
        new_params = tree_select(ok, new_params, params)
        new_opt_state = tree_select(ok, new_opt_state, opt_state)
        new_buffers = tree_select(ok, new_buffers, buffers)
        return loss, new_params, new_opt_state, new_buffers, rep


def make_spmd_train_step(model: PipeGCN, opt: Optimizer, mesh, topo: Topology,
                         axis_name: str = "parts",
                         health: HealthConfig | None = None):
    """`make_jitted_train_step` analogue on a device mesh: the PipeGCN step
    runs under shard_map over `axis_name` (any partitions-per-device ratio,
    see `PipeGCN.make_spmd_step`); the optimizer update applies to the
    replicated grads. Same signature/returns as the sim-backend step
    (health rollback and fault injection included)."""
    spmd_step = model.make_spmd_step(mesh, topo, axis_name, train=True)
    health = health if health is not None and health.enabled else None

    def step(topo, params, opt_state, buffers, data, key, step_idx=None,
             faults=None):
        loss, _, grads, new_buffers = spmd_step(topo, params, buffers, data,
                                                key, step_idx, faults)
        return _update(opt, health, loss, grads, params, opt_state, buffers,
                       new_buffers)

    return jax.jit(step, donate_argnums=(3,))


def place_on_mesh(model: PipeGCN, mesh, axis_name: str, parts, buffers,
                  replicated):
    """Put the SPMD step's inputs on `mesh` once, with the shardings its
    shard_map reads them in: `parts` (a pytree of leading-partition arrays —
    the topology and data splits) split over `axis_name`, `buffers` by
    `PipeGCN.spmd_buffer_specs`, and `replicated` (params, optimizer state)
    copied to every device. The step's outputs keep these shardings, so no
    step reshards its inputs from one device. An array shared between
    splits (the data splits share features and labels) is placed once.
    Returns the placed (parts, buffers, replicated)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PS

    split = NamedSharding(mesh, PS(axis_name))
    placed = {}

    def put(x):
        if id(x) not in placed:
            placed[id(x)] = jax.device_put(x, split)
        return placed[id(x)]

    specs = model.spmd_buffer_specs(buffers, axis_name)
    bufs = jax.device_put(buffers, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, PS)))
    return (jax.tree.map(put, parts), bufs,
            jax.device_put(replicated, NamedSharding(mesh, PS())))


def make_eval_forward(model: PipeGCN, mesh=None, topo: Topology | None = None,
                      axis_name: str = "parts"):
    """Jitted (topo, params, data) -> logits with fresh (synchronous)
    exchanges, as at test time: the sim-backend forward, or on a mesh the
    same forward under shard_map, reading the arrays where
    `place_on_mesh` put them (Pallas kernels cannot be partitioned
    automatically, so a mesh-placed topology needs the shard_map form)."""
    # a named function, so that a trace names the program jit_eval_forward
    if mesh is None:
        def eval_forward(t, p, d):
            return model.forward(t, p, d)[1]
        return jax.jit(eval_forward)
    fresh = dataclasses.replace(model, pipe=PipeConfig.vanilla())
    fwd = fresh.make_spmd_step(mesh, topo, axis_name, train=False)
    key = jax.random.PRNGKey(0)

    def eval_forward(t, p, d):
        return fwd(t, p, fresh.init_buffers(t), d, key)[1]
    return jax.jit(eval_forward)


def _check_staleness(es, pipe_cfg: PipeConfig, anomalies: dict, epoch: int):
    """Host-side guard bookkeeping on one step's "es" counters; raises
    StalenessExceededError once any exchange's effective staleness
    (FIFO depth + consecutive fallbacks) exceeds `max_staleness`."""
    es = np.asarray(es)
    anomalies["exchange_fallbacks"] += int((es > 0).sum())
    worst = int(es.max()) if es.size else 0
    eff = pipe_cfg.staleness_steps + worst
    anomalies["max_effective_staleness"] = max(
        anomalies["max_effective_staleness"], eff)
    if eff > pipe_cfg.max_staleness:
        dst, d, ell, src = np.unravel_index(int(es.argmax()), es.shape)
        raise StalenessExceededError(
            f"effective staleness {eff} exceeds max_staleness="
            f"{pipe_cfg.max_staleness} at epoch {epoch}: the "
            f"{'forward feature' if d == 0 else 'backward gradient'} "
            f"exchange of layer {ell} from partition {src} to partition "
            f"{dst} has fallen back {worst} consecutive steps on top of "
            f"the base staleness {pipe_cfg.staleness_steps}; the bounded-"
            "staleness convergence contract no longer holds")


def train_pipegcn(pipeline, model_cfg: ModelConfig,
                  pipe_cfg: PipeConfig, epochs: int, lr: float = 0.01,
                  seed: int = 0, eval_every: int = 10,
                  log: Callable[[str], None] | None = None,
                  mesh=None, axis_name: str = "parts",
                  health: HealthConfig | None = None,
                  faults: FaultPlan | None = None,
                  ckpt_dir: str | None = None, checkpoint_every: int = 0,
                  resume: bool = False, checkpoint_keep: int | None = None,
                  elastic: ElasticConfig | None = None,
                  elastic_plan: ElasticPlan | None = None) -> TrainResult:
    """Reference training loop. With `mesh=None` the step runs on the sim
    backend (single device, partitions vmapped); passing a mesh runs the
    same model under shard_map — partitions need only be a multiple of the
    mesh size (multi-partition-per-device SPMD). On a mesh the topology,
    data, buffers and parameters are placed once (`place_on_mesh`) and
    eval runs the same forward under shard_map (`make_eval_forward`).

    Fault tolerance (ISSUE 9):
      * `health` — numerical guard policy; None means HealthConfig()
        (guards ON: non-finite steps are skipped with bitwise rollback
        and counted in TrainResult.anomalies). Pass
        HealthConfig(enabled=False) to opt out.
      * `faults` — a declarative FaultPlan compiled over the epoch horizon
        and injected into every exchange (repro.core.faults); combine
        with `pipe_cfg.guard_exchange` for detect-and-fall-back behaviour.
      * `ckpt_dir` + `checkpoint_every` — atomically checkpoint the FULL
        training state (params, opt_state, buffers, PRNG key, epoch)
        every N epochs; `resume=True` restores the latest checkpoint and
        continues BIT-EXACTLY (the saved key is the already-advanced
        split chain, so the resumed run draws the same subkeys an
        uninterrupted run would). `checkpoint_keep` prunes all but the
        newest N committed checkpoints after each save.

    Elasticity (ISSUE 10, repro.core.elastic):
      * `elastic` — an enabled ElasticConfig arms device-loss detection
        (requires `pipe_cfg.guard_exchange`): once every forward exchange
        out of one device has fallen back `detect_after` consecutive
        steps, the trainer restores the latest checkpoint, remaps the
        lost device's partitions onto the survivors (padded idle slots
        for uneven fits), warm-marks the remapped exchanges with
        `warm_staleness` es counts, rebuilds the mesh/step, and resumes —
        then scales back up at a checkpoint boundary once the device is
        healthy (`rejoin`). Checkpoints are ALWAYS written in the flat
        original layout, so any device count can restore them.
      * `elastic_plan` — start directly on a survivor layout (a fresh
        launch at the smaller device count, e.g. after a crash): with
        `resume=True` this routes through the same restore → remap →
        warm-mark path as a mid-run recovery, which makes the two
        bitwise identical from the shared checkpoint on. On a mesh
        backend, pass the matching `launch.mesh.make_survivor_mesh(plan)`
        as `mesh`.

    Preemption: SIGTERM/SIGINT (main thread only) finishes the in-flight
    epoch, writes a final checkpoint (when checkpointing is configured),
    and returns cleanly with `TrainResult.preempted=True`."""
    # "auto" defers to the engine the pipeline resolved (and built tiles
    # for); an explicit engine is used as given.
    if model_cfg.agg == "auto":
        model_cfg = dataclasses.replace(
            model_cfg, agg=getattr(pipeline, "agg", "coo"))
        choice = getattr(pipeline, "agg_choice",
                         {"agg": model_cfg.agg, "how": "forced"})
    else:
        choice = {"agg": model_cfg.agg, "how": "forced"}
    split = pipeline.split_spec() if hasattr(pipeline, "split_spec") else None
    model = PipeGCN(model_cfg, pipe_cfg, split=split)
    topo = pipeline.topo
    # Fail fast (before tracing) if the selected aggregation engine needs
    # Topology fields the pipeline was not built with.
    model._agg_slice(topo)
    # ... and if the config EXPLICITLY declares a node layout that is not
    # the one the pipeline was actually built with. The layout lives in
    # the data, so a drifting ModelConfig.layout must be loud — but
    # "auto" means "defer to the pipeline" here: any built layout is
    # numerically valid under any engine (the LAYOUT parity cells prove
    # coo-on-rcm exact), so auto must not reject a shared pipeline.
    have = getattr(pipeline, "layout", "natural")
    if model_cfg.layout != "auto" and model_cfg.layout != have:
        raise ValueError(
            f"ModelConfig.layout={model_cfg.layout!r} but the pipeline "
            f"was built with layout={have!r}; pass the same layout to "
            "GraphDataPipeline.build (or use layout=\"auto\")")
    if log:
        from repro.core.trace_utils import expected_boundary_collectives
        from repro.kernels.aggregate import describe_choice
        log(f"aggregation engine: {describe_choice(choice)}")
        n_coll = expected_boundary_collectives(model_cfg.num_layers,
                                               pipe_cfg.fused, train=True)
        sched = "fused-deferred" if pipe_cfg.fused else "per-layer"
        where = (f"{n_coll} boundary collectives/train step"
                 if mesh is not None else
                 f"{n_coll} boundary exchanges/train step, local on the "
                 "sim backend")
        log(f"comm schedule: {sched} ({where}, L={model_cfg.num_layers})")
        sp = model._split_active()
        if sp is not None:
            log(f"overlap schedule: split-phase (fwd boundary "
                f"{sp.fwd_bnd_tiles} tiles @ rows>={sp.row_tail}, "
                f"transpose boundary {sp.t_bnd_tiles} tiles @ "
                f"cols>={sp.col_tail}; collectives issued between phases)")
        else:
            why = ("disabled" if pipe_cfg.overlap == "none" else
                   "no feasible split" if split is None else
                   f"engine {model_cfg.agg!r} has no tile phases")
            log(f"overlap schedule: unsplit ({why})")
        # under the split the fused epilogue is bypassed, so report the
        # orders the split step actually resolves (fused=False pricing)
        orders = model.layer_orders(topo, train=True,
                                    fused=False if sp is not None else None)
        how = ("static FLOP model" if model_cfg.matmul_order == "auto"
               else "forced")
        log(f"matmul order ({how}, agg={model_cfg.agg}): "
            + " ".join(f"L{i}:{'PH.W' if o == 'aggregate-first' else 'P.HW'}"
                       for i, o in enumerate(orders)))
        if pipe_cfg.wire != "f32" or pipe_cfg.slice_boundary:
            codecs = model.wire_codecs(topo)
            widths = model.payload_widths(topo)
            sl = model.sliced_layers(topo)
            log("boundary wire: " + " ".join(
                f"L{i}:{c.name}x{w}{'s' if i in sl else ''}"
                for i, (c, w) in enumerate(zip(codecs, widths)))
                + (" (s = sliced to the post-transform width)" if sl else ""))
        layout = getattr(pipeline, "layout", "natural")
        if topo.tile_rows is not None:
            from repro.analysis.cost import graph_layout_report
            rep = graph_layout_report(pipeline.pg)
            log(f"graph layout: {layout} ({rep['tiles']} nonempty tiles, "
                f"bandwidth {rep['bandwidth']}, "
                f"{rep['halo_runs']} halo row runs)")
        else:
            log(f"graph layout: {layout}")
    if health is None:
        health = HealthConfig()
    hc = health if health.enabled else None

    P = topo.num_parts
    el_on = elastic is not None and elastic.enabled
    if elastic_plan is not None and not el_on:
        raise ValueError("elastic_plan requires an enabled ElasticConfig "
                         "(pass elastic=ElasticConfig(...))")
    if el_on:
        if not pipe_cfg.guard_exchange:
            raise ValueError(
                "the elastic runtime detects device loss through the "
                "guarded exchange's es counters; set "
                "PipeConfig.guard_exchange=True")
        if (pipe_cfg.staleness_steps + elastic.detect_after
                > pipe_cfg.max_staleness):
            raise ValueError(
                f"elastic detect_after={elastic.detect_after} can never "
                f"fire: staleness_steps={pipe_cfg.staleness_steps} + "
                f"detect_after exceeds max_staleness="
                f"{pipe_cfg.max_staleness}, so the run would abort first")
    plan = elastic_plan
    if plan is not None and plan.num_parts != P:
        raise ValueError(f"elastic_plan remaps {plan.num_parts} partitions "
                         f"but the pipeline has {P}")
    # original device granularity: what "one device" means to the
    # device_down fault plane and the loss detector
    if plan is not None:
        orig_devices = plan.orig_devices
    elif mesh is not None:
        orig_devices = int(mesh.devices.size)
    elif el_on:
        orig_devices = P // elastic.parts_per_device
    else:
        orig_devices = P
    if orig_devices < 1 or P % orig_devices:
        raise ValueError(
            f"num_parts={P} is not a multiple of the device count "
            f"{orig_devices}")
    orig_ppd = P // orig_devices

    params = model.init_params(jax.random.PRNGKey(seed))
    opt = adam(lr)
    opt_state = opt.init(params)
    mesh0 = mesh
    topo_run, train_run, val_run = topo, pipeline.train_data, pipeline.val_data
    if plan is not None:
        if mesh is not None and int(mesh.devices.size) != plan.n_devices:
            raise ValueError(
                f"mesh has {int(mesh.devices.size)} devices but the plan's "
                f"survivor set has {plan.n_devices} — pass "
                "launch.mesh.make_survivor_mesh(plan)")
        topo_run = elastic_mod.remap_topology(topo, plan)
        train_run = elastic_mod.remap_data(pipeline.train_data, plan)
        val_run = elastic_mod.remap_data(pipeline.val_data, plan)
    buffers = model.init_buffers(topo_run)

    # the sim backend's step and eval; a mesh replaces both (use_mesh)
    step = make_jitted_train_step(model, opt, health=hc)
    fwd = make_eval_forward(model)

    def build_tables(active_plan):
        # with a plan active the lost device is already remapped away, so
        # its device_down sites are moot; pad partitions never carry real
        # faults (mask_pad_faults) — their idle wires must stay valid
        if faults is None or faults.is_empty():
            return None
        fp = faults if active_plan is None else faults.without_device_down()
        if fp.is_empty():
            return None
        if active_plan is None:
            return fp.compile(epochs, model_cfg.num_layers, P,
                              parts_per_device=orig_ppd)
        tab = fp.compile(epochs, model_cfg.num_layers,
                         active_plan.padded_parts,
                         parts_per_device=active_plan.n_local)
        return elastic_mod.mask_pad_faults(tab, P)

    tables = build_tables(plan)
    if tables is not None and log:
        n = int(np.asarray(tables.drop).sum() +
                np.asarray(tables.corrupt).sum())
        log(f"fault injection: {n} faulted exchange sites over "
            f"{epochs} epochs"
            + (", guard_exchange ON (checksum + stale fallback)"
               if pipe_cfg.guard_exchange else
               ", guard_exchange OFF (faults land undetected)"))

    key = jax.random.PRNGKey(seed + 1)
    start_epoch = 0
    resumed_from = None

    def flat_template():
        # checkpoints are ALWAYS written in the flat original layout
        # (remapped runs unmap before saving), so one template serves
        # every device count
        return {"params": params, "opt_state": opt_state,
                "buffers": model.init_buffers(topo), "key": key,
                "epoch": jnp.zeros((), jnp.int32)}

    def apply_plan_state(flat_bufs, p):
        # the ONE restore → remap → warm-mark path shared by mid-run
        # recovery and a fresh survivor-layout launch: routing both
        # through it is what makes them bitwise identical
        b = elastic_mod.remap_buffers(flat_bufs, p)
        return elastic_mod.warm_mark(b, p.moved_partitions(),
                                     elastic.warm_staleness if el_on else 0,
                                     P)

    if resume:
        if not ckpt_dir:
            raise ValueError("resume=True requires ckpt_dir")
        from repro.checkpoint import latest_step, restore_checkpoint
        last = latest_step(ckpt_dir)
        if last is not None:
            state = restore_checkpoint(ckpt_dir, last, flat_template())
            params, opt_state = state["params"], state["opt_state"]
            key = state["key"]
            buffers = (apply_plan_state(state["buffers"], plan)
                       if plan is not None else state["buffers"])
            start_epoch = int(state["epoch"])
            resumed_from = last
            if log:
                log(f"resumed from checkpoint step {last} "
                    f"(continuing at epoch {start_epoch})")

    def use_mesh(m):
        # a mesh gets its own step and eval, and every array the step reads
        # is placed where its shard_map reads it: once per mesh, not once
        # per step
        nonlocal step, fwd, topo_run, train_run, val_run, buffers, params
        nonlocal opt_state
        step = make_spmd_train_step(model, opt, m, topo_run, axis_name,
                                    health=hc)
        fwd = make_eval_forward(model, m, topo_run, axis_name)
        (topo_run, train_run, val_run), buffers, (params, opt_state) = (
            place_on_mesh(model, m, axis_name,
                          (topo_run, train_run, val_run), buffers,
                          (params, opt_state)))

    if mesh is not None:
        use_mesh(mesh)

    anomalies = {"skipped_steps": 0, "max_consecutive": 0}
    if pipe_cfg.guard_exchange:
        anomalies["exchange_fallbacks"] = 0
        anomalies["max_effective_staleness"] = pipe_cfg.staleness_steps
    if el_on:
        anomalies["device_losses"] = []
        anomalies["rejoins"] = 0

    def save_state(step_no):
        from repro.checkpoint import save_checkpoint
        # the saved key is ALREADY advanced past this epoch's split,
        # so a resumed run continues the exact subkey sequence
        flat = (elastic_mod.unmap_buffers(buffers, plan)
                if plan is not None else buffers)
        save_checkpoint(ckpt_dir, step_no, {
            "params": params, "opt_state": opt_state, "buffers": flat,
            "key": key, "epoch": jnp.asarray(step_no, jnp.int32)},
            keep_last=checkpoint_keep)
        return flat

    def device_back(at_step):
        lost = set(range(orig_devices)) - set(plan.survivors)
        if faults is not None and faults.downed_devices(at_step) & lost:
            return False
        if mesh0 is not None and len(jax.devices()) < int(mesh0.devices.size):
            return False
        return True

    stop_signals: list = []
    sig_handlers = {}
    if threading.current_thread() is threading.main_thread():
        for signum in (signal.SIGTERM, signal.SIGINT):
            try:
                sig_handlers[signum] = signal.signal(
                    signum, lambda s, _f: stop_signals.append(s))
            except (ValueError, OSError):
                pass

    consec = 0
    recoveries = 0
    preempted = False
    cur_survivors = (plan.survivors if plan is not None
                     else tuple(range(orig_devices)))
    cur_n_local = plan.n_local if plan is not None else orig_ppd
    last_metric, last_metric_epoch = None, -1
    history = {"loss": [], "val_acc": [], "test_acc": [], "epoch": []}
    finished = 0    # epochs that ran to their end, re-runs after recovery too
    t0 = time.perf_counter()
    epoch = start_epoch
    try:
        while epoch < epochs:
            # host spans (repro.core.trace_utils.HOST_SPANS): what the host
            # does in each epoch, on the profiler's clock when it traces
            with StepTraceAnnotation("trainer/epoch", step_num=epoch):
                try:
                    with TraceAnnotation("trainer/rng"):
                        key, sub = jax.random.split(key)
                    with TraceAnnotation("trainer/dispatch"):
                        if tables is not None:
                            out = step(topo_run, params, opt_state, buffers,
                                       train_run, sub,
                                       jnp.asarray(epoch, jnp.int32), tables)
                        else:
                            out = step(topo_run, params, opt_state, buffers,
                                       train_run, sub)
                    if hc is not None:
                        loss, params, opt_state, buffers, rep = out
                        with TraceAnnotation("trainer/wait"):
                            ok = bool(rep["ok"])
                        with TraceAnnotation("trainer/guard"):
                            if not ok:
                                anomalies["skipped_steps"] += 1
                                consec += 1
                                anomalies["max_consecutive"] = max(
                                    anomalies["max_consecutive"], consec)
                                if consec >= hc.max_consecutive_anomalies:
                                    raise TrainingAnomalyError(
                                        f"{consec} consecutive unhealthy "
                                        f"training steps (epoch {epoch}, "
                                        f"loss {float(loss)}, grad norm "
                                        f"{float(rep['grad_norm'])}); "
                                        "aborting instead of spinning on a "
                                        "poisoned run")
                            else:
                                consec = 0
                    else:
                        loss, params, opt_state, buffers = out
                    if pipe_cfg.guard_exchange:
                        with TraceAnnotation("trainer/wait"):
                            es_host = np.asarray(buffers["es"])
                        with TraceAnnotation("trainer/guard"):
                            if el_on:
                                # device loss pre-empts the staleness abort:
                                # a blanket whole-device fallback row is an
                                # outage to recover from, not a contract
                                # violation
                                down = elastic_mod.detect_device_loss(
                                    es_host, cur_n_local, P,
                                    elastic.detect_after)
                                if down is not None:
                                    dev = (cur_survivors[down]
                                           if plan is not None else down)
                                    rest = tuple(s for s in cur_survivors
                                                 if s != dev)
                                    raise elastic_mod.DeviceLossError(
                                        f"device {dev} detected down at "
                                        f"epoch {epoch}: every forward "
                                        "exchange out of it has fallen back "
                                        f">= {elastic.detect_after} "
                                        "consecutive steps", dev, rest, epoch)
                            _check_staleness(es_host, pipe_cfg, anomalies,
                                             epoch)
                    if epoch % eval_every == 0 or epoch == epochs - 1:
                        with TraceAnnotation("trainer/eval"):
                            logits = fwd(topo_run, params, val_run)
                            m = pipeline.metric(logits)
                            last_metric, last_metric_epoch = m, epoch
                            history["loss"].append(float(loss))
                            history["val_acc"].append(m["val"])
                            history["test_acc"].append(m["test"])
                            history["epoch"].append(epoch)
                        if log:
                            with TraceAnnotation("trainer/log"):
                                line = (f"epoch {epoch:5d} loss "
                                        f"{float(loss):.4f} val "
                                        f"{m['val']:.4f} test {m['test']:.4f}")
                                if anomalies["skipped_steps"]:
                                    line += (" anomalies "
                                             f"{anomalies['skipped_steps']}")
                                if (pipe_cfg.guard_exchange
                                        and anomalies["exchange_fallbacks"]):
                                    fb = anomalies["exchange_fallbacks"]
                                    es = anomalies["max_effective_staleness"]
                                    line += (f" fallbacks {fb} es {es}"
                                             f"/{pipe_cfg.max_staleness}")
                                log(line)
                    saved = False
                    if (ckpt_dir and checkpoint_every
                            and (epoch + 1) % checkpoint_every == 0):
                        with TraceAnnotation("trainer/checkpoint"):
                            flat = save_state(epoch + 1)
                        saved = True
                        if (plan is not None and el_on and elastic.rejoin
                                and device_back(epoch + 1)):
                            with TraceAnnotation("trainer/recover"):
                                # rejoin: the just-saved flat state IS the
                                # live state unmapped — resume it on the full
                                # device count, warm-marking the partitions
                                # moving home
                                moved = plan.moved_partitions()
                                buffers = elastic_mod.warm_mark(
                                    flat, moved, elastic.warm_staleness, P)
                                topo_run, train_run, val_run = (
                                    topo, pipeline.train_data,
                                    pipeline.val_data)
                                plan = None
                                cur_survivors = tuple(range(orig_devices))
                                cur_n_local = orig_ppd
                                if mesh0 is not None:
                                    use_mesh(mesh0)
                                tables = build_tables(None)
                                anomalies["rejoins"] += 1
                            if log:
                                with TraceAnnotation("trainer/log"):
                                    log(f"rejoin: scaled back up to "
                                        f"{orig_devices} devices at "
                                        f"checkpoint step {epoch + 1} "
                                        f"({len(moved)} partitions "
                                        "warm-marked)")
                    finished += 1
                    if stop_signals:
                        if ckpt_dir and checkpoint_every and not saved:
                            with TraceAnnotation("trainer/checkpoint"):
                                save_state(epoch + 1)
                        preempted = True
                        if log:
                            with TraceAnnotation("trainer/log"):
                                log(f"preempted (signal "
                                    f"{int(stop_signals[0])}): epoch {epoch}"
                                    " finished, final checkpoint written, "
                                    "exiting cleanly")
                        break
                    epoch += 1
                except elastic_mod.DeviceLossError as err:
                    if not el_on:
                        raise
                    if recoveries >= elastic.max_recoveries:
                        raise
                    if not ckpt_dir:
                        raise RuntimeError(
                            "elastic recovery needs a checkpoint to restore "
                            "from — run with ckpt_dir + checkpoint_every"
                        ) from err
                    with TraceAnnotation("trainer/recover"):
                        from repro.checkpoint import (latest_step,
                                                      restore_checkpoint)
                        last = latest_step(ckpt_dir)
                        if last is None:
                            raise RuntimeError(
                                "device lost before the first checkpoint "
                                "landed — nothing to recover from") from err
                        if not err.survivors:
                            raise RuntimeError(
                                "no surviving devices to remap onto") from err
                        plan = ElasticPlan(num_parts=P,
                                           orig_devices=orig_devices,
                                           survivors=err.survivors)
                        state = restore_checkpoint(ckpt_dir, last,
                                                   flat_template())
                        params, opt_state = (state["params"],
                                             state["opt_state"])
                        key = state["key"]
                        buffers = apply_plan_state(state["buffers"], plan)
                        epoch = int(state["epoch"])
                        topo_run = elastic_mod.remap_topology(topo, plan)
                        train_run = elastic_mod.remap_data(
                            pipeline.train_data, plan)
                        val_run = elastic_mod.remap_data(pipeline.val_data,
                                                         plan)
                        cur_survivors = plan.survivors
                        cur_n_local = plan.n_local
                        if mesh0 is not None:
                            from repro.launch.mesh import make_survivor_mesh
                            use_mesh(make_survivor_mesh(plan, axis_name))
                        tables = build_tables(plan)
                        recoveries += 1
                        consec = 0
                        anomalies["device_losses"].append({
                            "device": err.device,
                            "detected_epoch": err.epoch,
                            "resumed_from": int(last),
                            "survivors": list(plan.survivors)})
                    if log:
                        with TraceAnnotation("trainer/log"):
                            log(f"device {err.device} lost at epoch "
                                f"{err.epoch}: remapped {P} partitions onto "
                                f"survivors {list(plan.survivors)} "
                                f"({plan.n_local}/device, {plan.pad_parts} "
                                f"pad), restored checkpoint step {last}, "
                                f"resuming at epoch {epoch}")
    finally:
        for signum, h in sig_handlers.items():
            signal.signal(signum, h)
    dt = time.perf_counter() - t0
    if last_metric_epoch == epochs - 1:
        final = last_metric    # the last epoch already ran this eval
    else:
        final = pipeline.metric(fwd(topo_run, params, val_run))
    return TrainResult(history=history, params=params, final_metrics=final,
                       epochs_per_sec=(finished / dt if dt > 0 and finished
                                       else 0.0),
                       anomalies=anomalies, resumed_from=resumed_from,
                       recoveries=recoveries, preempted=preempted)
