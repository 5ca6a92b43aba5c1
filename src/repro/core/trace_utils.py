"""The PipeGCN step's trace vocabulary, and jaxpr-level collective
accounting.

Scopes and spans. Every op of the train step and the eval forward is
emitted under a `jax.named_scope` path, which XLA keeps as the op's
`op_name` metadata (no cost at run time): `fwd/L{i}` or `bwd/L{i}` names
the layer, then one of `SCOPES` names the part of the step:

  aggregate  the engines' SpMM P.H and its transpose (a fused kernel's
             dense epilogue included)
  transform  the dense layer math outside the engines: H.W, the SAGE self
             term, the weight and input gradients, activations, dropout
  exchange   payload to halo: the send gather, the codec's encode and
             decode, the exchange itself (a transpose on the sim backend,
             `all_to_all` on a mesh), the receive scatter, the stale-buffer
             update
  loss       the masked loss and its gradient
  update     the weight-gradient reduction, the health check and its
             rollback, the optimizer

Scopes nest (the engines open `aggregate` inside a layer's `transform`);
the innermost of `SCOPES` on an op's path is its part. The fused deferred
exchange packs every layer's payload into one collective, so its ops carry
`fwd/exchange` or `bwd/exchange` with no layer.

The epoch loop of `repro.core.trainer.train_pipegcn` opens host spans
(`jax.profiler.TraceAnnotation`) named by `HOST_SPANS`: `trainer/epoch`
(a `StepTraceAnnotation` carrying `step_num`) around each iteration, and
its children around what the host does in it. The profiler records them
on the clock of the device ops.

Collective accounting. The fused deferred exchange collapses the per-step
boundary collectives from 2L-1 blocking per-layer calls (L forward feature
exchanges + L-1 backward gradient exchanges) to exactly 2 (one packed
exchange per direction). These
helpers trace a step function and count primitives in the jaxpr — the
regression test and the benchmark trajectory both pin the counts so the
fusion can never silently regress.

Counting happens at the jaxpr level (before XLA optimization), so it works
on any backend and any device count — an `all_to_all` over a 1-device mesh
axis is still one `all_to_all` eqn in the trace.
"""
from __future__ import annotations

import jax

SCOPES = ("aggregate", "transform", "exchange", "loss", "update")
AGGREGATE, TRANSFORM, EXCHANGE, LOSS, UPDATE = SCOPES
# The epoch span, then its children: the PRNG split, the step's dispatch,
# host reads that block on the device, the guard's host checks, the eval,
# the log callback, checkpoint writes, and elastic recovery.
HOST_SPANS = ("trainer/epoch", "trainer/rng", "trainer/dispatch",
              "trainer/wait", "trainer/guard", "trainer/eval", "trainer/log",
              "trainer/checkpoint", "trainer/recover")


def layer_scope(direction: str, ell: int | None, part: str):
    """`jax.named_scope` of one part of one layer's forward or backward:
    `layer_scope("fwd", 2, EXCHANGE)` is `fwd/L2/exchange`; `ell=None`
    names a part shared by every layer (`fwd/exchange`)."""
    layer = () if ell is None else (f"L{ell}",)
    return jax.named_scope("/".join((direction,) + layer + (part,)))


def _iter_subjaxprs(v):
    """Yield every jaxpr reachable from an eqn-param value (jaxpr,
    ClosedJaxpr, or nested lists/tuples of either — covers shard_map,
    pjit, custom_vjp, scan and cond params)."""
    if hasattr(v, "eqns"):
        yield v
    elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
        yield v.jaxpr
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _iter_subjaxprs(x)


def count_primitives(jaxpr, names) -> dict[str, int]:
    """Occurrences of each primitive name anywhere in `jaxpr` (recursing
    into nested jaxprs). Accepts a ClosedJaxpr or a raw jaxpr."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    counts = dict.fromkeys(names, 0)

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in counts:
                counts[eqn.primitive.name] += 1
            for v in eqn.params.values():
                for sub in _iter_subjaxprs(v):
                    walk(sub)

    walk(jaxpr)
    return counts


def collective_counts(fn, *args) -> dict[str, int]:
    """Trace `fn(*args)` and count the inter-device collectives in its
    jaxpr: boundary exchanges (`all_to_all`) and reductions (`psum`)."""
    jx = jax.make_jaxpr(fn)(*args)
    return count_primitives(jx, ("all_to_all", "psum"))


def primitive_event_trace(jaxpr, names) -> list[str]:
    """The ORDERED sequence of `names` primitives in `jaxpr` — depth-first
    at each eqn's position (sub-jaxprs of pjit/shard_map/scan expand in
    place), so the list reflects jaxpr program order. This is what the
    split-phase schedule checker inspects: trace order is the order XLA
    receives, so a collective appearing between the boundary- and
    interior-phase `pallas_call`s proves it was ISSUED between them."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    wanted = set(names)
    events: list[str] = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in wanted:
                events.append(eqn.primitive.name)
            for v in eqn.params.values():
                for sub in _iter_subjaxprs(v):
                    walk(sub)

    walk(jaxpr)
    return events


def traced_step_events(fn, *args,
                       names=("pallas_call", "all_to_all")) -> list[str]:
    """`primitive_event_trace` of a traced step function."""
    return primitive_event_trace(jax.make_jaxpr(fn)(*args), names)


def expected_split_events(num_layers: int, fused: bool,
                          train: bool = True) -> list[str]:
    """The ("pallas_call" | "all_to_all") event sequence of a split-phase
    step on a TILE engine under aggregate-first ordering (one kernel per
    phase; the layer-0 backward has no Pᵀ pass, Alg. 1 stops there).

    Forward, per-layer schedule: layer 0's exchange precedes the loop
    (its payload is x), then each layer runs [boundary kernel, next
    layer's exchange (if any), interior kernel]. Fused schedule: the one
    packed exchange is issued right after the LAST payload is gathered —
    between layer L-2's phases (pre-loop when L == 1). Backward mirrors
    it transposed down to layer 1, with the fused flush between layer 1's
    phases. Same collective COUNT as the unsplit schedule in every mode —
    the split only repositions each collective between a phase pair.
    """
    L = num_layers
    P, A = "pallas_call", "all_to_all"
    ev: list[str] = []
    if fused and L == 1:
        ev += [A]
    if not fused:
        ev += [A]
    for ell in range(L):
        ev += [P]
        if fused:
            if L > 1 and ell == L - 2:
                ev += [A]
        elif ell < L - 1:
            ev += [A]
        ev += [P]
    if not train:
        return ev
    for ell in reversed(range(1, L)):
        ev += [P]
        if (not fused) or ell == 1:
            ev += [A]
        ev += [P]
    return ev


def check_split_schedule(model, mesh, topo, data, axis_name="parts",
                         train: bool = True) -> list[str]:
    """Trace a split-phase `make_spmd_step` and assert its boundary
    collectives sit BETWEEN the phase kernels exactly as scheduled
    (`expected_split_events`). Returns the traced event list."""
    step = model.make_spmd_step(mesh, topo, axis_name, train=train)
    params = model.init_params(jax.random.PRNGKey(0))
    buffers = model.init_buffers(topo)
    events = traced_step_events(step, topo, params, buffers, data,
                                jax.random.PRNGKey(0))
    expected = expected_split_events(model.model.num_layers,
                                     model.pipe.fused, train=train)
    if events != expected:
        raise AssertionError(
            f"split-phase schedule mismatch:\n  traced   {events}\n"
            f"  expected {expected}")
    return events


def expected_boundary_collectives(num_layers: int, fused: bool,
                                  train: bool = True) -> int:
    """The collective-count math of the two communication schedules.

    Per-layer (blocking): L forward feature exchanges + (L-1) backward
    gradient exchanges = 2L-1 per training step (L at eval).
    Fused-deferred (stale mode): 1 packed forward + 1 packed backward = 2
    per training step (1 at eval); a 1-layer model has no gradient sends,
    so its backward collective vanishes in both schedules.
    """
    L = num_layers
    if fused:
        fwd, bwd = 1, (1 if L > 1 else 0)
    else:
        fwd, bwd = L, L - 1
    return fwd + (bwd if train else 0)


def traced_step_collectives(model, mesh, topo, data, axis_name="parts",
                            train: bool = True) -> dict[str, int]:
    """Collective counts of a traced `PipeGCN.make_spmd_step` jaxpr, with
    freshly initialized params/buffers as example arguments."""
    step = model.make_spmd_step(mesh, topo, axis_name, train=train)
    params = model.init_params(jax.random.PRNGKey(0))
    buffers = model.init_buffers(topo)
    return collective_counts(step, topo, params, buffers, data,
                             jax.random.PRNGKey(0))


def traced_wire_bytes(fn, *args) -> int:
    """Total bytes-on-wire of every `all_to_all` in a traced `fn(*args)`.

    Sums, over each all_to_all eqn anywhere in the jaxpr (recursing into
    shard_map/pjit bodies), the operand's per-device element count times
    its dtype itemsize — i.e. the bytes ONE device hands the collective
    per step. This is the quantity the boundary codecs shrink: a bf16
    wire halves it, int8/int4 shrink it ~4x/~8x (plus the scale region),
    and feature slicing shrinks the payload width itself. Shape-and-dtype
    static, so the figure is exact, device-free, and diffable in CI."""
    jx = jax.make_jaxpr(fn)(*args)
    total = 0

    def walk(jxr):
        nonlocal total
        for eqn in jxr.eqns:
            if eqn.primitive.name == "all_to_all":
                for v in eqn.invars:
                    total += int(v.aval.size) * v.aval.dtype.itemsize
            for v in eqn.params.values():
                for sub in _iter_subjaxprs(v):
                    walk(sub)

    walk(jx.jaxpr)
    return total


def traced_step_wire_bytes(model, mesh, topo, data, axis_name="parts",
                           train: bool = True) -> int:
    """`traced_wire_bytes` of a `PipeGCN.make_spmd_step` with fresh
    params/buffers — the per-device boundary bytes one training (or eval)
    step puts on the wire under the model's codec/slicing config."""
    step = model.make_spmd_step(mesh, topo, axis_name, train=train)
    params = model.init_params(jax.random.PRNGKey(0))
    buffers = model.init_buffers(topo)
    return traced_wire_bytes(step, topo, params, buffers, data,
                             jax.random.PRNGKey(0))
