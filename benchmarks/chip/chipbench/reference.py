"""The plain reference: full-graph GraphSAGE-mean or GCN training with
PipeGCN's stale boundary exchange, written on the whole graph in
`jax.numpy`, from the benchmark's own graph and seed.

It imports nothing of the program. It follows the semantics the program
states (PipeGCN, Wan et al. 2022, Alg. 1 and Eq. 3-4; the EMA smoothing of
its section 3.4 as `PipeConfig` documents it):

* Layer l aggregates, for each node v, sum_u P[v,u] x_u over its in-edges,
  where x_u is the layer input h_u when u lies in v's partition and the
  stale boundary state S_u otherwise (Eq. 3). SAGE adds the node's own
  input through a second weight block, [P.H ; H].W + b; GCN is P.H.W + b.
  ReLU follows every layer but the last; the loss is softmax cross-entropy
  (or sigmoid BCE, multilabel) averaged over the training nodes.
* The backward through a cross-partition edge reaches the owner one step
  late: node u's input gradient gets the stale state G_u in place of the
  fresh cross-partition sum C_u = sum_{v in other partitions} P[v,u] dz_v
  (Eq. 4). Written as autodiff: the objective is the loss plus
  sum_l <G^l, H^l> for l >= 1, with S and G held constant; its gradient in
  the parameters is the step's gradient, and its gradient in S^l is C^l.
* After the step, S^l <- g*S^l + (1-g)*H^l and G^l <- g*G^l + (1-g)*C^l
  with smoothing (g the decay), or plain replacement without; both start
  at zero. Vanilla training exchanges fresh values and has no state.
* Weights start as N(0, 1) * sqrt(2 / (fan_in + fan_out)), one key split
  per layer from PRNGKey(seed), biases at zero; Adam (b1 0.9, b2 0.999,
  eps 1e-8, bias-corrected).

The only thing it takes from a run of the program is the partition of the
nodes, the program's decision of how to divide the graph: it is checked to
be a partition, and every other structure (halo sets, propagation weights,
boundary state) is built here from the graph.

Aggregations run in chunks of edges so that the gather of one chunk, not of
the whole edge list, is what memory holds. In float32 every matmul runs at
`Precision.HIGHEST`; `dtype=bfloat16` computes the same steps in bfloat16
throughout, the control that a correct comparison has to fail.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
CHUNK = 1 << 18


@dataclasses.dataclass(frozen=True)
class Semantics:
    """The exchange semantics of a PipeGCN variant."""

    stale: bool
    smooth_feat: bool
    smooth_grad: bool
    gamma: float = 0.95

    @staticmethod
    def named(variant: str) -> "Semantics":
        table = {"vanilla": (False, False, False),
                 "pipegcn": (True, False, False),
                 "pipegcn-g": (True, False, True),
                 "pipegcn-f": (True, True, False),
                 "pipegcn-gf": (True, True, True)}
        if variant not in table:
            raise KeyError(f"unknown variant {variant!r}; have {sorted(table)}")
        return Semantics(*table[variant])


def check_partition(part, num_nodes: int, num_parts: int) -> np.ndarray:
    part = np.asarray(part)
    if part.shape != (num_nodes,):
        raise ValueError(f"partition has shape {part.shape}, want "
                         f"({num_nodes},)")
    if part.min() < 0 or part.max() >= num_parts:
        raise ValueError(f"partition ids outside [0, {num_parts})")
    if len(np.unique(part)) != num_parts:
        raise ValueError(f"partition leaves some of {num_parts} parts empty")
    return part.astype(np.int64)


def propagation(graph, kind: str):
    """(dst, src, weight) of the propagation matrix: D^-1 A for SAGE-mean,
    D~^-1/2 (A + I) D~^-1/2 for GCN."""
    dst, src = graph.coo()
    n = graph.num_nodes
    if kind == "sage":
        deg = np.maximum(np.bincount(dst, minlength=n), 1).astype(np.float64)
        return dst, src, (1.0 / deg[dst]).astype(np.float32)
    loop = np.arange(n, dtype=np.int64)
    dst, src = np.concatenate([dst, loop]), np.concatenate([src, loop])
    deg = np.bincount(dst, minlength=n).astype(np.float64)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    return dst, src, (dinv[dst] * dinv[src]).astype(np.float32)


def _chunks(dst, src, w, chunk: int):
    """Edges padded to whole chunks (weight 0 on the padding), as
    (rows, cols, weights) arrays of shape (n_chunks, chunk)."""
    e = len(dst)
    chunk = max(8, min(chunk, 1 << max(3, math.ceil(math.log2(max(e, 1))))))
    n = max(1, -(-e // chunk))
    pad = n * chunk - e
    z = lambda a, dt: np.concatenate([a, np.zeros(pad, a.dtype)]  # noqa: E731
                                     ).astype(dt).reshape(n, chunk)
    return z(dst, np.int32), z(src, np.int32), z(w, np.float32)


def _spmm(x, rows, cols, wts):
    """out[r] += w * x[c] over every (r, c, w) edge, chunk by chunk."""
    import jax
    import jax.numpy as jnp

    def body(acc, blk):
        r, c, ww = blk
        return acc.at[r].add(ww.astype(x.dtype)[:, None] * x[c]), None

    out, _ = jax.lax.scan(body, jnp.zeros_like(x), (rows, cols, wts))
    return out


def _make_aggregate():
    import jax

    @jax.custom_vjp
    def aggregate(x, rows, cols, wts):
        return _spmm(x, rows, cols, wts)

    def fwd(x, rows, cols, wts):
        return _spmm(x, rows, cols, wts), (rows, cols, wts)

    def bwd(res, g):
        rows, cols, wts = res
        return _spmm(g, cols, rows, wts), None, None, None

    aggregate.defvjp(fwd, bwd)
    return aggregate


@dataclasses.dataclass
class Problem:
    """One training job on the whole graph, as device arrays."""

    x: object
    labels: object
    train_mask: object
    inner: tuple      # (rows, cols, weights) of same-partition edges
    cross: tuple      # ... of cross-partition edges
    consumers: object  # (N,) how many other partitions read each node
    dims: list
    kind: str
    multilabel: bool
    num_classes: int
    lr: float
    sem: Semantics
    dtype: object


def problem(graph, config: dict, variant: str, part, num_parts: int,
            dtype=None, chunk: int = CHUNK) -> Problem:
    import jax.numpy as jnp
    dtype = jnp.float32 if dtype is None else dtype
    part = check_partition(part, graph.num_nodes, num_parts)
    dst, src, w = propagation(graph, config["kind"])
    cross = part[dst] != part[src]
    readers = np.unique(src[cross] * num_parts + part[dst[cross]])
    consumers = np.bincount(readers // num_parts, minlength=graph.num_nodes)
    L = int(config["num_layers"])
    widths = ([int(config["feat_dim"])] + [int(config["hidden"])] * (L - 1)
              + [int(config["num_classes"])])
    put = lambda t: tuple(jnp.asarray(a) for a in t)  # noqa: E731
    return Problem(
        x=jnp.asarray(graph.features, dtype),
        labels=jnp.asarray(graph.labels),
        train_mask=jnp.asarray(graph.train_mask),
        inner=put(_chunks(dst[~cross], src[~cross], w[~cross], chunk)),
        cross=put(_chunks(dst[cross], src[cross], w[cross], chunk)),
        consumers=jnp.asarray(consumers, jnp.float32),
        dims=[(widths[i], widths[i + 1]) for i in range(L)],
        kind=config["kind"], multilabel=bool(config["multilabel"]),
        num_classes=int(config["num_classes"]), lr=float(config["lr"]),
        sem=Semantics.named(variant), dtype=dtype)


def init_params(seed: int, dims, kind: str, dtype) -> dict:
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(seed)
    params = {}
    for ell, (fin, fout) in enumerate(dims):
        fan_in = 2 * fin if kind == "sage" else fin
        key, sub = jax.random.split(key)
        scale = jnp.sqrt(2.0 / (fan_in + fout)).astype(jnp.float32)
        params[f"w{ell}"] = (jax.random.normal(sub, (fan_in, fout),
                                               jnp.float32) * scale
                             ).astype(dtype)
        params[f"b{ell}"] = jnp.zeros((fout,), dtype)
    return params


def _loss(logits, labels, mask, multilabel: bool):
    import jax
    import jax.numpy as jnp
    m = mask.astype(logits.dtype)
    if multilabel:
        z, y = logits, labels.astype(logits.dtype)
        per = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        return jnp.sum(per * m[:, None]) / (jnp.sum(m) * logits.shape[-1])
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                             axis=-1)[:, 0]
    return jnp.sum((lse - ll) * m) / jnp.sum(m)


def _objective(params, stale_feat, stale_grad, prob: Problem, aggregate,
               precision):
    """(loss + sum_l <G^l, H^l>, (loss, layer inputs H^l))."""
    import jax
    import jax.numpy as jnp
    mm = partial(jnp.matmul, precision=precision)
    h, inputs, extra = prob.x, [], 0.0
    L = len(prob.dims)
    for ell, (fin, _) in enumerate(prob.dims):
        inputs.append(h)
        if prob.sem.stale:
            z = (aggregate(h, *prob.inner)
                 + aggregate(stale_feat[ell], *prob.cross))
            if ell > 0:
                extra = extra + jnp.sum(stale_grad[ell] * h)
        else:
            z = aggregate(h, *prob.inner) + aggregate(h, *prob.cross)
        w, b = params[f"w{ell}"], params[f"b{ell}"]
        if prob.kind == "sage":
            u = mm(z, w[:fin]) + mm(h, w[fin:]) + b
        else:
            u = mm(z, w) + b
        h = jax.nn.relu(u) if ell < L - 1 else u
    loss = _loss(h, prob.labels, prob.train_mask, prob.multilabel)
    return loss + extra, (loss, inputs)


def _adam(params, grads, m, v, t, lr):
    import jax
    import jax.numpy as jnp
    m = jax.tree.map(lambda m_, g: B1 * m_ + (1 - B1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: B2 * v_ + (1 - B2) * g * g, v, grads)
    c1 = 1 - B1 ** t
    c2 = 1 - B2 ** t
    params = jax.tree.map(
        lambda p, m_, v_: (p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + EPS)
                           ).astype(p.dtype), params, m, v)
    return params, m, v


@dataclasses.dataclass
class Trajectory:
    """What the reference's first steps give, on the host."""

    losses: list
    grads_1: dict       # the first step's gradient
    params_0: dict
    params_out: dict    # parameters after the last step
    buffers: dict       # norm of each stale buffer after the last step


def buffer_norms(prob: Problem, feat, grad) -> dict:
    """The norm each stale buffer of the program holds, from the global
    state: a feature buffer keeps node u's row once for every other
    partition that reads it; a gradient buffer keeps the summed
    contribution at its owner. Layer 0 ships no gradient."""
    import jax.numpy as jnp
    out = {}
    for ell, (s, g) in enumerate(zip(feat, grad)):
        s = s.astype(jnp.float32)
        out[f"feat{ell}"] = float(jnp.sqrt(jnp.sum(
            prob.consumers[:, None] * s * s)))
        if ell > 0:
            g = g.astype(jnp.float32)
            out[f"grad{ell}"] = float(jnp.sqrt(jnp.sum(g * g)))
    return out


def step_fn(prob: Problem):
    """One training step of `prob`, to be jitted: (params, m, v, t, feat,
    grad, arrays) -> (loss, gradient, params, m, v, feat, grad), where
    `arrays` holds the problem's device arrays (passed as arguments, not
    closed over, so that they are not compiled in as constants)."""
    import jax
    import jax.numpy as jnp
    precision = (jax.lax.Precision.HIGHEST if prob.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    aggregate = _make_aggregate()
    sem = prob.sem

    def step(params, m, v, t, feat, grad, arrays):
        p = dataclasses.replace(prob, **arrays)
        (_, (loss, inputs)), (g_params, g_feat) = jax.value_and_grad(
            _objective, argnums=(0, 1), has_aux=True)(
                params, feat, grad, p, aggregate, precision)
        g_params = jax.tree.map(lambda g, q: g.astype(q.dtype), g_params,
                                params)
        new_params, m, v = _adam(params, g_params, m, v, t, prob.lr)
        if sem.stale:
            mix_f = sem.gamma if sem.smooth_feat else 0.0
            mix_g = sem.gamma if sem.smooth_grad else 0.0
            feat = [(mix_f * s + (1 - mix_f) * hh).astype(s.dtype)
                    for s, hh in zip(feat, inputs)]
            grad = [(mix_g * s + (1 - mix_g) * c).astype(s.dtype)
                    for s, c in zip(grad, g_feat)]
        return loss, g_params, new_params, m, v, feat, grad

    return step


def arrays_of(prob: Problem) -> dict:
    return dict(x=prob.x, labels=prob.labels, train_mask=prob.train_mask,
                inner=prob.inner, cross=prob.cross, consumers=prob.consumers)


def run(prob: Problem, seed: int, steps: int) -> Trajectory:
    """The first `steps` training steps from the seed."""
    import jax
    import jax.numpy as jnp
    step = jax.jit(step_fn(prob))
    arrays = arrays_of(prob)
    params = init_params(seed, prob.dims, prob.kind, prob.dtype)
    params_0 = jax.tree.map(np.asarray, params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    n = prob.x.shape[0]
    feat = [jnp.zeros((n, fin), prob.dtype) for fin, _ in prob.dims]
    grad = [jnp.zeros((n, fin), prob.dtype) for fin, _ in prob.dims]
    losses, grads_1 = [], None
    for t in range(1, steps + 1):
        loss, g, params, m, v, feat, grad = step(
            params, m, v, jnp.asarray(t, jnp.float32), feat, grad, arrays)
        losses.append(float(loss))
        if t == 1:
            grads_1 = jax.tree.map(lambda a: np.asarray(a, np.float32), g)
    return Trajectory(losses=losses, grads_1=grads_1, params_0=params_0,
                      params_out=jax.tree.map(
                          lambda a: np.asarray(a, np.float32), params),
                      buffers=buffer_norms(prob, feat, grad))
