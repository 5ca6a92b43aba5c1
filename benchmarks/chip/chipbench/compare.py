"""The comparison that decides `correct`: the program's first training
steps against the reference's, number by number, each against its limit.

  first_loss_gap  the relative gap between the first step's losses: the
              steady one (later steps' losses follow Adam's first,
              sign-like update, which rounding tips for the smallest
              gradient entries)
  loss_gap    the largest relative gap between the two losses over the
              recorded steps
  grad_gap    the first step's gradient, as the optimizer got it (worked out
              from Adam's first moment after one step, m = (1 - b1) g), by
              the worst leaf: | |g_prog| - |g_ref| | over the larger of the
              reference leaf's norm and the median leaf's norm
  update_gap  the same gap for each leaf's change over the recorded steps,
              leaving out the leaves whose reference gradient is under a
              thousandth of the median leaf's (they move under Adam by
              round-off alone)
  exchange_gap  the stale boundary buffers after the recorded steps (what
              the exchanges delivered, as the next step reads them), by the
              worst buffer: the gap of norms over the larger of that
              reference buffer's norm and the median buffer's
  placement   on a mesh, how many chips short of the mesh the least split of
              the step's topology, data and buffers is (limit 0)
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import B1

NUMBERS = ("first_loss_gap", "loss_gap", "grad_gap", "update_gap",
           "exchange_gap")
TINY_LEAF = 1e-3


def _norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in tree.items()}


def leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """Worst leaf's gap of norms, measured against the larger of that
    reference leaf's norm and the median reference leaf's (0 where both
    sides are all zero)."""
    rn = _norms(ref)
    pn = _norms(prog)
    med = float(np.median(list(rn.values())))
    worst, at = 0.0, ""
    for k in sorted(rn):
        if keep is not None and k not in keep:
            continue
        scale = max(rn[k], med)
        gap = abs(pn[k] - rn[k]) / scale if scale > 0 else float(pn[k] > 0)
        if gap > worst or not at:
            worst, at = gap, k
    return worst, at


def moving_leaves(ref_grads: dict) -> set:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's."""
    gn = _norms(ref_grads)
    med = float(np.median(list(gn.values())))
    return {k for k, v in gn.items() if v >= TINY_LEAF * med}


def readings(prog_losses, prog_grads_1: dict, prog_params_0: dict,
             prog_params_out: dict, prog_buffers: dict, ref) -> dict:
    """The compared numbers, from the program's recorded steps (or a
    stand-in's) and the reference's `Trajectory`. `prog_buffers` holds the
    norm of each stale buffer, named as `reference.buffer_norms` names
    them."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog_losses, ref.losses)]
    exchange_gap, exchange_at = leaf_gap(
        {k: np.asarray(prog_buffers[k]) for k in ref.buffers},
        {k: np.asarray(v) for k, v in ref.buffers.items()})
    grad_gap, grad_at = leaf_gap(prog_grads_1, ref.grads_1)
    delta = lambda p, q: {k: np.asarray(p[k], np.float64)  # noqa: E731
                          - np.asarray(q[k], np.float64) for k in p}
    update_gap, update_at = leaf_gap(
        delta(prog_params_out, prog_params_0),
        delta(ref.params_out, ref.params_0),
        keep=moving_leaves(ref.grads_1))
    return {"first_loss_gap": gaps[0], "loss_gap": max(gaps),
            "grad_gap": grad_gap, "update_gap": update_gap,
            "exchange_gap": exchange_gap,
            "_where": {"grad_gap": grad_at, "update_gap": update_at,
                       "exchange_gap": exchange_at}}


def grads_from_adam(opt_state_1) -> dict:
    """The first gradient from Adam's first moment after one step."""
    return {k: np.asarray(v, np.float64) / (1 - B1)
            for k, v in opt_state_1.mu.items()}


def verdict(read: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for every number compared. A
    number that is missing or not finite fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = read.get(name)
        good = (value is not None and np.isfinite(value)
                and value <= limit)
        ok = ok and bool(good)
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
