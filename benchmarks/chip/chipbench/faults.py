"""Faults planted in the program under a run, for the tests and the
calibration of the limits: never used by the benchmark's own runs.

Each fault is a pair (wrap_step, patches) for `job.train`: `wrap_step`
wraps the train step `train_pipegcn` builds, `patches` replace attributes
of the program for the length of the call.

  state_unchanged     the step hands back the parameters and optimizer
                      state it was given
  half_batch          half of the training nodes are left out of the loss,
                      which is then the mean over the rest
  exchange_left_out   the boundary exchange between partitions (between
                      chips on a mesh) delivers zeros
  gradient_altered    one leaf's gradient is doubled where the step
                      produces it
"""
from __future__ import annotations

FAULTS = ("state_unchanged", "half_batch", "exchange_left_out",
          "gradient_altered")


def _state_unchanged():
    def wrap(step):
        def call(topo, params, opt_state, buffers, data, key, *rest):
            out = step(topo, params, opt_state, buffers, data, key, *rest)
            return (out[0], params, opt_state) + tuple(out[3:])
        return call
    return wrap, ()


def _half_batch():
    import jax.numpy as jnp
    halved = {}

    def wrap(step):
        def call(topo, params, opt_state, buffers, data, key, *rest):
            if id(data) not in halved:
                m = data.train_mask
                keep = (jnp.cumsum(m.reshape(-1)) % 2 == 1).reshape(m.shape)
                halved[id(data)] = (data, data._replace(
                    train_mask=jnp.logical_and(m, keep)))
            return step(topo, params, opt_state, buffers,
                        halved[id(data)][1], key, *rest)
        return call
    return wrap, ()


def _exchange_left_out():
    import jax.numpy as jnp
    from repro.core.pipegcn import SimBackend, SpmdBackend
    zeros = lambda self, s: jnp.zeros_like(s)  # noqa: E731
    return None, ((SimBackend, "exchange", zeros),
                  (SpmdBackend, "exchange", zeros))


def _gradient_altered(leaf: str = "w0"):
    from repro.core.pipegcn import PipeGCN
    orig = PipeGCN._step_impl

    def step_impl(self, *args, **kwargs):
        loss, logits, grads, buffers = orig(self, *args, **kwargs)
        if grads is not None:
            grads = dict(grads, **{leaf: 2.0 * grads[leaf]})
        return loss, logits, grads, buffers

    return None, ((PipeGCN, "_step_impl", step_impl),)


def plant(name: str):
    """(wrap_step, patches) of the named fault."""
    table = {"state_unchanged": _state_unchanged,
             "half_batch": _half_batch,
             "exchange_left_out": _exchange_left_out,
             "gradient_altered": _gradient_altered}
    if name not in table:
        raise KeyError(f"unknown fault {name!r}; have {FAULTS}")
    return table[name]()
