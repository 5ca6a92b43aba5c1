"""Multi-pod dry-run: lower + compile every (arch × input-shape) on the
production meshes, recording memory analysis, HLO cost analysis, and the
collective-traffic breakdown parsed from the partitioned HLO.

The XLA_FLAGS assignment below MUST run before any jax import (device count
locks on first init); this module is the only place that forces 512 host
devices — do not import it from tests or benchmarks.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.json
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_arch
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.launch.specs import (abstract_caches, abstract_params, batch_axes,
                                input_specs)
from repro.analysis.cost import analytic_cost
from repro.models.config import INPUT_SHAPES
from repro.models.model import LM
from repro.optim import adam

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _tuple_bytes(type_str: str) -> int:
    """Sum byte sizes of all array types in an HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str, while_mult: int = 1) -> dict[str, int]:
    """Per-collective-type payload bytes (per device) from partitioned HLO.

    XLA counts a `while` (lax.scan) body once; collectives whose op_name
    metadata places them inside a loop body are multiplied by `while_mult`
    (= the layer-scan trip count of the model being analyzed).
    """
    out = {k: 0 for k in COLLECTIVE_OPS}
    out["f32_activation_bytes"] = 0   # candidates for bf16 on real TPU wire
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT )?[%\w.\-]+ = (.+?) (all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)"
                     r"(?:-start)?\(", line)
        if m:
            mult = while_mult if "/while/" in line else 1
            nbytes = _tuple_bytes(m.group(1)) * mult
            out[m.group(2)] += nbytes
            # The CPU backend promotes bf16 dots/collectives to f32; in-loop
            # activation collectives (dot partial sums, boundary payloads)
            # would travel as bf16 on TPU. Track them for the corrected term.
            if "/while/" in line and "f32[" in m.group(1):
                out["f32_activation_bytes"] += nbytes
    return out


def _fsdp_params(lm: LM, mesh):
    """ZeRO-3/FSDP layout: every weight sharded over ALL mesh axes on its
    first dimension divisible by the chip count (replicated otherwise).
    XLA then all-gathers each layer's weights at use and reduce-scatters
    grads — replacing tensor-parallel activation all-reduces."""
    chips = int(np.prod(list(mesh.shape.values())))
    flat = tuple(mesh.axis_names)
    sds = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0)))

    def spec_of(leaf):
        for dim, size in enumerate(leaf.shape):
            if size % chips == 0:
                entries = [None] * len(leaf.shape)
                entries[dim] = flat
                return NamedSharding(mesh, P(*entries))
        return NamedSharding(mesh, P())
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=spec_of(leaf)), sds)


def _build_step(lm: LM, shape, mesh, fsdp: bool = False):
    """Returns (fn, example_args) for the mode of this input shape."""
    cfg = lm.cfg
    params = _fsdp_params(lm, mesh) if fsdp else abstract_params(lm, mesh)
    batch = input_specs(cfg, shape, mesh)
    if fsdp:
        flat = tuple(mesh.axis_names)
        batch = jax.tree.map(
            lambda sds: jax.ShapeDtypeStruct(
                sds.shape, sds.dtype,
                sharding=NamedSharding(
                    mesh, P(*([flat] + [None] * (len(sds.shape) - 1))))),
            batch)

    if shape.mode == "train":
        opt = adam(1e-4)
        opt_state = jax.eval_shape(opt.init, params)
        opt_state = jax.tree.map(
            lambda s, p: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=(p.sharding if s.shape == p.shape
                          else NamedSharding(mesh, P()))),
            opt_state, type(opt_state)(step=jax.ShapeDtypeStruct((), jnp.int32),
                                       mu=params, nu=params))

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(lm.loss_fn)(params, batch)
            new_params, new_state = opt.apply(params, grads, opt_state)
            return loss, new_params, new_state

        return train_step, (params, opt_state, batch)

    caches = abstract_caches(lm, shape, mesh)
    if shape.mode == "prefill":
        def prefill_step(params, batch, caches):
            return lm.prefill(params, batch, caches)
        return prefill_step, (params, batch, caches)

    pos = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=NamedSharding(mesh, P()))

    def serve_step(params, token, caches, pos):
        return lm.decode_step(params, token, caches, pos)

    return serve_step, (params, batch["tokens"], caches, pos)


def variant_for(cfg, shape_name: str):
    """long_500k needs sub-quadratic attention: archs without a native
    sub-quadratic mixer run an explicit sliding-window decode variant
    (window 4096) — recorded as a variant in DESIGN.md §Arch-applicability."""
    if (shape_name == "long_500k" and cfg.sliding_window == 0
            and cfg.family != "ssm"):
        import dataclasses
        return dataclasses.replace(cfg, sliding_window=4096), "sw4096"
    return cfg, None


def opt_sharding_rules(mesh):
    """§Perf optimized activation sharding (Megatron-style residual +
    vocab-sharded logits); None entries fall back to GSPMD propagation."""
    from repro.launch.specs import batch_axes
    bx = batch_axes(mesh)
    return {
        "residual": NamedSharding(mesh, P(bx, None, None)),
        "logits": NamedSharding(mesh, P(bx, None, "model")),
        "moe_expert": NamedSharding(mesh, P("model", None, None)),
        # grouped routing: token groups track the data shards
        "moe_tokens": NamedSharding(mesh, P(bx, None, None)),
        "moe_gathered": NamedSharding(mesh, P(bx, "model", None, None)),
    }


def dryrun_one(arch_id: str, shape_name: str, multi_pod: bool = False,
               lower_only: bool = False, opt_sharding: bool = False,
               fsdp: bool = False) -> dict:
    from repro.models.shardctx import sharding_rules
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = INPUT_SHAPES[shape_name]
    cfg, variant = variant_for(get_arch(arch_id), shape_name)
    lm = LM(cfg)
    chips = int(np.prod(list(mesh.shape.values())))

    rules = opt_sharding_rules(mesh) if opt_sharding else None
    if fsdp:
        flat = tuple(mesh.axis_names)
        rules = {"residual": NamedSharding(mesh, P(flat, None, None)),
                 "logits": NamedSharding(mesh, P(flat, None, None))}
    if opt_sharding and cfg.num_experts:
        import dataclasses
        data_shards = mesh.shape["data"] * mesh.shape.get("pod", 1)
        cfg = dataclasses.replace(cfg, moe_groups=data_shards)
        lm = LM(cfg)
    t0 = time.perf_counter()
    with sharding_rules(rules):
        fn, args = _build_step(lm, shape, mesh, fsdp=fsdp)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn).lower(*args)
            t_lower = time.perf_counter() - t0
            result = {
                "arch": arch_id, "shape": shape_name, "mode": shape.mode,
                "variant": variant, "opt_sharding": opt_sharding,
                "fsdp": fsdp,
                "mesh": "x".join(str(s) for s in mesh.shape.values()),
                "chips": chips, "lower_s": round(t_lower, 1),
            }
            if lower_only:
                return result
            t1 = time.perf_counter()
            compiled = lowered.compile()
            result["compile_s"] = round(time.perf_counter() - t1, 1)

    mem = compiled.memory_analysis()
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                result[k] = int(v)
        result["bytes_per_device"] = int(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0))

    # HLO cost analysis (recorded verbatim; NOTE: while/scan bodies counted
    # once — see EXPERIMENTS.md §Dry-run. Roofline compute/memory terms use
    # the analytic model below instead).
    cost = compiled.cost_analysis()
    if cost:
        result["hlo_flops_body_once"] = float(cost.get("flops", 0.0))
        result["hlo_bytes_body_once"] = float(cost.get("bytes accessed", 0.0))

    # layer-scan trip count for while-body collective correction
    scan_ns = [n for _, n in lm.groups if n > 1]
    if cfg.is_encdec:
        scan_ns += [n for _, n in lm.encoder_groups if n > 1]
    while_mult = max(scan_ns) if scan_ns else 1
    result["while_mult"] = while_mult
    coll = collective_bytes(compiled.as_text(), while_mult)
    f32_act = coll.pop("f32_activation_bytes")
    result["collective_bytes_per_device"] = coll
    result["collective_total_bytes"] = int(sum(coll.values()))
    # TPU wire-dtype correction: bf16 activations promoted to f32 by the CPU
    # backend travel at half the measured bytes on real hardware.
    result["collective_bytes_tpu_wire"] = int(
        result["collective_total_bytes"] - f32_act // 2)

    # analytic FLOPs / HBM bytes (global -> per device)
    ac = analytic_cost(cfg, shape)
    flops = ac["flops_global"] / chips
    bytes_hbm = ac["hbm_bytes_global"] / chips
    result["flops_per_device"] = flops
    result["hbm_bytes_per_device"] = bytes_hbm
    result["params_total"] = ac["params_total"]

    bytes_coll = result["collective_total_bytes"]
    result["t_compute"] = flops / PEAK_FLOPS_BF16
    result["t_memory"] = bytes_hbm / HBM_BW
    result["t_collective"] = bytes_coll / ICI_BW
    result["t_collective_tpu_wire"] = (
        result["collective_bytes_tpu_wire"] / ICI_BW)
    terms = {"compute": result["t_compute"], "memory": result["t_memory"],
             "collective": result["t_collective"]}
    result["bottleneck"] = max(terms, key=terms.get)

    # MODEL_FLOPS (6·N_active·D for train, 2·N_active per token for serve)
    n_active = _active_params(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
    mult = 6 if shape.mode == "train" else 2
    model_flops = mult * n_active * tokens
    result["model_flops_total"] = float(model_flops)
    result["model_flops_ratio"] = (
        float(model_flops / ac["flops_global"]) if ac["flops_global"] else 0.0)
    return result


def _active_params(cfg) -> int:
    """Parameter count active per token (MoE counts top-k+shared experts)."""
    lm = LM(cfg)
    sds = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0)))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(sds)[0]:
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        n = int(np.prod(leaf.shape))
        if cfg.num_experts and any(k in ("wi", "wg", "wo") for k in keys) \
                and len(leaf.shape) >= 3 and leaf.shape[-3] == cfg.num_experts:
            n = n * cfg.experts_per_tok // cfg.num_experts
        total += n
    return total


def skip_reason(arch_id: str, shape_name: str) -> str | None:
    """Combos skipped by design (documented in DESIGN.md §Arch-applicability)."""
    return None   # all 40 combos lower: dense archs use the sliding-window
                  # decode variant for long_500k (see DESIGN.md)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--opt-sharding", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    combos = []
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                combos.append((a, s, args.multi_pod))
    else:
        combos.append((args.arch, args.shape, args.multi_pod))

    results = []
    for arch, shape, mp in combos:
        tag = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
        try:
            r = dryrun_one(arch, shape, multi_pod=mp,
                           lower_only=args.lower_only,
                           opt_sharding=args.opt_sharding, fsdp=args.fsdp)
            results.append(r)
            print(f"[dryrun OK ] {tag}: lower={r.get('lower_s')}s "
                  f"compile={r.get('compile_s')}s "
                  f"bottleneck={r.get('bottleneck')}", flush=True)
        except Exception as e:
            results.append({"arch": arch, "shape": shape,
                            "multi_pod": mp, "error": str(e)[:2000]})
            print(f"[dryrun ERR] {tag}: {type(e).__name__}: {str(e)[:300]}",
                  flush=True)
            traceback.print_exc()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
