"""Multi-pod dry-run (assignment deliverable e).

For every (architecture x input-shape x mesh) cell:
  jax.jit(step, in_shardings=..., out_shardings=...)
      .lower(**input_specs(arch, shape))  ->  .compile()
then record memory_analysis() (fits 16 GB/chip), cost_analysis() FLOPs /
bytes, and the collective bytes parsed from the compiled HLO (with while-
loop trip-count attribution) — the inputs to EXPERIMENTS.md §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2_5_14b --shape train_4k \
      --mesh single --out results/
  python -m repro.launch.dryrun --all --mesh both   # every live cell
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede the jax import (jax locks the device count on first init).
#   512 host devices back both the 16x16 single-pod and the 2x16x16
#   multi-pod production meshes.  Set here (and only here): smoke tests and
#   benches see 1 device.

import argparse
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCH_IDS, SHAPES, cells, get_arch
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_spec, cache_specs, param_specs,
                                   sanitize, to_shardings)
from repro.lm.config import ArchConfig
from repro.lm.model import decode_step, init_cache, init_params
from repro.lm.steps import TrainState, make_train_step
from repro.train.optimizer import AdamW

HBM_PER_CHIP = 16 * 1024 ** 3          # v5e
PEAK_FLOPS = 197e12                     # bf16 / chip
HBM_BW = 819e9                          # bytes/s / chip
ICI_BW = 50e9                           # bytes/s/link (~per chip effective)


# --------------------------------------------------------------------------
# Shape-policy helpers
# --------------------------------------------------------------------------
def microbatches_for(cfg: ArchConfig, batch: int,
                     data_size: int = 16) -> int:
    if cfg.d_model >= 8192:
        mb = 16
    elif cfg.d_model >= 4096:
        mb = 8
    elif cfg.d_model >= 2048:
        mb = 4
    else:
        mb = 2
    mb = min(mb, max(1, batch // data_size))   # keep b/mb shardable
    while batch % mb:
        mb //= 2
    return max(1, mb)


def sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def input_specs(cfg: ArchConfig, shape_name: str, mesh):
    """ShapeDtypeStruct stand-ins for every model input of the cell —
    weak-type-correct, shardable, no device allocation."""
    seq, batch, kind = SHAPES[shape_name]
    bspec = batch_spec(mesh, 2)
    bshard = NamedSharding(mesh, sanitize((batch, seq), bspec, mesh))
    out = {}
    if kind == "train":
        out["tokens"] = sds((batch, seq), jnp.int32, bshard)
        out["labels"] = sds((batch, seq), jnp.int32, bshard)
    else:
        s_tok = seq if kind == "prefill" else 1
        tshard = NamedSharding(
            mesh, sanitize((batch, s_tok), batch_spec(mesh, 2), mesh))
        out["tokens"] = sds((batch, s_tok), jnp.int32, tshard)
    if cfg.mrope:
        s_tok = seq if kind in ("train", "prefill") else 1
        p3 = sanitize((batch, 3, s_tok), batch_spec(mesh, 3), mesh)
        out["positions3"] = sds((batch, 3, s_tok), jnp.int32,
                                NamedSharding(mesh, p3))
    if cfg.encoder_decoder and kind == "train":
        es = sanitize((batch, cfg.enc_positions, cfg.d_model),
                      batch_spec(mesh, 3), mesh)
        out["enc_input"] = sds((batch, cfg.enc_positions, cfg.d_model),
                               jnp.bfloat16, NamedSharding(mesh, es))
    if cfg.frontend == "vision" and kind == "train":
        n_patch = 256        # stub: 256 patch embeddings per sample
        es = sanitize((batch, n_patch, cfg.d_model),
                      batch_spec(mesh, 3), mesh)
        out["extra_embeds"] = sds((batch, n_patch, cfg.d_model),
                                  jnp.bfloat16, NamedSharding(mesh, es))
    return out, kind, seq, batch


def abstract_state(cfg: ArchConfig, mesh, dtype=jnp.bfloat16, policy=None):
    """TrainState ShapeDtypeStructs with shardings attached."""
    from repro.launch.sharding import DEFAULT_POLICY
    policy = policy or DEFAULT_POLICY
    opt = AdamW()
    def init(key):
        p = init_params(cfg, key, dtype)
        return TrainState(p, opt.init(p), jnp.zeros((), jnp.int32))
    shape_tree = jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    fallbacks: list = []
    pspecs = param_specs(shape_tree.params, mesh, fallbacks, policy)
    from repro.launch.sharding import zero1_specs
    from repro.train.optimizer import AdamWState
    ospecs = (zero1_specs(shape_tree.params, mesh)
              if getattr(policy, "zero1", False) else pspecs)
    state_specs = TrainState(
        pspecs, AdamWState(step=P(), m=ospecs, v=ospecs), P())
    shardings = to_shardings(state_specs, mesh)
    with_sh = jax.tree.map(
        lambda s, sh: sds(s.shape, s.dtype, sh), shape_tree, shardings)
    return with_sh, shardings, fallbacks


def abstract_cache(cfg: ArchConfig, batch, max_len, mesh,
                   dtype=jnp.bfloat16, kv_dtype=None):
    act_dtype = jnp.bfloat16
    kvd = dtype if kv_dtype is None else kv_dtype

    def init(_):
        memory = params = None
        if cfg.encoder_decoder:
            # cross-KV needs params + memory; approximate with eval_shape
            from repro.lm.model import init_params as ip
            params = ip(cfg, jax.random.PRNGKey(0), act_dtype)
            memory = jnp.zeros((batch, cfg.enc_positions, cfg.d_model),
                               act_dtype)
        return init_cache(cfg, batch, max_len, act_dtype, memory=memory,
                          params=params, kv_dtype=kvd)
    shape_tree = jax.eval_shape(init, 0)
    fallbacks: list = []
    cspecs = cache_specs(shape_tree, mesh, fallbacks)
    shardings = to_shardings(cspecs, mesh)
    with_sh = jax.tree.map(
        lambda s, sh: sds(s.shape, s.dtype, sh) if s is not None else None,
        shape_tree, shardings, is_leaf=lambda x: x is None)
    return with_sh, shardings, fallbacks


# --------------------------------------------------------------------------
# Analytic MODEL_FLOPS
# --------------------------------------------------------------------------
def model_flops(cfg: ArchConfig, kind: str, seq: int, batch: int) -> float:
    n = cfg.active_param_count()
    if kind == "train":
        tokens = batch * seq
        base = 6.0 * n * tokens
        attn = 0.0
        if cfg.block_type == "transformer":
            attn = 12.0 * cfg.n_layers * batch * seq * seq * cfg.q_dim
        return base + attn
    if kind == "prefill":
        tokens = batch * seq
        base = 2.0 * n * tokens
        attn = 0.0
        if cfg.block_type == "transformer":
            attn = 4.0 * cfg.n_layers * batch * seq * seq * cfg.q_dim
        return base + attn
    # decode: one token per sequence + KV/state read
    base = 2.0 * n * batch
    attn = 0.0
    if cfg.block_type == "transformer":
        attn = 4.0 * cfg.n_layers * batch * seq * cfg.q_dim
    return base + attn


# --------------------------------------------------------------------------
# Cell runner
# --------------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, policy=None,
             microbatches: int | None = None,
             kv_dtype=None) -> dict:
    """``policy`` / ``microbatches`` / ``kv_dtype`` are the §Perf hillclimb
    knobs; None selects the paper-baseline defaults."""
    cfg = get_arch(arch)
    from repro.lm import pshard
    pshard.set_dp_only(bool(policy and policy.dp_only))
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.shape.values())
    inputs, kind, seq, batch = input_specs(cfg, shape_name, mesh)
    fallbacks: list = []
    t0 = time.time()

    if kind == "train":
        state_sds, state_sh, fb = abstract_state(cfg, mesh, policy=policy)
        fallbacks += fb
        mb = microbatches or microbatches_for(
            cfg, batch, 32 if multi_pod else 16)
        opt = AdamW()

        def constrain_mb(tree):
            def c(x):
                from repro.launch.sharding import batch_axes
                full = (None, batch_axes(mesh)) + (None,) * (x.ndim - 2)
                spec = sanitize(x.shape, P(*full), mesh)
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, spec))
            return jax.tree.map(c, tree)

        step = make_train_step(
            cfg, opt, microbatches=mb, constrain_mb=constrain_mb,
            grad_dtype=(jnp.bfloat16 if policy is not None
                        and getattr(policy, "grads_bf16", False)
                        else None))
        batch_tree = inputs
        jitted = jax.jit(step, donate_argnums=(0,))
        with jax.set_mesh(mesh):        # ambient mesh for pshard hints
            lowered = jitted.lower(state_sds, batch_tree)
    else:
        max_len = seq if kind != "prefill" else seq
        cache_sds, cache_sh, fb = abstract_cache(
            cfg, batch, max_len, mesh, kv_dtype=kv_dtype)
        fallbacks += fb
        state_sds, state_sh, fb2 = abstract_state(cfg, mesh, policy=policy)
        fallbacks += fb2
        params_sds = state_sds.params

        if cfg.mrope:
            def step(params, token, cache, positions3):
                return decode_step(params, cfg, token, cache,
                                   positions3=positions3)
            args = (params_sds, inputs["tokens"], cache_sds,
                    inputs["positions3"])
        else:
            def step(params, token, cache):
                return decode_step(params, cfg, token, cache)
            args = (params_sds, inputs["tokens"], cache_sds)
        jitted = jax.jit(step, donate_argnums=(2,))
        with jax.set_mesh(mesh):        # ambient mesh for pshard hints
            lowered = jitted.lower(*args)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    cost_flops = float(cost.get("flops", 0.0))
    cost_bytes = float(cost.get("bytes accessed", 0.0))
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    stats = analyze_hlo(hlo)

    # Per-device quantities (post-SPMD HLO shapes are shards).  cost_*
    # counts while bodies once; our parser attributes trips for dot flops
    # and collectives.  Bytes get the first-order loop correction by the
    # flops ratio (same bodies dominate both) — recorded as an estimate.
    flops_dev = stats["flops_per_device"]
    loop_corr = (flops_dev / cost_flops) if cost_flops > 0 else 1.0
    bytes_dev = cost_bytes * max(1.0, loop_corr)
    coll_dev = stats["collective_bytes_per_device"]

    per_dev_bytes = None
    if mem is not None:
        try:
            per_dev_bytes = int(mem.temp_size_in_bytes
                                + mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                - mem.alias_size_in_bytes)
        except Exception:
            per_dev_bytes = None

    mf = model_flops(cfg, kind, seq, batch)
    t_comp = flops_dev / PEAK_FLOPS if flops_dev else None
    t_mem = bytes_dev / HBM_BW if bytes_dev else None
    t_coll = coll_dev / ICI_BW if coll_dev else 0.0

    result = {
        "arch": arch, "shape": shape_name, "kind": kind,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "chips": chips, "seq": seq, "batch": batch,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "hlo_flops_per_device": flops_dev,
        "hlo_flops_global": flops_dev * chips,
        "hlo_bytes_per_device": bytes_dev,
        "cost_analysis_flops": cost_flops,
        "cost_analysis_bytes": cost_bytes,
        "loop_correction": loop_corr,
        "collective_bytes_per_device": coll_dev,
        "collectives": stats["collective_bytes_by_op"],
        "collective_counts": stats["collective_counts"],
        "model_flops": mf,
        "useful_flops_ratio": (mf / (flops_dev * chips))
        if flops_dev else None,
        "per_device_bytes": per_dev_bytes,
        "fits_hbm": (per_dev_bytes is not None
                     and per_dev_bytes < HBM_PER_CHIP),
        "t_compute_s": t_comp, "t_memory_s": t_mem,
        "t_collective_s": t_coll,
        "sharding_fallbacks": sorted({f"{a}@{d}" for (s, a, d)
                                      in fallbacks})[:12],
        "ok": True,
    }
    if verbose:
        dom = max((k for k in ("t_compute_s", "t_memory_s",
                               "t_collective_s")
                   if result[k] is not None),
                  key=lambda k: result[k] or 0)
        print(f"[dryrun] {arch} {shape_name} {result['mesh']} "
              f"compile={t_compile:.0f}s flops/dev={flops_dev:.3e} "
              f"bytes/dev={bytes_dev:.3e} coll/dev={coll_dev:.3e} "
              f"dominant={dom} perdev_hbm={per_dev_bytes}")
        if mem is not None:
            print(f"  memory_analysis: {mem}")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    todo = []
    if args.all:
        todo = cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    for arch, shape in todo:
        for mp in meshes:
            tag = f"{arch}.{shape}.{'multi' if mp else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            try:
                res = run_cell(arch, shape, mp)
            except Exception as e:  # noqa: BLE001 - record and continue
                failures += 1
                res = {"arch": arch, "shape": shape,
                       "mesh": "multi" if mp else "single",
                       "ok": False, "error": f"{type(e).__name__}: {e}"}
                print(f"[dryrun] FAIL {tag}: {res['error']}",
                      file=sys.stderr)
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
