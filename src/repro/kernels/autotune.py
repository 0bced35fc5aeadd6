"""Block-shape autotuner for the Pallas kernels (DESIGN.md §4).

Ahn-style near-optimal tile geometry is shape-dependent: the best
(block_m, block_n, block_k) / channel-block / row-block for a 112x112x32
depthwise layer is not the best for a 7x7x1024 pointwise layer.  Rather than
bake one heuristic into every wrapper, each op consults this module with its
*layer signature*; the tuner benchmarks a small candidate set once per
signature, caches the winner in a JSON file, and every later call (same
process or a fresh one) gets the cached config with zero benchmark cost.

Cache format (``autotune_cache.json``)::

    {
      "version": 1,
      "entries": {
        "conv/h14.w14.ci32.co64.k3x3.s1.p1/f32": {
          "config": {"block_h": 9, "block_n": 64},
          "us": 1234.5,
          "backend": "cpu"
        },
        ...
      }
    }

Keys are ``kind/signature/dtype``; ``us`` is the winning median wall-clock in
microseconds on the machine that tuned.  The cache path defaults to
``results/autotune_cache.json`` (cwd-relative, matching the benchmarks'
results/ convention) and can be redirected with the
``REPRO_AUTOTUNE_CACHE`` env var (tests and CI point it at a temp file).

The lookup path (``get_config``) is pure python — cheap enough to run at
trace time inside the jit'd wrappers.  The benchmark path (``tune`` /
``tune_layer``) executes kernels eagerly and must only be called outside jit
(benchmarks/kernel_specs.py --smoke, tests, or an explicit warm-up).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
from typing import Any, Callable

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
CACHE_VERSION = 1

_DTYPE_TAGS = {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}

# in-memory mirror of the JSON files, keyed by resolved path
_MEM: dict[str, dict[str, Any]] = {}

# when not None, get_config appends every signature it is asked for —
# how the --sweep-zoo entry discovers exactly the signatures the op
# wrappers consult (see record_signatures / zoo_signatures)
_RECORDING: list["LayerSig"] | None = None


@dataclasses.dataclass(frozen=True)
class LayerSig:
    """Kernel-shape signature — the autotune cache key (DESIGN.md §4)."""

    kind: str                    # 'conv' | 'pointwise' | 'depthwise' |
                                 # 'fused_dw_pw' | 'fused_pw_dw_pw'
    H: int
    W: int
    C_i: int
    C_o: int
    K_h: int = 1
    K_w: int = 1
    stride: int = 1
    pad: int = 0
    dtype: str = "float32"

    def key(self) -> str:
        tag = _DTYPE_TAGS.get(self.dtype, self.dtype)
        return (f"{self.kind}/h{self.H}.w{self.W}.ci{self.C_i}.co{self.C_o}"
                f".k{self.K_h}x{self.K_w}.s{self.stride}.p{self.pad}/{tag}")


def cache_path(path: str | None = None) -> str:
    if path:
        return path
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    # repo-relative (matches the results/ convention of benchmarks/run.py)
    return os.path.join("results", "autotune_cache.json")


def load_cache(path: str | None = None) -> dict[str, Any]:
    p = cache_path(path)
    if p in _MEM:
        return _MEM[p]
    data: dict[str, Any] = {"version": CACHE_VERSION, "entries": {}}
    try:
        with open(p) as f:
            raw = json.load(f)
        if raw.get("version") == CACHE_VERSION:
            data = raw
    except (OSError, ValueError):
        pass
    _MEM[p] = data
    return data


def save_cache(data: dict[str, Any], path: str | None = None) -> None:
    p = cache_path(path)
    os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
    tmp = p + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
    os.replace(tmp, p)
    _MEM[p] = data


def clear_memory_cache() -> None:
    """Drop the in-process mirror (tests use this to force a re-read)."""
    _MEM.clear()


# --------------------------------------------------------------------------
# lookup path (trace-time cheap)
# --------------------------------------------------------------------------
def get_config(sig: LayerSig, path: str | None = None) -> dict | None:
    """Cached winning config for ``sig``, or None on a miss.

    Entries tuned on a different backend are treated as misses: block
    shapes ranked by CPU interpret-mode wall-clock say nothing about MXU
    performance (and vice versa), so a TPU run must not inherit a cache
    populated by CPU CI.
    """
    if _RECORDING is not None:
        _RECORDING.append(sig)
    entry = load_cache(path)["entries"].get(sig.key())
    if not entry:
        return None
    import jax
    if entry.get("backend") != jax.default_backend():
        return None
    return dict(entry["config"])


def heuristic_config(sig: LayerSig) -> dict:
    """Default block shapes used on a cache miss.  Channel blocks are
    lane-aligned (``lane_tile``: the full dim or a multiple of 128); the
    kernels size their output-row tiles to the VMEM budget themselves, so
    the full channel count is the default wherever channels are blocked."""
    from repro.kernels.util import lane_tile
    if sig.kind == "conv":
        wo = max(1, (sig.W + 2 * sig.pad - sig.K_w) // sig.stride + 1)
        ho = max(1, (sig.H + 2 * sig.pad - sig.K_h) // sig.stride + 1)
        return {"block_h": max(1, min(ho, -(-256 // wo))),
                "block_n": lane_tile(128, sig.C_o)}
    if sig.kind == "pointwise":
        return {"block": (128, 128, 128)}
    if sig.kind == "depthwise":
        return {"block_c": sig.C_i}
    if sig.kind in ("fused_dw_pw", "fused_pw_dw_pw"):
        return {"block_c": sig.C_i, "block_n": lane_tile(128, sig.C_o)}
    raise ValueError(f"unknown kernel kind {sig.kind!r}")


def candidates(sig: LayerSig) -> list[dict]:
    """Small per-kind candidate sets (kept tiny: interpret mode is slow).
    Every channel block is lane-aligned, so each candidate is one the TPU
    compiler accepts: a failing candidate is an error, not a skip."""
    from repro.kernels.util import lane_tile
    out: list[dict] = [heuristic_config(sig)]
    if sig.kind == "conv":
        ho = max(1, (sig.H + 2 * sig.pad - sig.K_h) // sig.stride + 1)
        for bh in (1, 4, 8, 16):
            for bn in (128, 256):
                out.append({"block_h": min(bh, ho),
                            "block_n": lane_tile(bn, sig.C_o)})
    elif sig.kind == "pointwise":
        for b in ((128, 128, 128), (256, 128, 128), (256, 256, 256)):
            out.append({"block": b})
    elif sig.kind == "depthwise":
        for bc in (128, 256):
            out.append({"block_c": lane_tile(bc, sig.C_i)})
    else:
        for bc in (128, sig.C_i):
            for bn in (128, 256):
                out.append({"block_c": lane_tile(bc, sig.C_i),
                            "block_n": lane_tile(bn, sig.C_o)})
    # dedupe, preserving order
    seen: set[str] = set()
    uniq = []
    for c in out:
        k = json.dumps(c, sort_keys=True)
        if k not in seen:
            seen.add(k)
            uniq.append(c)
    return uniq


# --------------------------------------------------------------------------
# benchmark path (eager only)
# --------------------------------------------------------------------------
def _time_us(fn: Callable[[], Any], reps: int = 3) -> float:
    from repro.kernels.util import bench_best_us
    return bench_best_us(fn, reps=reps)


def tune(sig: LayerSig, run: Callable[[dict], Callable[[], Any]], *,
         path: str | None = None, reps: int = 3,
         force: bool = False) -> dict:
    """Benchmark ``candidates(sig)`` and cache the winner.

    ``run(config)`` returns a zero-arg callable executing the kernel with
    that config.  A cached entry short-circuits the benchmark (deterministic
    round-trips) unless ``force``.
    """
    if not force:
        hit = get_config(sig, path)
        if hit is not None:
            return hit
    import jax
    best_cfg, best_us = None, float("inf")
    for cfg in candidates(sig):
        us = _time_us(run(cfg), reps=reps)
        if us < best_us:
            best_cfg, best_us = cfg, us
    data = load_cache(path)
    data["entries"][sig.key()] = {"config": best_cfg,
                                  "us": round(best_us, 1),
                                  "backend": jax.default_backend()}
    save_cache(data, path)
    return dict(best_cfg)


def tune_layer(sig: LayerSig, *, path: str | None = None, reps: int = 3,
               force: bool = False) -> dict:
    """Tune one layer signature end-to-end: builds dummy operands of the
    signature's shape and benchmarks the matching op wrapper."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(sig.dtype)
    key = jax.random.PRNGKey(0)
    kx, kw, kw2, kw3 = jax.random.split(key, 4)
    x = (jax.random.normal(kx, (1, sig.H, sig.W, sig.C_i)) * 0.3
         ).astype(dtype)

    if sig.kind == "conv":
        from repro.kernels.conv_gemm.kernel import conv2d_implicit_gemm
        w = (jax.random.normal(kw, (sig.K_h, sig.K_w, sig.C_i, sig.C_o))
             * 0.2).astype(dtype)

        def run(cfg):
            return lambda: conv2d_implicit_gemm(
                x, w, stride=sig.stride, pad=sig.pad, **cfg)
    elif sig.kind == "pointwise":
        from repro.kernels.conv_gemm.kernel import matmul_bias_act
        xm = x.reshape(sig.H * sig.W, sig.C_i)
        w = (jax.random.normal(kw, (sig.C_i, sig.C_o)) * 0.2).astype(dtype)

        def run(cfg):
            block = tuple(cfg["block"])
            return lambda: matmul_bias_act(xm, w, block=block)
    elif sig.kind == "depthwise":
        from repro.kernels.depthwise.kernel import depthwise_conv2d
        w = (jax.random.normal(kw, (sig.K_h, sig.K_w, sig.C_i))
             * 0.3).astype(dtype)

        def run(cfg):
            return lambda: depthwise_conv2d(
                x, w, stride=sig.stride, pad=sig.pad, **cfg)
    elif sig.kind == "fused_dw_pw":
        from repro.kernels.fused_block.kernel import fused_dw_pw_conv
        dw_w = (jax.random.normal(kw, (sig.K_h, sig.K_w, sig.C_i))
                * 0.3).astype(dtype)
        pw_w = (jax.random.normal(kw2, (sig.C_i, sig.C_o)) * 0.2
                ).astype(dtype)

        def run(cfg):
            return lambda: fused_dw_pw_conv(
                x, dw_w, None, pw_w, None, stride=sig.stride, pad=sig.pad,
                **cfg)
    elif sig.kind == "fused_pw_dw_pw":
        # C_i in the signature is C_mid (the dw channel count, what the
        # block_c knob tiles); expand input is fixed at C_mid // 6 (the
        # common t=6 expansion) purely to exercise the expand GEMM.
        from repro.kernels.fused_block.kernel import fused_pw_dw_pw_conv
        cm = sig.C_i
        ci = max(8, cm // 6)
        x = (jax.random.normal(kx, (1, sig.H, sig.W, ci)) * 0.3
             ).astype(dtype)
        exp_w = (jax.random.normal(kw, (ci, cm)) * 0.2).astype(dtype)
        dw_w = (jax.random.normal(kw2, (sig.K_h, sig.K_w, cm))
                * 0.3).astype(dtype)
        proj_w = (jax.random.normal(kw3, (cm, sig.C_o)) * 0.2).astype(dtype)

        def run(cfg):
            return lambda: fused_pw_dw_pw_conv(
                x, exp_w, None, dw_w, None, proj_w, None,
                stride=sig.stride, pad=sig.pad, **cfg)
    else:
        raise ValueError(f"tune_layer: unsupported kind {sig.kind!r}")
    return tune(sig, run, path=path, reps=reps, force=force)


# --------------------------------------------------------------------------
# zoo sweep (python -m repro.kernels.autotune --sweep-zoo)
# --------------------------------------------------------------------------
ZOO_MODELS = ("mobilenet_v1", "mobilenet_v2", "squeezenet")


@contextlib.contextmanager
def record_signatures():
    """Collect every LayerSig the op wrappers consult inside the block."""
    global _RECORDING
    prev, _RECORDING = _RECORDING, []
    try:
        yield _RECORDING
    finally:
        _RECORDING = prev


def zoo_signatures(image_size: int = 224,
                   models: tuple[str, ...] = ZOO_MODELS) -> list[LayerSig]:
    """Every layer signature the zoo forwards consult at ``image_size`` —
    per-layer and fused-block paths both — discovered by abstractly
    evaluating the real step programs with signature recording on, so the
    sweep can never drift from what the op wrappers actually ask for."""
    import jax
    import jax.numpy as jnp

    from repro.dualcore.program import build_program
    from repro.models.cnn import init_params
    from repro.models.zoo import get_graph

    sigs: list[LayerSig] = []
    seen: set[str] = set()
    x = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    for name in models:
        params = init_params(get_graph(name), jax.random.PRNGKey(0))
        for fuse in (False, True):
            prog = build_program(name, use_pallas=True, fuse=fuse)
            with record_signatures() as rec:
                jax.eval_shape(
                    lambda p, xx, prog=prog: prog.run(p, xx), params, x)
            for s in rec:
                if s.key() not in seen:
                    seen.add(s.key())
                    sigs.append(s)
    return sigs


def sweep_zoo(image_size: int = 224, *, reps: int = 3, limit: int = 0,
              force: bool = False, path: str | None = None) -> dict:
    """Warm the autotune cache over all zoo layer signatures (ROADMAP
    "autotune coverage").  ``limit`` bounds how many *missing* signatures
    get tuned this run (0 = all) so CI can warm incrementally inside its
    time budget; cached entries always short-circuit.  Returns a summary
    dict (total / cached / tuned / skipped)."""
    sigs = zoo_signatures(image_size)
    cached = [s for s in sigs if get_config(s, path) is not None]
    missing = [s for s in sigs if get_config(s, path) is None]
    if force:
        missing, cached = sigs, []
    todo = missing if limit <= 0 else missing[:limit]
    for i, sig in enumerate(todo):
        cfg = tune_layer(sig, path=path, reps=reps, force=force)
        us = load_cache(path)["entries"][sig.key()]["us"]
        print(f"[{i + 1:>3}/{len(todo)}] {sig.key():<48} -> {cfg} "
              f"({us:.0f} us)")
    summary = {"image_size": image_size, "total": len(sigs),
               "cached": len(cached), "tuned": len(todo),
               "skipped": len(missing) - len(todo),
               "cache_path": cache_path(path)}
    print(f"sweep: {summary['total']} signatures @ {image_size}px — "
          f"{summary['cached']} already cached, {summary['tuned']} tuned, "
          f"{summary['skipped']} deferred (limit) -> "
          f"{summary['cache_path']}")
    return summary


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="repro.kernels.autotune",
        description="Warm the block-shape autotune cache over the zoo.")
    ap.add_argument("--sweep-zoo", action="store_true", required=True,
                    help="tune every zoo layer signature into the cache")
    ap.add_argument("--image-size", type=int, default=None,
                    help="input H=W the signatures are taken at "
                         "(default: 224 paper size; 64 with --smoke, "
                         "matching the CI perf benches)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI bounds: 64px signatures, reps=1, --limit 12 "
                         "unless overridden (incremental warming via the "
                         "persisted cache)")
    ap.add_argument("--reps", type=int, default=None,
                    help="timing reps per candidate (default 3; 1 smoke)")
    ap.add_argument("--limit", type=int, default=None,
                    help="max missing signatures tuned this run "
                         "(0 = all; default 0, 12 with --smoke)")
    ap.add_argument("--force", action="store_true",
                    help="re-tune even cached signatures")
    ap.add_argument("--cache", default=None,
                    help=f"cache file (default: ${CACHE_ENV} or "
                         f"results/autotune_cache.json)")
    args = ap.parse_args(argv)

    image_size = args.image_size or (64 if args.smoke else 224)
    reps = args.reps if args.reps is not None else (1 if args.smoke else 3)
    limit = args.limit if args.limit is not None else (12 if args.smoke
                                                      else 0)
    sweep_zoo(image_size, reps=reps, limit=limit, force=args.force,
              path=args.cache)
    return 0


if __name__ == "__main__":
    import sys

    # run the *canonical* module instance: under ``python -m`` this file
    # executes as ``__main__``, whose module-level recording state would be
    # invisible to the op wrappers importing ``repro.kernels.autotune``
    from repro.kernels.autotune import main as _main

    sys.exit(_main())
