"""Streaming serving benchmark: both engines under the shared API.

For the CNN engine (dual-core pipeline with online slot-refill admission)
and the LM engine (dual-mesh continuous batching), measure on this host:

  * steady-state throughput — every request available at slot 0, the
    saturated-queue regime.  ``pipelined_fps`` times the full
    ``run_pipelined`` API surface (engine construction + submits + drain,
    what pre-engine callers paid) and ``engine_fps`` the engine's steady
    wall (first step -> result), both taken from the same physical runs —
    ``run_pipelined`` is a shim over the engine now, so the ratio
    measures the submit/bookkeeping overhead of the streaming surface
    (~1.0 means continuous admission costs no throughput versus the
    retired static dispatch path), not two competing implementations;
  * request latency under load — a fixed Poisson-ish arrival trace
    (``repro.serving.poisson_arrivals``, seeded, identical across runs)
    drives ``replay``; p50/p95 per-request wall-clock latency lands in the
    JSON, where ``benchmarks/compare_bench.py`` gates CI on it (p50_ms /
    p95_ms are gated fields — a >2x latency regression fails the PR).

Writes ``BENCH_serving.json`` — the committed baseline CI diffs against.

    PYTHONPATH=src python -m benchmarks.serving_bench --smoke
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

# A >=2-device mesh is the point of the exercise: force two host platform
# devices unless the caller already configured XLA (must happen pre-import).
if "jax" not in sys.modules and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")

CNN_MODELS = ("mobilenet_v1", "mobilenet_v2", "squeezenet")
ARRIVAL_RATE = 1.0          # requests per scheduler slot (Poisson-ish)
ARRIVAL_SEED = 0


def bench_cnn(report: dict, image_size: int, requests: int,
              reps: int) -> None:
    """Streaming CNN engine vs the committed pipelined baseline."""
    import jax

    from repro.core.arch import DUAL_BASELINE, BoardModel
    from repro.core.scheduler import build_schedule
    from repro.dualcore.runtime import DualCoreRunner
    from repro.models.cnn import build_model
    from repro.serving import (DualCoreEngine, Request, poisson_arrivals,
                               replay, stream_images)

    board = BoardModel()
    print(f"\n## CNN serving (balanced scheme, {image_size}px, "
          f"{requests} requests, {len(jax.devices())} local device(s))")
    print(f"{'model':<14}{'pipelined fps':>14}{'engine fps':>12}"
          f"{'ratio':>7}{'p50 ms':>9}{'p95 ms':>9}")
    for model in CNN_MODELS:
        params, _, graph = build_model(model)
        sched = build_schedule(graph, DUAL_BASELINE, board, "balanced")
        runner = DualCoreRunner(model, params, sched, use_pallas=True)
        imgs = [jax.random.normal(k, (1, image_size, image_size, 3))
                for k in jax.random.split(jax.random.PRNGKey(0), requests)]
        runner.run_sequential(imgs[:1])        # warm the per-group jits

        # steady state: saturated queue, same work as the old static path.
        # run_pipelined IS the engine shim now, so both numbers come from
        # the same physical runs, each timed at its own API surface: the
        # outer window (engine construction + submits + drain — what a
        # run_pipelined caller pays) vs the engine's steady wall
        # (first step -> result).  Same-run measurement sidesteps the
        # 2-5% coin-flips separate interleaved legs showed on this host;
        # gc.collect keeps the previous run's deallocations (2-3x swings
        # on this allocator) out of the timed window.
        t_pipe = t_eng = float("inf")
        for _ in range(max(2, reps)):
            gc.collect()
            t0 = time.perf_counter()
            res = stream_images(runner, imgs)
            t_pipe = min(t_pipe, time.perf_counter() - t0)
            t_eng = min(t_eng, res.stats["wall_s"])
            del res
        pipelined_fps = requests / t_pipe
        engine_fps = requests / t_eng

        # latency under the fixed Poisson-ish arrival trace — best-of like
        # the gated timing fields (a single replay's p95 of ~6 samples is
        # one GC pause away from a phantom CI failure)
        arrivals = poisson_arrivals(requests, rate=ARRIVAL_RATE,
                                    seed=ARRIVAL_SEED)
        p50 = p95 = float("inf")
        for _ in range(max(2, reps // 2)):
            gc.collect()
            m = replay(DualCoreEngine(runner),
                       [Request(x) for x in imgs], arrivals).metrics
            p50 = min(p50, m.p50_ms())
            p95 = min(p95, m.p95_ms())
        row = {
            "requests": requests,
            "exec_groups": len(runner.groups),
            "pipelined_fps": round(pipelined_fps, 2),
            "engine_fps": round(engine_fps, 2),
            "engine_vs_pipelined": round(engine_fps / pipelined_fps, 3),
            "arrival_rate_per_slot": ARRIVAL_RATE,
            "p50_ms": round(p50, 2),
            "p95_ms": round(p95, 2),
        }
        report["cnn"][model] = row
        print(f"{model:<14}{row['pipelined_fps']:>14.2f}"
              f"{row['engine_fps']:>12.2f}"
              f"{row['engine_vs_pipelined']:>6.2f}x"
              f"{row['p50_ms']:>9.1f}{row['p95_ms']:>9.1f}")


def bench_lm(report: dict, requests: int, batch: int, prompt_len: int,
             gen: int, arch: str = "qwen2_0_5b", reps: int = 2) -> None:
    """Streaming LM engine: tokens/s + request latency percentiles."""
    import jax

    from repro.configs.registry import get_smoke
    from repro.dualmesh import DualMeshRunner, split_mesh
    from repro.lm.model import init_params
    from repro.serving import (DualMeshEngine, Request, poisson_arrivals,
                               replay)

    cfg = get_smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    dual = split_mesh(jax.devices(), 0.5)
    runner = DualMeshRunner(cfg, params, dual, max_len=prompt_len + gen + 8)
    prompts = [jax.random.randint(k, (batch, prompt_len), 0, cfg.vocab)
               for k in jax.random.split(jax.random.PRNGKey(1), requests)]
    gs = runner.planned_group_size(prompts, [gen] * requests)

    def run_once(arrivals):
        eng = DualMeshEngine(runner, group_size=gs)
        return replay(eng, [Request(p, gen_steps=gen) for p in prompts],
                      arrivals)

    run_once([0] * requests)                   # warm the jit caches
    steady = run_once([0] * requests)
    arrivals = poisson_arrivals(requests, rate=ARRIVAL_RATE,
                                seed=ARRIVAL_SEED)
    p50 = p95 = float("inf")
    for _ in range(max(2, reps // 2)):         # best-of, like every gated
        gc.collect()                           # timing field
        m = run_once(arrivals).metrics
        p50 = min(p50, m.p50_ms())
        p95 = min(p95, m.p95_ms())
    row = {
        "arch": arch, "requests": requests, "batch": batch,
        "prompt_len": prompt_len, "gen": gen, "group_size": gs,
        "tokens_per_s": round(steady.stats["tokens_per_s"], 1),
        "total_tokens": steady.stats["total_tokens"],
        "arrival_rate_per_slot": ARRIVAL_RATE,
        "p50_ms": round(p50, 2),
        "p95_ms": round(p95, 2),
    }
    report["lm"][arch] = row
    print(f"\n## LM serving ({arch} smoke, {requests} requests x "
          f"batch {batch}, prompt {prompt_len}, gen {gen})")
    print(f"steady {row['tokens_per_s']:.0f} tok/s "
          f"(group_size={gs}); under Poisson arrivals "
          f"p50 {row['p50_ms']:.0f} ms, p95 {row['p95_ms']:.0f} ms")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI shape: small images, few requests, write the "
                         "JSON artifact")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--image-size", type=int, default=None,
                    help="CNN input H=W (default: 64 smoke / 96 full)")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per engine (default: 6 smoke / 16 full)")
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args(argv)

    image_size = args.image_size or (64 if args.smoke else 96)
    requests = args.requests or (6 if args.smoke else 16)

    import jax

    report: dict = {"cnn": {}, "lm": {},
                    "devices": len(jax.devices()),
                    "backend": jax.default_backend(),
                    "image_size": image_size}
    bench_cnn(report, image_size, requests, args.reps)
    bench_lm(report, requests=min(requests, 4), batch=1,
             prompt_len=16, gen=8, reps=args.reps)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
