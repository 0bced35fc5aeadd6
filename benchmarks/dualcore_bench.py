"""Dual-core CNN pipeline benchmark: measured execution vs. simulation.

Model side (always): for mbv1 / mbv2 / squeezenet under every allocation
scheme, the analytical two-batch latency T_b2 of the *executable* group
chain (the exec schedule the runtime actually runs), the instruction-level
simulator's prediction, and the pipeline speedup over serialized execution
(2 x sum of group latencies / T_b2) — the paper's Fig.4b claim.

Measured (``--smoke``): the balanced-scheme schedule is executed for real by
``repro.dualcore.runtime`` on a >=2-device host mesh (the module forces two
host platform devices when none are configured): two images pipelined
through the c/p submeshes vs. strictly sequential, wall-clock side by side
with the simulator's T_b2.  Writes ``BENCH_dualcore.json`` — the committed
baseline that ``benchmarks/compare_bench.py`` gates CI against.

    PYTHONPATH=src python -m benchmarks.dualcore_bench --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# A >=2-device mesh is the point of the exercise: force two host platform
# devices unless the caller already configured XLA (must happen pre-import).
if "jax" not in sys.modules and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")

MODELS = ("mobilenet_v1", "mobilenet_v2", "squeezenet")
SCHEMES = ("layer_type", "greedy", "round_robin", "balanced")


def bench_model_side(report: dict) -> None:
    """Analytic + simulated numbers for every model x scheme."""
    from repro.core.arch import DUAL_BASELINE, BoardModel
    from repro.core.scheduler import build_schedule
    from repro.core.simulator import simulate_dual_core
    from repro.dualcore.program import build_program
    from repro.dualcore.runtime import build_exec_plan
    from repro.models.zoo import get_graph

    board = BoardModel()
    print("\n## dual-core pipeline, model side (DUAL_BASELINE, cycles)")
    print(f"{'model':<14}{'scheme':<13}{'grp':>4}{'T_b2':>12}"
          f"{'sim T_b2':>12}{'sim ms':>8}{'fps':>8}{'speedup':>9}")
    for model in MODELS:
        graph = get_graph(model)
        program = build_program(graph, use_pallas=True)
        report["model_side"][model] = {}
        for scheme in SCHEMES:
            sched = build_schedule(graph, DUAL_BASELINE, board, scheme)
            es = build_exec_plan(program, sched).exec_schedule
            sim = simulate_dual_core(es)
            seq = 2 * sum(es.group_latencies)
            row = {
                "exec_groups": len(es.groups),
                "t_b2_cycles": es.t_b2(),
                "sim_t_b2_cycles": sim.cycles_two_images,
                "sim_t_b2_ms": round(board.cycles_to_seconds(
                    sim.cycles_two_images) * 1e3, 3),
                "fps": round(es.throughput_fps(), 1),
                "sequential_cycles": seq,
                "pipeline_speedup": round(seq / es.t_b2(), 3),
            }
            report["model_side"][model][scheme] = row
            print(f"{model:<14}{scheme:<13}{row['exec_groups']:>4}"
                  f"{row['t_b2_cycles']:>12,}{row['sim_t_b2_cycles']:>12,}"
                  f"{row['sim_t_b2_ms']:>8.2f}{row['fps']:>8.1f}"
                  f"{row['pipeline_speedup']:>8.2f}x")


def bench_measured(report: dict, image_size: int, reps: int) -> None:
    """Execute the balanced schedule for real: pipelined vs sequential
    wall-clock for the two-image batch, next to the simulator's T_b2."""
    import jax

    from repro.core.arch import DUAL_BASELINE, BoardModel
    from repro.core.scheduler import build_schedule
    from repro.core.simulator import simulate_dual_core
    from repro.dualcore.runtime import DualCoreRunner
    from repro.models.cnn import build_model

    board = BoardModel()
    report["devices"] = len(jax.devices())
    report["backend"] = jax.default_backend()
    report["image_size"] = image_size
    print(f"\n## dual-core pipeline, measured two-batch latency "
          f"({len(jax.devices())} local device(s), {image_size}px, "
          f"balanced scheme, Pallas group-fused)")
    for model in MODELS:
        params, _, graph = build_model(model)
        sched = build_schedule(graph, DUAL_BASELINE, board, "balanced")
        runner = DualCoreRunner(model, params, sched, use_pallas=True)
        es = runner.plan.exec_schedule
        sim = simulate_dual_core(es)
        imgs = [jax.random.normal(k, (1, image_size, image_size, 3))
                for k in jax.random.split(jax.random.PRNGKey(0), 2)]
        runner.run_sequential(imgs[:1])        # warm the per-group jits
        _, t_pipe = runner.timed(imgs, "pipelined", reps=reps)
        _, t_seq = runner.timed(imgs, "sequential", reps=reps)
        row = {
            "scheme": "balanced",
            "exec_groups": len(es.groups),
            "pipelined_ms": round(t_pipe * 1e3, 2),
            "sequential_ms": round(t_seq * 1e3, 2),
            "measured_speedup": round(t_seq / t_pipe, 3),
            "model_speedup": round(
                2 * sum(es.group_latencies) / es.t_b2(), 3),
            "sim_t_b2_cycles": sim.cycles_two_images,
            "sim_t_b2_ms": round(board.cycles_to_seconds(
                sim.cycles_two_images) * 1e3, 3),
        }
        report["measured"][model] = row
        print(f"{model:<14} pipelined {row['pipelined_ms']:8.1f} ms  "
              f"sequential {row['sequential_ms']:8.1f} ms  "
              f"({row['measured_speedup']:.2f}x measured, "
              f"{row['model_speedup']:.2f}x model-side, "
              f"sim T_b2 {row['sim_t_b2_ms']:.2f} ms @200MHz)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="also measure wall-clock on this host and write "
                         "the JSON artifact")
    ap.add_argument("--out", default="BENCH_dualcore.json")
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args(argv)

    report: dict = {"model_side": {}, "measured": {}}
    bench_model_side(report)
    if args.smoke:
        bench_measured(report, args.image_size, args.reps)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
