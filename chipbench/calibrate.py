#!/usr/bin/env python3
"""The measurements that set the benchmark's fixed numbers, made on the chip.

Run from the root of a checkout, on the chip:

    python3 chipbench/calibrate.py sweep --workload mbv2-poisson \\
        --rates 100,200,300 --seconds 5
    python3 chipbench/calibrate.py readings --workload mbv2-poisson \\
        --seeds 1,2,3 --seconds 3
    python3 chipbench/calibrate.py explore --workload mbv2-poisson \\
        --seconds 1

``sweep`` serves an open-loop cell at each offered rate in turn, in one
process on one built system, and prints what each rate achieved: the knee
is the highest rate served with no growing backlog (:func:`knee_of`);
``--set-rate`` writes 0.8 of it into the cell's traffic file.
``readings`` runs the cell on each seed (short windows at the cell's own
load), on the first ``--control-seeds`` of them with the control in the
program's place (``--control``: the reference at that precision; the run
also reads the program's own answers).  The limits in the configurations
are set from these two readings.  ``explore`` makes one traced run and
keeps its trace, gzipped, and its host log, with a listing of the trace's
planes, lines and busiest event names.

Every result is also written to ``--out`` (``calibrate_out/`` in the
checkout by default).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _emit(out: pathlib.Path, kind: str, workload: str, obj) -> None:
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{kind}-{workload}.json").write_text(json.dumps(obj, indent=1))
    print(json.dumps(obj), flush=True)


def sweep(args, bench, harness, gen) -> None:
    """Serve an open-loop cell at each of ``--rates`` and report each."""
    import jax
    import numpy as np

    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    devices = jax.devices()[:cell["chips"]]
    w_weights, w_traffic, _ = gen.seed_words(args.seed, 3)
    rng = np.random.default_rng(w_traffic)
    span = harness._no_span
    system, _, _ = harness.build(bench, config, devices, w_weights, span)
    pool = gen.payload_pool(mix, config["image_size"], rng)
    system.warm(pool[0])
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        sched = gen.open_schedule(dict(mix, rate_img_s=rate),
                                  sorted(config["models"]), rng,
                                  args.seconds)
        t0, window_s, recs, by_rid = harness.drive_open(
            system, sched, pool, args.seconds, span)
        backlog = sum(1 for r in recs if r.started is None)
        harness.drain(system, by_rid, t0, span)
        ok = [r for r in recs if r.status == "ok"
              and r.finished <= window_s]
        lat = [(r.finished - r.due) * 1e3 for r in recs
               if r.status == "ok"]
        row = {"rate_img_s": rate,
               "throughput_img_s": sum(r.images for r in ok) / window_s,
               "p50_ms": harness.percentile(lat, 50),
               "p95_ms": harness.percentile(lat, 95),
               "p99_ms": harness.percentile(lat, 99),
               "not_admitted_at_close": backlog, "requests": len(recs)}
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
        for r in recs:
            r.output = None
    knee = knee_of(rows)
    _emit(args.out, "sweep", args.workload,
          {"workload": args.workload, "seconds": args.seconds,
           "device": harness.device_info(devices), "rows": rows,
           "knee_img_s": knee})
    if args.set_rate and knee:
        path = bench.home / "traffic" / f"{cell['traffic']}.json"
        path.write_text(json.dumps(dict(mix, rate_img_s=int(0.8 * knee)),
                                   indent=2) + "\n")


def knee_of(rows: list[dict]) -> float | None:
    """The highest offered rate, of an ascending sweep, below which every
    rate was served: at least 98% of it completed in the window and at
    most 1% of its requests (or 2) were still waiting for admission at
    the close, so no backlog grew."""
    knee = None
    for row in rows:
        waiting = max(2, 0.01 * row["requests"])
        if (row["throughput_img_s"] < 0.98 * row["rate_img_s"]
                or row["not_admitted_at_close"] > waiting):
            break
        knee = row["rate_img_s"]
    return knee


def readings(args, bench, harness, gen) -> None:
    """The program's and the control's readings on each of ``--seeds``."""
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        control = args.control if len(rows) < args.control_seeds else None
        line = harness.run_cell(bench.root, args.workload, seed,
                                args.seconds, False,
                                t_start=time.perf_counter(),
                                home=bench.home, control=control)
        checks = line.get("program_checks", line["checks"])
        row = {"seed": seed, "attempted": line["attempted"],
               "failed": line["failed"],
               "program": {k: v["value"] for k, v in checks.items()}}
        if control:
            row["control"] = {k: v["value"]
                              for k, v in line["checks"].items()}
            row["control_correct"] = line["correct"]
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    _emit(args.out, "readings", args.workload,
          {"workload": args.workload, "seconds": args.seconds,
           "control": args.control, "rows": rows})


def explore(args, bench, harness, gen) -> None:
    """One traced run; keep its trace and list what it holds."""
    from chipbench import trace_reduce

    tdir = args.out / f"trace-{args.workload}"
    tdir.mkdir(parents=True, exist_ok=True)
    err = None
    try:
        line = harness.run_cell(ROOT, args.workload, args.seed,
                                args.seconds, True, t_start=T_START,
                                trace_dir=str(tdir))
    except Exception as e:            # keep the trace to read the cause
        line, err = None, repr(e)
    listing = {"error": err, "line": line, "planes": []}
    found = sorted(tdir.glob("*/plugins/profile/*/*.xplane.pb"))
    if found:
        raw = found[-1].read_bytes()
        (args.out / f"{args.workload}.xplane.pb.gz").write_bytes(
            gzip.compress(raw))
        for log in tdir.glob("*/host.json"):
            shutil.copy(log, args.out / f"{args.workload}.host.json")
        pd = trace_reduce.load(found[-1])
        for plane in pd.planes:
            lines = []
            for ln in plane.lines:
                names: dict[str, list] = {}
                first = None
                for e in ln.events:
                    first = first or (e.name, e.start_ns, e.duration_ns,
                                      [(k, str(v)) for k, v in e.stats][:8])
                    rec = names.setdefault(e.name, [0, 0.0])
                    rec[0] += 1
                    rec[1] += e.duration_ns
                top = sorted(names.items(), key=lambda kv: -kv[1][1])[:25]
                lines.append({"line": ln.name, "events": sum(
                    v[0] for v in names.values()), "top": top,
                    "first": first})
            listing["planes"].append({"plane": plane.name, "lines": lines})
    _emit(args.out, "explore", args.workload, listing)


def main(argv=None) -> int:
    """Parse the arguments and run one measurement on the chip."""
    ap = argparse.ArgumentParser(prog="chipbench/calibrate.py",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("sweep", "readings", "explore"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 7)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--out", type=pathlib.Path,
                    default=ROOT / "calibrate_out")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="readings: read the control on this many of the "
                         "seeds, the first ones")
    ap.add_argument("--control", choices=("high", "bf16x3"),
                    default="high")
    ap.add_argument("--set-rate", action="store_true",
                    help="sweep: write 0.8 x the knee into the cell's "
                         "traffic file")
    args = ap.parse_args(argv)
    cache = str(ROOT / ".jax_cache" / "chipbench")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from chipbench import harness, spec, traffic as gen

    harness.use_compile_cache(cache)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    bench = spec.Bench(ROOT)
    {"sweep": sweep, "readings": readings,
     "explore": explore}[args.what](args, bench, harness, gen)
    return 0


if __name__ == "__main__":
    sys.exit(main())
