#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the chip this starts on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run it from the root of a checkout.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit.  The same checks are the last lines of
standard error.  Without a TPU, with fewer chips than the cell asks for,
or outside a checkout, it exits non-zero and prints no result.

JAX's persistent compilation cache lives at ``.jax_cache/chipbench/`` in
the checkout, a fixed directory that only the benchmark writes, with
eviction off (no size limit, so no access-time files), so that only the
first run of a cell there compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    """Parse the arguments, run the cell once, print the line."""
    ap = argparse.ArgumentParser(prog="chipbench/run.py",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cache = str(ROOT / ".jax_cache" / "chipbench")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, spec

    harness.use_compile_cache(cache)
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"chipbench: refused: the program is not in this checkout "
              f"({e})", file=sys.stderr)
        return 2
    try:
        line = harness.run_cell(ROOT, args.workload, args.seed,
                                args.seconds, bool(args.trace),
                                t_start=T_START)
    except spec.Refused as e:
        print(f"chipbench: refused: {e}", file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
