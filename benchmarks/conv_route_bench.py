"""Device time of ``conv2d_gemm``'s two routes off the plain 1x1 path.

For each zoo conv shape that is not a plain 1x1 (the RGB stem and
squeezenet's ``fire*_e3x3``), two lane-full 3x3 convs, two strided 1x1
convs, and each batch, the implicit-GEMM kernel (``implicit_gemm_conv``,
today's blocks) and XLA's convolution (``xla_conv``, f32 at HIGHEST) are
each compiled, warmed, checked against each other, then run ``--runs``
times under ``jax.profiler.trace``.  The device time per call is the sum
of the TPU plane's ``XLA Ops`` events over the runs, divided by the runs;
the per-op split is kept beside it.

    python benchmarks/conv_route_bench.py [--runs 50] [--out DIR]

Needs a TPU: device time comes from the profiler's TPU plane.  Prints one
JSON line per case and a last line with the kernel/XLA ratio per case;
``--out`` keeps each case's trace and ``result.json``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import tempfile

# (name, H, C_i, C_o, K, stride, pad): mobilenet_v2's stem, squeezenet's
# distinct 3x3 expands, and, as no zoo layer has them, two lane-full 3x3
# convs and two strided 1x1 convs, one below and one at a full lane tile
SHAPES = [
    ("stem", 224, 3, 32, 3, 2, 1),
    ("fire2_e3x3", 56, 16, 64, 3, 1, 1),
    ("fire4_e3x3", 28, 32, 128, 3, 1, 1),
    ("fire6_e3x3", 14, 48, 192, 3, 1, 1),
    ("fire8_e3x3", 14, 64, 256, 3, 1, 1),
    ("lane_full_28", 28, 128, 128, 3, 1, 1),
    ("lane_full_14", 14, 256, 256, 3, 1, 1),
    ("pw_s2_64", 28, 64, 128, 1, 2, 0),
    ("pw_s2_128", 28, 128, 256, 1, 2, 0),
]
BATCHES = (1, 32)
_HLO = re.compile(r"%([^\s=]+) = ")
_SUFFIX = re.compile(r"(\.(\d+|clone))+\Z")


def _op(name: str) -> str:
    m = _HLO.match(name)
    return _SUFFIX.sub("", m.group(1)) if m else name


def device_ops(log_dir: pathlib.Path) -> dict[str, float]:
    """Summed device ns per op name on TPU 0 in the one trace under
    ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_serialized_xspace(path.read_bytes())
    ops: dict[str, float] = {}
    for plane in pd.planes:
        if not plane.name.endswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                ops[_op(e.name)] = ops.get(_op(e.name), 0.0) + e.duration_ns
    return ops


def time_case(fn, args, runs: int, log_dir: pathlib.Path) -> dict:
    import jax

    fn(*args).block_until_ready()
    with jax.profiler.trace(str(log_dir)):
        for _ in range(runs):
            out = fn(*args)
        out.block_until_ready()
    ops = device_ops(log_dir)
    per_call = {k: v / runs / 1e3 for k, v in
                sorted(ops.items(), key=lambda kv: -kv[1])}
    return {"device_us_per_call": sum(per_call.values()),
            "ops_us_per_call": per_call}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--runs", type=int, default=50)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.conv_gemm.ops import implicit_gemm_conv, xla_conv

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    print(json.dumps({"device": dev.device_kind}))
    out_dir = pathlib.Path(a.out or tempfile.mkdtemp())
    routes = {"kernel": implicit_gemm_conv, "xla": xla_conv}
    ratios = {}
    for name, h, ci, co, k, s, p in SHAPES:
        key = jax.random.PRNGKey(0)
        kx, kw, kb = jax.random.split(key, 3)
        w = 0.2 * jax.random.normal(kw, (k, k, ci, co), jnp.float32)
        b = 0.1 * jax.random.normal(kb, (co,), jnp.float32)
        for n in BATCHES:
            x = 0.5 * jax.random.normal(kx, (n, h, h, ci), jnp.float32)
            args = jax.device_put((x, w, b), dev)
            res, outs = {}, {}
            for route, conv in routes.items():
                fn = jax.jit(lambda x, w, b, conv=conv: conv(
                    x, w, b, stride=s, pad=p, act="relu"))
                outs[route] = np.asarray(fn(*args))
                case = f"{name}.b{n}.{route}"
                res[route] = time_case(fn, args, a.runs, out_dir / case)
                print(json.dumps({"case": case, "runs": a.runs,
                                  **res[route]}), flush=True)
            scale = float(np.max(np.abs(outs["xla"]))) or 1.0
            diff = float(np.max(np.abs(outs["kernel"] - outs["xla"])))
            ratios[f"{name}.b{n}"] = {
                "kernel_over_xla": res["kernel"]["device_us_per_call"]
                / res["xla"]["device_us_per_call"],
                "max_rel_diff": diff / scale}
    (out_dir / "result.json").write_text(json.dumps(ratios, indent=1))
    print(json.dumps(ratios))


if __name__ == "__main__":
    main()
