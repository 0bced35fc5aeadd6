#!/usr/bin/env python3
"""Chip smoke: serve the dual-core CNN path once on a TPU and check it.

Run from the repository root, on a machine with a TPU:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a four-chip host

One chip: ``serve cnn mobilenet_v2`` (8 requests) and ``serve fleet
--models mbv1,mbv2,sqz`` (12 requests), both at 224x224, batch 1, through
the compiled Pallas kernels — the same ``repro.launch.serve.run`` the CLI
calls.  On one chip the c- and p-submeshes alias the one device.

Four chips (``--four-chips``): only the three-model fleet, over a pool
split 2 c-chips + 2 p-chips; the c and p device sets must be disjoint and
every exec group's output must live on its own submesh.

Every phase checks that each exec-group program contains a Pallas kernel
(``tpu_custom_call`` in its compiled HLO), except a group whose every
layer ``conv2d_gemm`` routes to XLA (``xla_routed``: a stem conv alone),
which must hold an XLA ``convolution`` and no kernel; and that every
served output is
within ``TOL`` of a plain whole-model reference run on one chip: the XLA
forward (``use_pallas=False``) under ``jax.default_matmul_precision(
"highest")``.  Weights are random, from a seed; payloads are seeded.

The last line of stdout is ``{"ok": true, "device": {...}}`` with the
device as JAX reports it.  Without a TPU, or outside the repository, the
script exits non-zero and prints no such line.  Compile seconds printed
here are smoke timings, not measurements.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
IMAGE = 224
# One tolerance for every output: max |served - reference| over max
# |reference|, per request.  Both sides compute in f32 at full MXU
# precision (the kernels contract f32 operands at HIGHEST, the reference
# runs under "highest"), so they differ only in summation order (taps,
# channel blocks and fused epilogues vs XLA's convolutions) through ~50
# layers.  A wrong tap, halo row or padding mask moves the logits by far
# more; a single bf16 MXU pass per matmul alone gives 2.5e-2 on
# mobilenet_v2 (PERF.md, PR 11).
TOL = 1e-3
CNN_ARGV = ["cnn", "mobilenet_v2", "--requests", "8"]
FLEET_ARGV = ["fleet", "--models", "mbv1,mbv2,sqz", "--requests", "12"]


class SmokeError(RuntimeError):
    """A phase failed: the message says which check."""


BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events, while a phase runs."""

    def __init__(self):
        import jax

        self.seconds, self.cache_hits = 0.0, 0

        def on_duration(event, secs, **_):
            if event == BACKEND_COMPILE:
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def stop(self) -> None:
        import jax

        on_duration, on_event = self._listeners
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def device() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def reference(model: str):
    """The plain whole-model reference on one device: the XLA forward in
    f32 under the highest matmul precision."""
    import jax

    from repro.models.cnn import build_model

    params, fwd, _ = build_model(model)
    dev = jax.devices()[0]
    params = jax.device_put(params, dev)
    run = jax.jit(lambda p, x: fwd(p, x, use_pallas=False))

    def ref(x):
        with jax.default_matmul_precision("highest"):
            return run(params, jax.device_put(x, dev))

    return ref


def xla_only(graph, group) -> bool:
    """Whether every layer of an exec group is a conv that ``conv2d_gemm``
    routes to XLA's convolution (``xla_routed``): such a group holds no
    Pallas kernel."""
    from repro.kernels.conv_gemm.ops import xla_routed

    return all(l.op == "conv" and xla_routed(l.K_h, l.K_w, l.stride, l.pad)
               for l in map(graph.layer, group.layers))


def check_member(model: str, runner, x, *, require_kernels: bool) -> dict:
    """Exec groups of one member: their count, the c/p device ids, whether
    each compiled group program holds a Pallas kernel (a group of
    XLA-routed convs alone: an XLA convolution and no kernel), and whether
    each group's output sits on its own core's submesh."""
    c_ids = sorted(d.id for d in runner.dual.c_mesh.devices.flat)
    p_ids = sorted(d.id for d in runner.dual.p_mesh.devices.flat)
    kernels, xla_convs, placed, wrong = 0, 0, 0, []
    for (compiled, env), group in zip(runner.trace_groups(x),
                                      runner.groups):
        text = compiled.as_text()
        has_kernel = "tpu_custom_call" in text
        kernels += has_kernel
        if xla_only(runner.graph, group):
            xla_convs += 1
            if has_kernel or " convolution(" not in text:
                wrong.append(group.layers)
        elif not has_kernel:
            wrong.append(group.layers)
        want = set(c_ids if group.core == "c" else p_ids)
        placed += all({d.id for d in a.sharding.device_set} == want
                      for a in env.values())
    n = len(runner.groups)
    if require_kernels and wrong:
        raise SmokeError(f"{model}: {len(wrong)} of {n} exec-group "
                         f"programs miss their route (a tpu_custom_call, "
                         f"or for XLA-routed convs alone a convolution "
                         f"and no kernel): {wrong}")
    if placed != n:
        raise SmokeError(f"{model}: {n - placed} of {n} exec-group outputs "
                         f"are not on their core's submesh")
    return {"model": model, "exec_groups": n, "c_devices": c_ids,
            "p_devices": p_ids, "groups_with_tpu_custom_call": kernels,
            "groups_xla_routed": xla_convs}


def run_phase(label: str, argv: list[str], *, image_size: int = IMAGE,
              require_kernels: bool = True) -> list[dict]:
    """Serve ``argv`` through ``repro.launch.serve.run`` at
    ``image_size``, batch 1, then check every member and every output.
    Returns one report per member; raises :class:`SmokeError`."""
    from repro.launch import serve

    clock = CompileClock()
    t0 = time.perf_counter()
    served = serve.run(argv + ["--image-size", str(image_size),
                               "--batch", "1"])
    wall = time.perf_counter() - t0
    clock.stop()
    if served.rc:
        raise SmokeError(f"{label}: serve exited {served.rc}")
    res = served.result
    bad = [c.ticket.rid for c in res.completions if c.status != "ok"]
    if len(res.completions) != len(served.requests) or bad:
        raise SmokeError(f"{label}: {len(res.completions)} of "
                         f"{len(served.requests)} requests completed, "
                         f"not ok: {bad}")
    import numpy as np

    errs: dict[str, list[float]] = {m: [] for m in served.runners}
    refs = {m: reference(m) for m in served.runners}
    only = next(iter(served.runners)) if len(served.runners) == 1 else None
    for req, out in zip(served.requests, res.outputs):
        model = req.model or only
        want = refs[model](req.payload)
        if out.shape != want.shape:
            raise SmokeError(f"{label}/{model}: output {out.shape}, "
                             f"reference {want.shape}")
        out, want = np.asarray(out, np.float32), np.asarray(want)
        errs[model].append(float(np.max(np.abs(out - want))
                                 / np.max(np.abs(want))))
    reports = []
    for model, runner in served.runners.items():
        rep = check_member(model, runner, served.requests[0].payload,
                           require_kernels=require_kernels)
        if not errs[model]:
            raise SmokeError(f"{label}/{model}: served no request")
        worst = max(errs[model])
        rep.update(phase=label, requests=len(errs[model]),
                   max_rel_err=worst, tol=TOL)
        if not worst <= TOL:              # NaN fails too
            raise SmokeError(f"{label}/{model}: max relative error "
                             f"{worst:.3e} exceeds {TOL:.0e}")
        reports.append(rep)
    print(f"[smoke] {label}: compile {clock.seconds:.1f} s, "
          f"{clock.cache_hits} persistent-cache hits, wall {wall:.1f} s "
          f"(smoke timings, not measurements)", flush=True)
    for rep in reports:
        print("[smoke] " + json.dumps(rep), flush=True)
    return reports


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke.py",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve the three-model fleet over four chips "
                         "(2 c + 2 p) and run nothing else")
    args = ap.parse_args(argv)

    def fail(msg: str) -> int:
        print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
        return 1

    # the TPU runtime would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    dev = device()
    if dev["platform"] != "tpu":
        return fail(f"JAX platform is {dev['platform']!r} "
                    f"({dev['kind']}), not 'tpu': this smoke runs the "
                    f"compiled kernels on a TPU and has no fallback")
    want = 4 if args.four_chips else 1
    if dev["count"] < want:
        return fail(f"{dev['count']} TPU device(s), the phase needs {want}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return fail(f"the repository's src/ is not next to this script "
                    f"({e})")
    print(f"[smoke] {jax.devices()}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    try:
        if args.four_chips:
            reports = run_phase("fleet-4chip", FLEET_ARGV)
            for r in reports:
                if set(r["c_devices"]) & set(r["p_devices"]):
                    raise SmokeError(f"{r['model']}: c devices "
                                     f"{r['c_devices']} and p devices "
                                     f"{r['p_devices']} overlap")
        else:
            run_phase("cnn", CNN_ARGV)
            run_phase("fleet", FLEET_ARGV)
    except SmokeError as e:
        return fail(str(e))
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
