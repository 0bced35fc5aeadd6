"""Kernel spec table: per-Pallas-kernel block shapes, VMEM working set and
arithmetic intensity on representative shapes of the assigned archs —
the structural evidence used in place of wall-clock (CPU-only container).

AI (arithmetic intensity) is computed from true HBM traffic under the
kernel's blocking: inputs read once per tile-pass, outputs written once.
v5e ridge point = 197e12 / 819e9 ~= 240 flops/byte.

``--smoke`` additionally measures real wall-clock on this container
(interpret mode) for the fused blocks (dw->pw and pw->dw->pw) against the
unfused per-layer kernels, and writes ``BENCH_kernels.json`` so CI keeps a
perf trajectory (DESIGN.md §6).
"""
from __future__ import annotations

import argparse
import json
import sys

RIDGE = 197e12 / 819e9


def gemm_case(name, M, K, N, bm, bn, bk, dtype_bytes=2):
    flops = 2.0 * M * K * N
    # k-loop accumulates in VMEM: A read once per (j) column-block pass,
    # B read once per (i) row-block pass, C written once.
    passes_a = -(-N // bn)
    passes_b = -(-M // bm)
    bytes_ = (M * K * passes_a + K * N * passes_b + M * N) * dtype_bytes
    vmem = (bm * bk + bk * bn) * dtype_bytes + bm * bn * 4
    return name, f"M{M} K{K} N{N}", (bm, bn, bk), vmem, flops / bytes_


def run_all():
    print("\n## Pallas kernel specs (TPU target, validated interpret=True)")
    print(f"{'kernel':<18}{'shape':<28}{'block':<18}"
          f"{'VMEM/step':>10}{'AI fl/B':>9}{'bound':>7}")
    rows = []
    cases = [
        gemm_case("conv_gemm im2col", 200704, 27, 32, 128, 32, 27),
        gemm_case("conv_gemm pw", 12544, 1024, 1024, 128, 128, 128),
        gemm_case("lm qkv (14b)", 4096 * 8, 5120, 6144, 128, 128, 128),
        gemm_case("lm mlp (104b)", 4096, 12288, 33792 // 16, 128, 128, 128),
    ]
    # depthwise: halo tile read once, K*K taps reuse it from VMEM
    h, c, k = 112, 64, 3
    dw_flops = 2.0 * h * h * c * k * k
    dw_bytes = ((h + 2) * (h + 2) * c + h * h * c + k * k * c) * 2
    cases.append(("depthwise", f"{h}x{h}x{c} k{k}", ("H-tile", 64),
                  (h + 2) * (h + 2) * 64 * 4, dw_flops / dw_bytes))
    # flash attention: per (q-block, kv-block) pass
    b_, hq, s, d = 8, 96, 4096, 128
    fa_flops = 4.0 * b_ * hq * s * s * d
    fa_bytes = (b_ * hq * s * d                              # q once
                + 2 * b_ * 8 * s * d * (s // 128)            # kv per q-blk
                + b_ * hq * s * d) * 2
    cases.append(("flash_attn", f"B{b_} H{hq}/8 S{s} D{d}", (128, 128),
                  (128 * d * 3 + 128 * 128) * 4, fa_flops / fa_bytes))
    # decode attention: the p-class kernel — streams KV once
    b_, hkv, s = 128, 8, 32768
    dec_flops = 4.0 * b_ * 96 * s * 128
    dec_bytes = 2 * b_ * hkv * s * 128 * 2
    cases.append(("flash_decode", f"B{b_} Hkv{hkv} S{s}", (8, 512),
                  (512 * 128 * 3) * 4, dec_flops / dec_bytes))
    # rmsnorm: one pass
    rows_, dm = 2 ** 20, 12288
    cases.append(("rmsnorm", f"rows 1M d {dm}", (256, dm),
                  256 * dm * 4 * 2, (3.0 * rows_ * dm)
                  / (2.0 * rows_ * dm * 2)))
    for name, shape, block, vmem, ai in cases:
        bound = "MXU" if ai > RIDGE else "HBM"
        rows.append((name, shape, block, vmem, ai, bound))
        print(f"{name:<18}{shape:<28}{str(block):<18}"
              f"{vmem/1024:>8.0f}KB{ai:>9.1f}{bound:>7}")
    print(f"(ridge ~{RIDGE:.0f} fl/B on v5e; depthwise/decode/rmsnorm are "
          f"HBM-bound by design — the p-class kernels)")
    return rows


# --------------------------------------------------------------------------
# --smoke: measured wall-clock on this container -> BENCH_kernels.json
# --------------------------------------------------------------------------
def _time_ms(fn, reps: int = 3) -> float:
    from repro.kernels.util import bench_best_us
    return bench_best_us(fn, reps=reps) / 1e3


def smoke(out_path: str = "BENCH_kernels.json", reps: int = 4) -> dict:
    """Measure fused-vs-unfused wall-clock on small model-zoo shapes,
    write the JSON perf artifact, return it."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import autotune
    from repro.kernels.conv_gemm.ops import pointwise_conv
    from repro.kernels.depthwise.ops import depthwise
    from repro.kernels.fused_block.ops import (fused_dw_pw,
                                               fused_inverted_residual)

    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    report: dict = {"backend": jax.default_backend(),
                    "interpret": jax.default_backend() == "cpu",
                    "reps": reps, "fused_dw_pw": [], "fused_pw_dw_pw": [],
                    "autotune": []}

    print("\n## kernel smoke bench (wall-clock on this container)")
    # -- fused dw->pw vs unfused two-kernel path (MobileNet-v1 blocks) -----
    for h, c, co, s in [(14, 256, 256, 1),   # mbv1 dw7..11/pw
                        (14, 512, 512, 1),
                        (7, 1024, 1024, 1)]:
        x = (jax.random.normal(keys[0], (1, h, h, c)) * 0.5)
        dw_w = (jax.random.normal(keys[1], (3, 3, c)) * 0.3)
        dw_b = jnp.zeros((c,))
        pw_w = (jax.random.normal(keys[2], (c, co)) * 0.2)
        pw_b = jnp.zeros((co,))

        def run_unfused():
            y = depthwise(x, dw_w, dw_b, stride=s, pad=1, act="relu6")
            return pointwise_conv(y, pw_w, pw_b, act="relu6")

        def run_fused():
            return fused_dw_pw(x, dw_w, dw_b, pw_w, pw_b, stride=s, pad=1,
                               dw_act="relu6", pw_act="relu6")

        t_unf = _time_ms(run_unfused, reps)
        t_fus = _time_ms(run_fused, reps)
        row = {"shape": f"{h}x{h}x{c}->{co} s{s}",
               "unfused_ms": round(t_unf, 2), "fused_ms": round(t_fus, 2),
               "speedup": round(t_unf / t_fus, 2),
               "hbm_intermediate_bytes_saved": h * h * c * 4 // (s * s)}
        report["fused_dw_pw"].append(row)
        print(f"dw->pw {row['shape']:<22} unfused {t_unf:8.1f}ms  "
              f"fused {t_fus:8.1f}ms  ({row['speedup']}x)")

    # -- fused inverted residual (MobileNet-v2 blocks) ---------------------
    for h, ci, t_exp, s in [(14, 64, 6, 1),      # mbv2 b8-ish
                            (7, 160, 6, 1)]:     # mbv2 b15-ish
        cm, co = ci * t_exp, ci
        x = (jax.random.normal(keys[0], (1, h, h, ci)) * 0.5)
        ew = (jax.random.normal(keys[1], (ci, cm)) * 0.2)
        dw_w = (jax.random.normal(keys[2], (3, 3, cm)) * 0.3)
        pw = (jax.random.normal(keys[3], (cm, co)) * 0.2)
        eb, db, pb = jnp.zeros((cm,)), jnp.zeros((cm,)), jnp.zeros((co,))

        def run_unfused():
            y = pointwise_conv(x, ew, eb, act="relu6")
            y = depthwise(y, dw_w, db, stride=s, pad=1, act="relu6")
            return pointwise_conv(y, pw, pb) + x

        def run_fused():
            return fused_inverted_residual(x, ew, eb, dw_w, db, pw, pb, x,
                                           stride=s, pad=1)

        t_unf = _time_ms(run_unfused, reps)
        t_fus = _time_ms(run_fused, reps)
        row = {"shape": f"{h}x{h}x{ci} t{t_exp} s{s}",
               "unfused_ms": round(t_unf, 2), "fused_ms": round(t_fus, 2),
               "speedup": round(t_unf / t_fus, 2),
               "hbm_intermediate_bytes_saved":
                   (h * h * cm + (h // s) * (h // s) * cm) * 4}
        report["fused_pw_dw_pw"].append(row)
        print(f"pw->dw->pw {row['shape']:<18} unfused {t_unf:8.1f}ms  "
              f"fused {t_fus:8.1f}ms  ({row['speedup']}x)")

    # -- autotuner: tune one signature per kind, report the winners --------
    for sig in [autotune.LayerSig("pointwise", 14, 14, 256, 256),
                autotune.LayerSig("fused_dw_pw", 14, 14, 128, 128, 3, 3,
                                  1, 1)]:
        cfg = autotune.tune_layer(sig, reps=1)
        entry = autotune.load_cache()["entries"][sig.key()]
        report["autotune"].append({"sig": sig.key(), "config": cfg,
                                   "us": entry["us"]})
        print(f"autotune {sig.key():<42} -> {cfg}")

    with open(out_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"wrote {out_path}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="measure wall-clock and write BENCH_kernels.json")
    ap.add_argument("--out", default="BENCH_kernels.json")
    ap.add_argument("--reps", type=int, default=4)
    args = ap.parse_args(argv)
    if args.smoke:
        smoke(args.out, reps=args.reps)
    else:
        run_all()
    return 0


if __name__ == "__main__":
    sys.exit(main())
