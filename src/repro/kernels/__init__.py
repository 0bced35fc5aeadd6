"""Pallas TPU kernels for the perf-critical hot-spots.

Each kernel ships three files (repo convention):
  kernel.py  pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target;
             validated in interpret mode on this CPU container — the
             ``interpret=None`` default auto-detects the backend)
  ops.py     dispatch wrapper (consults the block-shape autotune cache)
  ref.py     pure-jnp oracle used by the allclose test sweeps

Kernels (see DESIGN.md for the dual-OPU mapping):
  conv_gemm    c-core analogue — tiled GEMM for 1x1 convs and fc, fused
               bias+ReLU6 epilogue; every other conv on XLA's convolution
  depthwise    p-core analogue — VMEM halo tile (the line-buffer port)
  fused_block  dw->pw and pw-expand->dw->pw-project in ONE pallas_call;
               the intermediate feature maps never leave VMEM
  attention    flash attention (train/prefill) + split-K decode; int8-KV
               variants live in repro.lm.modules
  rmsnorm      fused norm used by every assigned arch

Shared helpers: kernels/util.py (padding, grid cdiv, interpret default);
kernels/autotune.py (JSON-cached per-layer-signature block shapes).
"""
