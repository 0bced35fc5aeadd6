"""Pure-jnp oracles for the conv_gemm path (DESIGN.md §1)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_bias_act_ref(x: jax.Array, w: jax.Array,
                        bias: jax.Array | None = None,
                        act: str | None = None) -> jax.Array:
    out = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if act == "relu":
        out = jnp.maximum(out, 0.0)
    elif act == "relu6":
        out = jnp.clip(out, 0.0, 6.0)
    return out.astype(x.dtype)


def conv2d_ref(x: jax.Array, w: jax.Array, bias: jax.Array | None = None,
               stride: int = 1, pad: int = 0,
               act: str | None = None) -> jax.Array:
    """Reference NHWC conv via jax.lax (oracle for the full conv op)."""
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if act == "relu":
        out = jnp.maximum(out, 0.0)
    elif act == "relu6":
        out = jnp.clip(out, 0.0, 6.0)
    return out.astype(x.dtype)
