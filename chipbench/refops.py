"""Plain ops and the weight recipe shared by the reference forwards.

The reference forwards (``chipbench/models/<family>.py``) import nothing of
the program under test.  Each contraction runs at a stated precision:

* ``"highest"``: f32 operands at ``lax.Precision.HIGHEST`` (full f32 on a
  TPU's MXU) -- the precision the configurations state;
* ``"high"``: f32 operands at ``lax.Precision.HIGH``: on a TPU three
  bf16 passes (each operand split into a bf16 high and low part, the
  low-by-low product dropped); a CPU computes it in full f32;
* ``"bf16x3"``: the same three products written out, so that they read
  alike on every platform.  The parts are cut with ``lax.reduce_precision``
  and each product runs at ``HIGHEST``, where a product of two bf16 values
  is exact: a round trip through a bf16 ``convert`` is one that XLA may
  drop as excess precision, and did on the TPU.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("highest", "high", "bf16x3")


@dataclasses.dataclass(frozen=True)
class Layer:
    """One weighted layer as the papers define it, with its map sizes."""

    name: str
    op: str                  # "conv" | "dwconv" | "fc"
    k: int
    stride: int
    pad: int
    cin: int
    cout: int
    hin: int
    hout: int

    @property
    def weight_shape(self) -> tuple[int, ...]:
        """``(K, K, C)`` for a depthwise kernel, else ``(K, K, Cin, Cout)``."""
        if self.op == "dwconv":
            return (self.k, self.k, self.cin)
        return (self.k, self.k, self.cin, self.cout)

    @property
    def fan_in(self) -> int:
        """Inputs summed into one output (He initialisation)."""
        return self.k * self.k * (1 if self.op == "dwconv" else self.cin)


def conv_out(h: int, k: int, stride: int, pad: int) -> int:
    """Output rows of a convolution."""
    return (h + 2 * pad - k) // stride + 1


def _split(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """A bf16-exact high part and a bf16-exact low part, both f32."""
    hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _contract(f, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    """``f(a, b, lax_precision)`` at the named precision, accumulated in
    f32."""
    if precision == "highest":
        return f(a, b, lax.Precision.HIGHEST)
    if precision == "high":
        return f(a, b, lax.Precision.HIGH)
    if precision == "bf16x3":
        ah, al = _split(a)
        bh, bl = _split(b)
        hp = lax.Precision.HIGHEST
        return f(ah, bh, hp) + (f(ah, bl, hp) + f(al, bh, hp))
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def conv(x: jax.Array, w: jax.Array, b: jax.Array, *, stride: int,
         pad: int, precision: str, depthwise: bool = False) -> jax.Array:
    """NHWC convolution with symmetric zero padding ``pad``; a depthwise
    weight is ``(K, K, C)``."""
    groups = 1
    if depthwise:
        groups = w.shape[-1]
        w = w.reshape(w.shape[0], w.shape[1], 1, groups)

    def f(a, k, prec):
        return lax.conv_general_dilated(
            a, k, (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=prec,
            preferred_element_type=jnp.float32)

    return _contract(f, x, w, precision) + b


def dense(x: jax.Array, w: jax.Array, b: jax.Array, *,
          precision: str) -> jax.Array:
    """``(N, C) @ (C, O) + b``."""

    def f(a, k, prec):
        return jnp.dot(a, k, precision=prec,
                       preferred_element_type=jnp.float32)

    return _contract(f, x, w, precision) + b


def relu6(x: jax.Array) -> jax.Array:
    """``min(max(x, 0), 6)``."""
    return jnp.clip(x, 0.0, 6.0)



def global_avgpool(x: jax.Array) -> jax.Array:
    """Mean over the spatial axes: ``(N, C)``."""
    return jnp.mean(x, axis=(1, 2))



def init_params(layers: list[Layer], key: jax.Array) -> dict:
    """The benchmark's weights: He-normal kernels and N(0, 0.1^2) biases,
    one key split per layer in order.  Jit it to make the whole pytree in
    one call on the device."""
    params = {}
    for l in layers:
        key, kw, kb = jax.random.split(key, 3)
        w = jax.random.normal(kw, l.weight_shape, jnp.float32)
        params[l.name] = {
            "w": w * math.sqrt(2.0 / l.fan_in),
            "b": 0.1 * jax.random.normal(kb, (l.cout,), jnp.float32)}
    return params
