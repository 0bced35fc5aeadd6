"""End-to-end JAX implementations of the paper's CNN workloads.

Every model is driven by its ``LayerGraph`` from ``repro.models.zoo`` — the
graph IS the single source of truth for layer characteristics, so the JAX
execution, the dual-OPU scheduler and the latency model can never diverge
(a test asserts per-layer activation shapes match the graph).

Execution is expressed once, as a step program (``repro.dualcore.program``),
and consumed two ways:

  * sequential forward (this module): run the steps in order on one device.
    ``use_pallas`` selects XLA reference ops vs the Pallas kernels; on the
    Pallas path the program runs as one exec group, its dw->pw /
    pw-expand->dw->pw-project chains fused into single fused_block
    pallas_calls by the same matcher the runtime applies per group
    (DESIGN.md §3).
  * pipelined dual-core (``run_pipelined`` -> ``repro.dualcore.runtime``):
    the same steps partitioned into the alternating c/p-core groups of a
    scheduler ``Schedule`` and executed on the two submeshes with the
    paper's one-slot image offset (DESIGN.md §8).

On the XLA path both run the same step objects.  On the Pallas path a
chain the schedule splits across cores fuses in the forward but not in the
runtime, so the two agree to float rounding, not bitwise.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.graph import LayerGraph
from repro.dualcore.program import build_program, run_layer as _run_layer
from repro.models.zoo import get_graph

__all__ = ["FORWARDS", "build_model", "init_params", "run_pipelined",
           "_run_layer"]

Params = dict[str, dict[str, jax.Array]]


def init_params(graph: LayerGraph, key: jax.Array,
                dtype=jnp.float32) -> Params:
    """He-init weights for every conv/dwconv/fc layer in the graph."""
    params: Params = {}
    for l in graph.layers:
        key, sub = jax.random.split(key)
        if l.op == "dwconv":
            shape = (l.K_h, l.K_w, l.C_i)
            fan_in = l.K_h * l.K_w
        else:
            shape = (l.K_h, l.K_w, l.C_i, l.C_o)
            fan_in = l.K_h * l.K_w * l.C_i
        w = jax.random.normal(sub, shape) * (2.0 / fan_in) ** 0.5
        params[l.name] = {"w": w.astype(dtype),
                         "b": jnp.zeros((l.C_o,), dtype)}
    return params


def _make_forward(name: str) -> Callable:
    def forward(params: Params, x: jax.Array, use_pallas: bool = False,
                collect: dict | None = None) -> jax.Array:
        prog = build_program(name, use_pallas=use_pallas)
        return prog.run(params, x, collect)

    forward.__name__ = f"{name}_forward"
    forward.__qualname__ = forward.__name__
    forward.__doc__ = (f"Sequential forward pass of {name} "
                       f"(step program in repro.dualcore.program).")
    return forward


mobilenet_v1_forward = _make_forward("mobilenet_v1")
mobilenet_v2_forward = _make_forward("mobilenet_v2")
squeezenet_forward = _make_forward("squeezenet")

FORWARDS: dict[str, Callable] = {
    "mobilenet_v1": mobilenet_v1_forward,
    "mobilenet_v2": mobilenet_v2_forward,
    "squeezenet": squeezenet_forward,
}


def build_model(name: str, key=None, dtype=jnp.float32):
    """Return (params, forward_fn, graph) for one of the paper workloads."""
    g = get_graph(name)
    key = key if key is not None else jax.random.PRNGKey(0)
    params = init_params(g, key, dtype)
    return params, FORWARDS[name], g


def run_pipelined(name: str, params: Params, schedule, images, *,
                  devices=None, use_pallas: bool = True,
                  record: list | None = None):
    """Execute ``schedule`` for real: pipeline ``images`` through the
    alternating c/p-core group chain on the split device mesh with the
    paper's one-slot offset (Fig.4b).  Returns the per-image logits in
    submission order.  Compatibility wrapper: continuous serving goes
    through ``repro.serving.DualCoreEngine`` (submit/step/drain with
    online slot-refill admission); this submits a ready list and drains.
    See ``repro.dualcore.runtime.DualCoreRunner`` for the knobs; pass
    ``record=[]`` to capture the execution trace."""
    from repro.dualcore.runtime import DualCoreRunner

    runner = DualCoreRunner(name, params, schedule, devices=devices,
                            use_pallas=use_pallas)
    return runner.run_pipelined(images, record=record)
