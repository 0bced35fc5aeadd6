"""Step-program IR: one uniform execution representation of the CNN zoo.

The dual-core runtime needs to execute *parts* of a network on different
submeshes, so the hand-written per-model forward functions are factored into
a flat list of per-layer :class:`Step` objects — the program.  Each step
reads/writes named buffers in an environment dict and knows how to run
itself given the parameter pytree.  ``repro.dualcore.runtime`` partitions the
program into alternating c-/p-core exec groups from a
:class:`~repro.core.scheduler.Schedule` and pipelines images through them;
on the Pallas path :func:`regroup_fused` then fuses dw->pw and
pw->dw->pw chains *within* each group into single fused-block steps.  The
sequential forward (``repro.models.cnn``, :meth:`Program.run`) is the same
program as one group, fused the same way.  On the XLA path nothing fuses,
so the runner's groups run exactly the sequential forward's steps.

Buffer conventions: the main chain flows through ``"h"``; the final logits
land in ``"out"``; SqueezeNet fire modules use ``"sq"``/``"e1"`` for the
squeeze/expand branches; the MobileNet-v2 per-layer path stashes the block
input in ``"res"`` for the residual add.  ``collect`` dicts receive
activation *shapes* (never values), recorded at trace time, keyed by the
layer (a fused step records its last layer).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.fusion import match_chain
from repro.core.graph import LayerGraph, LayerSpec
from repro.kernels.conv_gemm.ops import conv2d_gemm
from repro.kernels.conv_gemm.ref import conv2d_ref
from repro.kernels.depthwise.ops import depthwise
from repro.kernels.depthwise.ref import depthwise_conv2d_ref
from repro.kernels.fused_block.ops import (fused_dw_pw,
                                           fused_inverted_residual)
from repro.models.zoo import get_graph

Params = dict[str, dict[str, jax.Array]]
Env = dict[str, jax.Array]


def run_layer(l: LayerSpec, x: jax.Array, p: dict[str, jax.Array],
              act: str | None, use_pallas: bool) -> jax.Array:
    """One graph layer on either execution backend (XLA ref / Pallas)."""
    if l.op == "dwconv":
        if use_pallas:
            return depthwise(x, p["w"], p["b"], stride=l.stride, pad=l.pad,
                             act=act)
        return depthwise_conv2d_ref(x, p["w"], p["b"], stride=l.stride,
                                    pad=l.pad, act=act)
    if use_pallas:
        return conv2d_gemm(x, p["w"], p["b"], stride=l.stride, pad=l.pad,
                           act=act)
    return conv2d_ref(x, p["w"], p["b"], stride=l.stride, pad=l.pad, act=act)


def avgpool_all(x: jax.Array) -> jax.Array:
    return jnp.mean(x, axis=(1, 2), keepdims=True)


def maxpool(x: jax.Array, window: int = 3, stride: int = 2) -> jax.Array:
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")


def _pad_pool(x: jax.Array) -> jax.Array:
    """SqueezeNet v1.1 pool: pad bottom/right so 2x-stride covers the map."""
    return maxpool(jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)),
                           constant_values=-jnp.inf))


def mbv1_act(name: str) -> str | None:
    return None if name == "fc" else "relu6"


def mbv2_act(name: str) -> str | None:
    if name in ("fc",) or name.endswith("_project"):
        return None                 # linear bottleneck / classifier head
    return "relu6"


def sqz_act(name: str) -> str | None:
    return "relu"


ACT_OF: dict[str, Callable[[str], str | None]] = {
    "mobilenet_v1": mbv1_act,
    "mobilenet_v2": mbv2_act,
    "squeezenet": sqz_act,
}


# --------------------------------------------------------------------------
# Step / Program
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Step:
    """One execution unit: reads buffers from the env, writes buffers back.

    ``fn(params, env, collect)`` mutates ``env`` in place; ``collect`` (when
    not None) receives ``name -> shape`` entries at trace time.  ``layers``
    are the graph layers this step computes — the hook the scheduler's
    core-assignment uses.
    """

    name: str
    layers: tuple[str, ...]
    reads: tuple[str, ...]
    writes: tuple[str, ...]
    fn: Callable[[Params, Env, dict | None], None]

    def __repr__(self) -> str:  # keep traces readable
        return f"Step({self.name}, layers={list(self.layers)})"


@dataclasses.dataclass
class Program:
    """Ordered step list + the graph and activation map it was built from."""

    graph: LayerGraph
    steps: list[Step]
    act_of: Callable[[str], str | None]
    use_pallas: bool

    @functools.cached_property
    def sequential_steps(self) -> list[Step]:
        """The whole program as one exec group, fused as the runner fuses
        each of its groups (:func:`regroup_fused`)."""
        return regroup_fused(self, [self.steps])[0]

    def run(self, params: Params, x: jax.Array,
            collect: dict | None = None) -> jax.Array:
        """Sequential execution — the plain forward pass."""
        return run_steps(self.sequential_steps, params, x, collect)


def run_steps(steps: list[Step], params: Params, x: jax.Array,
              collect: dict | None = None) -> jax.Array:
    """Run ``steps`` in order on the input ``x``; returns ``out``."""
    env: Env = {"h": x}
    for s in steps:
        s.fn(params, env, collect)
    return env["out"]


# --------------------------------------------------------------------------
# step constructors (shared by the builders and the within-group fusion)
# --------------------------------------------------------------------------
def layer_step(graph: LayerGraph, name: str,
               act_of: Callable[[str], str | None],
               use_pallas: bool) -> Step:
    """Plain single-layer step on the main chain."""
    l = graph.layer(name)
    act = act_of(name)

    def fn(params, env, collect):
        env["h"] = run_layer(l, env["h"], params[name], act, use_pallas)
        if collect is not None:
            collect[name] = env["h"].shape

    return Step(name=name, layers=(name,), reads=("h",), writes=("h",),
                fn=fn)


def fused_step(graph: LayerGraph, kind: str, names: tuple[str, ...],
               act_of: Callable[[str], str | None]) -> Step:
    """One fused-block pallas_call (dw->pw or pw->dw->pw) as a step."""
    last = names[-1]

    if kind == "dw_pw":
        d, p = (graph.layer(nm) for nm in names)

        def fn(params, env, collect):
            pd, pp = params[d.name], params[p.name]
            env["h"] = fused_dw_pw(env["h"], pd["w"], pd["b"], pp["w"],
                                   pp["b"], stride=d.stride, pad=d.pad,
                                   dw_act=act_of(d.name),
                                   pw_act=act_of(p.name))
            if collect is not None:
                collect[last] = env["h"].shape

    elif kind == "pw_dw_pw":
        e, d, p = (graph.layer(nm) for nm in names)
        with_res = ("add" in p.fused and d.stride == 1 and e.C_i == p.C_o)

        def fn(params, env, collect):
            res = env["h"] if with_res else None
            pe, pd, pp = params[e.name], params[d.name], params[p.name]
            env["h"] = fused_inverted_residual(
                env["h"], pe["w"], pe["b"], pd["w"], pd["b"], pp["w"],
                pp["b"], res, stride=d.stride, pad=d.pad,
                exp_act=act_of(e.name), dw_act=act_of(d.name),
                proj_act=act_of(p.name))
            if collect is not None:
                collect[last] = env["h"].shape

    else:
        raise ValueError(f"unknown fused step kind {kind!r}")

    return Step(name="+".join(names), layers=tuple(names), reads=("h",),
                writes=("h",), fn=fn)


def head_step(graph: LayerGraph, name: str,
              act_of: Callable[[str], str | None], use_pallas: bool,
              avgpool_first: bool) -> Step:
    """Classifier head: optional global avgpool, the fc/conv layer, flatten
    into ``out``."""
    l = graph.layer(name)
    act = act_of(name)

    def fn(params, env, collect):
        h = env["h"]
        if avgpool_first:
            h = avgpool_all(h)
        h = run_layer(l, h, params[name], act, use_pallas)
        if collect is not None:
            collect[name] = h.shape
        env["out"] = h.reshape(h.shape[0], -1)

    return Step(name=name, layers=(name,), reads=("h",), writes=("out",),
                fn=fn)


# --------------------------------------------------------------------------
# model builders
# --------------------------------------------------------------------------
def _mbv1_steps(graph: LayerGraph, use_pallas: bool) -> list[Step]:
    steps = [layer_step(graph, l.name, mbv1_act, use_pallas)
             for l in graph.layers[:-1]]
    steps.append(head_step(graph, "fc", mbv1_act, use_pallas,
                           avgpool_first=True))
    return steps


def _mbv2_layer_step(graph: LayerGraph, name: str,
                     use_pallas: bool) -> Step:
    """MobileNet-v2 per-layer step with the residual stash/add protocol of
    the pre-refactor loop: ``_expand`` records the block input, ``_project``
    adds it back when the graph marks the block residual."""
    l = graph.layer(name)
    act = mbv2_act(name)
    stash = name.endswith("_expand")
    add = name.endswith("_project") and "add" in l.fused

    def fn(params, env, collect):
        h = env["h"]
        if stash:
            env["res"] = h          # block input, for the residual add
        out = run_layer(l, h, params[name], act, use_pallas)
        if add and "res" in env and env["res"].shape == out.shape:
            out = out + env["res"]
        env["h"] = out
        if collect is not None:
            collect[name] = out.shape

    reads = ("h", "res") if add else ("h",)
    writes = ("h", "res") if stash else ("h",)
    return Step(name=name, layers=(name,), reads=reads, writes=writes,
                fn=fn)


def _mbv2_steps(graph: LayerGraph, use_pallas: bool) -> list[Step]:
    steps = [_mbv2_layer_step(graph, l.name, use_pallas)
             for l in graph.layers[:-1]]
    steps.append(head_step(graph, "fc", mbv2_act, use_pallas,
                           avgpool_first=True))
    return steps


def _sqz_fire_steps(graph: LayerGraph, fire: str, use_pallas: bool,
                    pool_after: bool) -> list[Step]:
    sq_l = graph.layer(f"{fire}_squeeze")
    e1_l = graph.layer(f"{fire}_e1x1")
    e3_l = graph.layer(f"{fire}_e3x3")

    def sq_fn(params, env, collect):
        env["sq"] = run_layer(sq_l, env["h"], params[sq_l.name], "relu",
                              use_pallas)
        if collect is not None:
            collect[sq_l.name] = env["sq"].shape

    def e1_fn(params, env, collect):
        env["e1"] = run_layer(e1_l, env["sq"], params[e1_l.name], "relu",
                              use_pallas)
        if collect is not None:
            collect[e1_l.name] = env["e1"].shape

    def e3_fn(params, env, collect):
        e3 = run_layer(e3_l, env["sq"], params[e3_l.name], "relu",
                       use_pallas)
        if collect is not None:
            collect[e3_l.name] = e3.shape
        h = jnp.concatenate([env["e1"], e3], axis=-1)
        env["h"] = _pad_pool(h) if pool_after else h

    return [
        Step(f"{fire}_squeeze", (sq_l.name,), ("h",), ("sq",), sq_fn),
        Step(f"{fire}_e1x1", (e1_l.name,), ("sq",), ("e1",), e1_fn),
        Step(f"{fire}_e3x3", (e3_l.name,), ("sq", "e1"), ("h",), e3_fn),
    ]


def _sqz_steps(graph: LayerGraph, use_pallas: bool) -> list[Step]:
    # no dwconv layers: nothing fuses
    conv1 = graph.layer("conv1")

    def conv1_fn(params, env, collect):
        h = run_layer(conv1, env["h"], params["conv1"], "relu", use_pallas)
        if collect is not None:
            collect["conv1"] = h.shape
        env["h"] = _pad_pool(h)

    steps = [Step("conv1", ("conv1",), ("h",), ("h",), conv1_fn)]
    pool_after = {"fire3", "fire5"}        # v1.1 pool placement
    for i in range(2, 10):
        steps += _sqz_fire_steps(graph, f"fire{i}", use_pallas,
                                 pool_after=f"fire{i}" in pool_after)
    # conv10 head: conv -> global avgpool -> flatten (pool after the conv)
    conv10 = graph.layer("conv10")

    def conv10_fn(params, env, collect):
        h = run_layer(conv10, env["h"], params["conv10"], "relu", use_pallas)
        if collect is not None:
            collect["conv10"] = h.shape
        env["out"] = avgpool_all(h).reshape(h.shape[0], -1)

    steps.append(Step("conv10", ("conv10",), ("h",), ("out",), conv10_fn))
    return steps


_BUILDERS = {
    "mobilenet_v1": _mbv1_steps,
    "mobilenet_v2": _mbv2_steps,
    "squeezenet": _sqz_steps,
}


def build_program(name_or_graph: str | LayerGraph, *,
                  use_pallas: bool = False) -> Program:
    """Build the per-layer step program for one zoo model.

    ``use_pallas`` selects the kernel backend per layer.  Fusion is not
    the program's: :func:`regroup_fused` fuses within each exec group.

    Programs are pure (steps close over specs and read params per call),
    so the by-name path is cached: repeated forward calls don't re-fuse
    or re-allocate the step closures.
    """
    if isinstance(name_or_graph, str):
        return _cached_program(name_or_graph, use_pallas)
    return _build(name_or_graph, use_pallas)


@functools.lru_cache(maxsize=None)
def _cached_program(name: str, use_pallas: bool) -> Program:
    return _build(get_graph(name), use_pallas)


def _build(graph: LayerGraph, use_pallas: bool) -> Program:
    try:
        builder = _BUILDERS[graph.name]
    except KeyError:
        raise KeyError(f"no step builder for graph {graph.name!r}; "
                       f"choices: {sorted(_BUILDERS)}") from None
    steps = builder(graph, use_pallas)
    return Program(graph=graph, steps=steps, act_of=ACT_OF[graph.name],
                   use_pallas=use_pallas)


def regroup_fused(program: Program,
                  groups: list[list[Step]]) -> list[list[Step]]:
    """Within-group fusion: given per-layer steps partitioned into core
    groups, run the fusion matcher (``core.fusion.match_chain``) *inside*
    each group so dw->pw chains that the schedule kept on one core run as
    single fused pallas_calls, while chains the schedule split across
    cores stay per-layer.  The fused-block kernels are Pallas-only: on
    the XLA path the groups come back as they are.

    Only plain main-chain steps fuse (single-layer, reads==writes==("h",));
    branch/head/residual steps pass through untouched.
    """
    if not program.use_pallas:
        return groups
    graph, act_of = program.graph, program.act_of
    out: list[list[Step]] = []
    for grp in groups:
        fused: list[Step] = []
        i = 0
        while i < len(grp):
            chain = []
            for s in grp[i:i + 3]:
                if not _plain(s):
                    break
                chain.append(s.layers[0])
            m = match_chain(graph, chain)
            if m is not None:
                fused.append(fused_step(graph, m.kind, m.layers, act_of))
                i += len(m.layers)
            else:
                fused.append(grp[i])
                i += 1
        out.append(fused)
    return out


def _plain(s: Step) -> bool:
    return (len(s.layers) == 1 and s.reads == ("h",)
            and s.writes == ("h",))
