"""Operations and least HBM bytes of the work a cell asks for.

Layer sizes come from the benchmark's own reference layer tables
(``chipbench/models``), never from the program.  What the program decides
is how layers group into kernel calls: each step of a runner's exec plan
is one Pallas call, of the family its layers give it.

* FLOPs of a layer: 2 x multiply-adds (``conv``: ``2 Ho Wo K^2 Cin Cout``;
  ``dwconv``: ``2 Ho Wo K^2 C``; ``fc``: ``2 Cin Cout``).  Bias and
  activation are not counted.
* Least HBM bytes of a call: its input map, its output map and all of its
  weights and biases, read or written once (f32, 4 bytes each); a fused
  call keeps its intermediate maps on chip.
* Least time of a call: the larger of FLOPs over the peak FLOP/s and
  bytes over the HBM bandwidth.  Weights are read once per call, maps
  once per image.
"""
from __future__ import annotations

import dataclasses

from chipbench.refops import Layer

ITEMSIZE = 4                 # f32, the dtype every configuration serves


def layer_flops(l: Layer) -> int:
    """FLOPs of one layer for one image."""
    if l.op == "fc":
        return 2 * l.cin * l.cout
    per_out = l.k * l.k * (1 if l.op == "dwconv" else l.cin)
    return 2 * l.hout * l.hout * l.cout * per_out


def map_bytes(h: int, c: int) -> int:
    """Bytes of an ``h`` x ``h`` x ``c`` f32 map."""
    return h * h * c * ITEMSIZE


def weight_bytes(l: Layer) -> int:
    """Bytes of a layer's kernel and bias."""
    n = 1
    for d in l.weight_shape:
        n *= d
    return (n + l.cout) * ITEMSIZE


def family(layers: list[Layer]) -> str:
    """The kernel family of one call: a fused block when it spans layers,
    else the depthwise kernel or the conv/GEMM kernel."""
    if len(layers) > 1:
        return "fused_block"
    return "depthwise" if layers[0].op == "dwconv" else "conv_gemm"


@dataclasses.dataclass(frozen=True)
class CallWork:
    """One kernel call of one forward pass, per image and per call."""

    name: str
    family: str
    flops: int               # per image
    map_bytes: int           # per image: input map + output map
    weight_bytes: int        # per call

    def least_s(self, images: int, peak_flops: float, hbm_bw: float):
        """Least time of one call over ``images`` images, and which bound
        sets it (``"compute"`` or ``"memory"``)."""
        t_c = images * self.flops / peak_flops
        t_m = (images * self.map_bytes + self.weight_bytes) / hbm_bw
        return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def call_work(name: str, layers: list[Layer]) -> CallWork:
    """The work of one call over ``layers`` (one, or a fused chain)."""
    first, last = layers[0], layers[-1]
    return CallWork(
        name=name, family=family(layers),
        flops=sum(layer_flops(l) for l in layers),
        map_bytes=(map_bytes(first.hin, first.cin)
                   + map_bytes(last.hout, last.cout)),
        weight_bytes=sum(weight_bytes(l) for l in layers))


def plan_calls(runner, table: dict[str, Layer]) -> list[CallWork]:
    """One :class:`CallWork` per step of a runner's exec plan, in order."""
    out = []
    for group in runner.groups:
        for step in group.steps:
            missing = [n for n in step.layers if n not in table]
            if missing:
                raise ValueError(f"program layers {missing} are not in the "
                                 f"reference's layer table")
            out.append(call_work(step.name,
                                 [table[n] for n in step.layers]))
    return out


def model_flops(table: dict[str, Layer]) -> int:
    """FLOPs of one image's forward pass."""
    return sum(layer_flops(l) for l in table.values())


def roofline_share(run, family_name: str, kernels) -> float | None:
    """Least time over device time of one kernel family's calls in the
    traced window, in %.  The calls are counted from the trace's events;
    with several models they are split by each model's share of the
    requests served.  None when the trace holds no such call."""
    if run.trace is None:
        return None
    n_events, device_s = run.trace.kernel(kernels)
    if n_events == 0 or device_s <= 0:
        return None
    served: dict[str, int] = {}
    for r in run.ok_in_window():
        served[r.model] = served.get(r.model, 0) + 1
    total = sum(served.values())
    per_fwd_calls, per_fwd_least = 0.0, 0.0
    bound = {"compute": 0.0, "memory": 0.0}
    for model, calls in run.calls.items():
        share = served.get(model, 0) / total if total else 0.0
        for c in calls:
            if c.family != family_name:
                continue
            t, which = c.least_s(run.batch, run.peaks["bf16_flop_per_s"],
                                 run.peaks["hbm_byte_per_s"])
            per_fwd_calls += share
            per_fwd_least += share * t
            bound[which] += share * t
    if per_fwd_calls == 0:
        return None
    forwards = n_events / per_fwd_calls
    import sys

    print(f"[chipbench] {family_name}: {n_events} calls in "
          f"{device_s} device s, least time bound by memory for "
          f"{100 * bound['memory'] / per_fwd_least} % of it",
          file=sys.stderr)
    return 100.0 * forwards * per_fwd_least / device_s
