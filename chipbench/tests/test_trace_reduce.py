"""The reduction from a profiler trace to busy time, idle time by host
annotation and per-kernel device time."""
import pathlib

import pytest
from jax.profiler import ProfileData

from chipbench import trace_reduce

DATA = pathlib.Path(__file__).resolve().parents[1] / "data"

# window 1000..11000 ns, between the opening mark (500..1000) and the
# closing one (11000..11500) on TPU 0; TPU 0 runs _dw_kernel 2000..4000
# and fusion.3 6000..7000, plus an op wholly before the window; TPU 1 runs
# one _dw_kernel 1500..3500.  The host log runs 100000 ns ahead of the
# trace's clock: the host is in engine.advance 4000..5500 and in gen.sleep
# 9000..12000 on the trace's clock
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 300000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 500000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 9000000 }
    events { metadata_id: 4 offset_ps: 11000000 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "_dw_kernel" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.3" } }
  event_metadata { key: 3 value { id: 3 name: "jit_group_fn(17)" } }
  event_metadata { key: 4 value { id: 4
    name: "jit_chipbench_window_mark(5)" } } }
planes { id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1500000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "_dw_kernel" } } }
"""
HOST = trace_reduce.HostLog(
    spans=[(104000, 105500, "engine.advance"),
           (109000, 112000, "gen.sleep")],
    marks={trace_reduce.MARK_OPEN: (100400, 101100),
           trace_reduce.MARK_CLOSE: (110900, 111600)})


@pytest.fixture(scope="module")
def synthetic():
    """The synthetic trace above."""
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))


def test_busy_idle_and_kernels_of_one_chip(synthetic):
    """Busy union, idle split and kernel sums of TPU 0."""
    s = trace_reduce.reduce(synthetic, HOST, {0})
    assert s.window_ns == 10000
    (chip,) = s.chips
    assert chip.busy_ns == 3000
    assert s.idle_share() == pytest.approx(0.7)
    assert s.kernel(["_dw_kernel"]) == (1, pytest.approx(2e-6))
    # gaps 1000-2000, 4000-6000, 7000-11000 split over the host spans
    assert chip.idle_by_label == {"other": 1000 + 500 + 2000,
                                  "engine.advance": 1500,
                                  "gen.sleep": 2000}
    assert s.idle_gaps()[0] == ["other", pytest.approx(3.5e-6)]
    assert s.top_ops() == [["_dw_kernel", pytest.approx(2e-6)],
                           ["fusion.3", pytest.approx(1e-6)]]


def test_two_chips_are_averaged(synthetic):
    """Busy time is the mean over chips; kernel sums add."""
    s = trace_reduce.reduce(synthetic, HOST)
    assert [c.id for c in s.chips] == [0, 1]
    assert s.busy_s == pytest.approx((3000 + 2000) / 2 * 1e-9)
    assert s.kernel(["_dw_kernel"]) == (2, pytest.approx(4e-6))


def test_no_window_mark_is_an_error():
    """A trace without the closing mark is refused."""
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(SYNTHETIC.replace(
            "events { metadata_id: 4 offset_ps: 11000000 duration_ps: "
            "500000 }", "")))
    with pytest.raises(RuntimeError, match="window mark"):
        trace_reduce.reduce(pd, HOST)


def test_host_log_round_trips(tmp_path):
    """The host log a traced run keeps beside its trace reads back."""
    HOST.dump(tmp_path / "host.json")
    assert trace_reduce.HostLog.read(tmp_path / "host.json") == HOST


def test_op_names_drop_the_hlo_text():
    """Device events carry the whole HLO instruction; ops are named by the
    instruction's name without its numeric suffix."""
    assert trace_reduce.op_name(
        '%depthwise_conv2d.3 = f32[1,56,56,144]{3,2,1,0} custom-call('
        'f32[1,58,58,144]{3,2,1,0} %pad.1), custom_call_target='
        '"tpu_custom_call"') == "depthwise_conv2d"
    assert trace_reduce.op_name("%pad.2.clone = f32[1]{0} pad()") == "pad"
    assert trace_reduce.op_name("%copy-done = f32[1]{0} copy-done()") == \
        "copy-done"
    assert trace_reduce.op_name("fusion.3") == "fusion.3"


def test_a_window_recorded_on_the_chip():
    """One second of ``mbv2-poisson`` (88 img/s) traced on a TPU v5e, with
    the run's own host log: the reduction gives what that run printed
    (busy 0.049636922 s of a 1.0596910080000002 s window), busy and idle
    time add up to the window, the idle time splits over the run's host
    spans, and the kernels appear under the names the roofline reader
    matches, in the exec plan's proportions (per forward 30 conv/GEMM, 11
    depthwise and 6 fused calls)."""
    from chipbench.metrics.roofline import KERNELS

    s = trace_reduce.reduce(
        trace_reduce.load(DATA / "mbv2-poisson-1s.xplane.pb.gz"),
        trace_reduce.HostLog.read(DATA / "mbv2-poisson-1s.host.json"))
    (chip,) = s.chips
    assert s.busy_s == pytest.approx(0.049636922)
    assert s.window_s == pytest.approx(1.0596910080000002)
    assert sum(chip.idle_by_label.values()) == pytest.approx(
        s.window_ns - chip.busy_ns)
    assert set(chip.idle_by_label) <= {*trace_reduce.LABELS, "other"}
    assert chip.idle_by_label["other"] < 0.1 * s.window_ns
    calls = {f: s.kernel(names)[0] for f, names in KERNELS.items()}
    forwards = calls["depthwise"] / 11
    assert forwards > 50
    assert calls == {"conv_gemm": 30 * forwards, "depthwise": 11 * forwards,
                     "fused_block": 6 * forwards}
