"""The program's spans in a run: idle time by the innermost span, each
exec group's device time and its part outside the kernels, the three
quantities read from the spans, and a run with the spans on."""
import pathlib

import pytest
from jax.profiler import ProfileData

from chipbench import harness, program_spans as ps, trace_reduce

DATA = pathlib.Path(__file__).resolve().parents[1] / "data"
SEED = 2 ** 31 + 91


def _op(name, key):
    return (f'event_metadata {{ key: {key} value {{ id: {key} '
            f'name: "%{name} = f32[1]{{0}} op()" }} }}')


# window 1000..11000 ns between the marks on TPU 0.  Two exec-group
# programs run: g00_c 2000..4000 (a conv kernel 2000..3000, a copy and a
# pad 500 ns each) and g01_p 6000..7500 (a depthwise kernel 6000..7000, a
# slice 7000..7500).  Idle: 1000..2000, 4000..6000, 7500..11000.  The host
# runs 100000 ns ahead of the trace; on the trace's clock the harness is
# in engine.advance 1500..6500 and in gen.sleep 8000..12000, the program
# in slot.dispatch 3600..6400, with a group.call 4500..5500 inside it,
# and collects garbage 9000..9500
SYNTHETIC = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 2000000 duration_ps: 1000000 }}
    events {{ metadata_id: 2 offset_ps: 3000000 duration_ps: 500000 }}
    events {{ metadata_id: 3 offset_ps: 3500000 duration_ps: 500000 }}
    events {{ metadata_id: 4 offset_ps: 6000000 duration_ps: 1000000 }}
    events {{ metadata_id: 5 offset_ps: 7000000 duration_ps: 500000 }} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 8 offset_ps: 500000 duration_ps: 500000 }}
    events {{ metadata_id: 6 offset_ps: 2000000 duration_ps: 2000000 }}
    events {{ metadata_id: 7 offset_ps: 6000000 duration_ps: 1500000 }}
    events {{ metadata_id: 8 offset_ps: 11000000 duration_ps: 500000 }} }}
  {_op("conv2d_implicit_gemm.1", 1)}
  {_op("copy.2", 2)}
  {_op("pad.1", 3)}
  {_op("depthwise_conv2d.1", 4)}
  {_op("slice.3", 5)}
  event_metadata {{ key: 6 value {{ id: 6 name: "jit_dualcore_g00_c(17)" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "jit_dualcore_g01_p(18)" }} }}
  event_metadata {{ key: 8 value {{ id: 8
    name: "jit_chipbench_window_mark(5)" }} }} }}
"""
HOST = trace_reduce.HostLog(
    spans=[(101500, 106500, "engine.advance"),
           (108000, 112000, "gen.sleep")],
    marks={trace_reduce.MARK_OPEN: (100400, 101100),
           trace_reduce.MARK_CLOSE: (110900, 111600)})
PROGRAM = [(103600, 106400, "slot.dispatch", None, None, None),
           (104500, 105500, "group.call", 0, 3, 1),
           (109000, 109500, "gc", None, None, None)]


@pytest.fixture(scope="module")
def synthetic():
    """The synthetic trace above."""
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))


def test_idle_time_goes_to_the_innermost_span(synthetic):
    """A program span before the harness span around it, the innermost
    program span first, ``other`` where nothing covers the gap; every
    idle nanosecond counted once."""
    red = ps.reduce_program(synthetic, HOST, PROGRAM)
    idle = {k: v * 1e9 for k, v in red["idle_gaps"]}
    assert idle == pytest.approx({"other": 500 + 500,
                                  "engine.advance": 500,
                                  "slot.dispatch": 500 + 500,
                                  "group.call": 1000,
                                  "gen.sleep": 3000 - 500, "gc": 500})
    assert sum(idle.values()) == pytest.approx(10000 - 3500)


def test_device_time_per_exec_group_program(synthetic):
    """Runs, device time and the time of ops that are not kernels, per
    ``jit_dualcore_g*`` program; the window marks are not a group."""
    red = ps.reduce_program(synthetic, HOST, PROGRAM)
    assert red["groups"] == [
        {"module": "jit_dualcore_g00_c", "runs": 1, "device_ns": 2000,
         "non_kernel_ns": 1000},
        {"module": "jit_dualcore_g01_p", "runs": 1, "device_ns": 1500,
         "non_kernel_ns": 500}]


def test_without_program_spans_the_recorded_window_splits_as_before():
    """On the window recorded on the chip, with no program spans, idle
    time splits over the harness's spans exactly as the benchmark's own
    reduction splits it; that run's programs predate the group names."""
    pd = trace_reduce.load(DATA / "mbv2-poisson-1s.xplane.pb.gz")
    host = trace_reduce.HostLog.read(DATA / "mbv2-poisson-1s.host.json")
    red = ps.reduce_program(pd, host, [])
    (chip,) = trace_reduce.reduce(pd, host).chips
    assert dict(red["idle_gaps"]) == pytest.approx(
        {k: v * 1e-9 for k, v in chip.idle_by_label.items()}, rel=1e-12)
    assert red["groups"] == []


SPANS = [(0, 50_000_000, "slot.dispatch", None, None, None),
         (1_000_000, 1_400_000, "group.call", 0, 0, 0),
         (2_000_000, 2_200_000, "group.call", 0, 1, 1),
         (3_000_000, 3_100_000, "request.admit", 0, 1, None),
         (60_000_000, 260_000_000, "slot.retire", None, None, None),
         (70_000_000, 250_000_000, "gc", 4, None, None),
         (-900, -100, "group.call", None, 0, 0),         # before the window
         (300_000_000, None, "slot.dispatch", None, None, None)]  # open


def test_quantities_read_from_the_spans():
    """Mean group call, stalls and self time over the window."""
    assert ps.dispatch_call_us(SPANS, 0, 10 ** 9) == pytest.approx(300.0)
    assert ps.stall_s(SPANS, 0, 10 ** 9) == pytest.approx(0.2)
    assert ps.stall_s(SPANS, 0, 50_000_000) == 0.0
    assert ps.stall_s([], 0, 1) is None
    assert ps.dispatch_call_us(SPANS, 4_000_000, 5_000_000) is None
    table = ps.span_table(SPANS, 0, 10 ** 9)
    assert table["slot.dispatch"]["n"] == 1
    assert table["slot.dispatch"]["self_s"] == pytest.approx(0.0493)
    assert table["slot.retire"]["self_s"] == pytest.approx(0.02)
    assert table["group.call"] == {"n": 2, "s": pytest.approx(6e-4),
                                   "self_s": pytest.approx(6e-4)}


def test_gc_pause_is_the_window_part_of_the_histogram():
    """Collections between the two snapshots, every generation."""
    def snap(series):
        return {"histograms": {"gc_pause_seconds": {"series": series}}}

    before = snap({"generation=0": {"sum": 0.5, "n": 10}})
    after = snap({"generation=0": {"sum": 0.502, "n": 14},
                  "generation=2": {"sum": 0.03, "n": 1}})
    ms, by_gen = ps.gc_pause(before, after)
    assert ms == pytest.approx(32.0)
    assert by_gen == {"generation=0": 4, "generation=2": 1}
    assert ps.gc_pause({"histograms": {}}, {"histograms": {}}) is None


def test_a_run_with_the_program_spans_on(small_bench):
    """A short run at the test size records the engine's spans and the
    collections, stays correct, and leaves the harness as it found it."""
    root, home = small_bench(traffic={"mbv2-poisson": {"rate_img_s": 10}})
    build = harness.build
    line = ps.run(root, "mbv2-poisson", SEED, 1.0, False, t_start=0.0,
                  require_tpu=False, home=home)
    assert harness.build is build
    assert line["correct"], line["checks"]
    prog = line["program"]
    assert {"slot.dispatch", "group.call", "request.admit", "slot.retire",
            "request.materialize"} <= set(prog["spans"])
    # 16 exec groups; a request admitted late runs the rest after the close
    admitted = prog["spans"]["request.admit"]["n"]
    assert 0 < prog["spans"]["group.call"]["n"] <= 16 * admitted
    assert prog["dispatch_call_us"] > 0
    assert prog["stall_s"] is not None and prog["gc_pause_ms"] is not None
