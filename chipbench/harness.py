"""One run of one cell: set-up, the measured window, the check, the line.

The run builds the cell's configuration through the program's own serving
objects (``chipbench/systems``) with weights made on the device from the
seed, warms every exec group at the cell's batch shape, drives the engine
for ``seconds`` on the traffic's schedule (``chipbench/traffic.py``),
drains what is in flight, and then checks every output served in the
window against the plain reference (``chipbench/models``), run after the
program's state is freed.  Latency runs from each request's *due* time, so
a stall of the driving loop shows in it.

With ``control`` the run puts the reference, computed at that lower
precision, in the program's place: every answer of the window is replaced
by the control's answer to the same payload before the check, which must
then come out false.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import spec, trace_reduce, traffic as gen, work
from chipbench.refops import init_params

DRAIN_S = 60.0               # an answer may come this late after the close


@dataclasses.dataclass
class Rec:
    """One request of the window."""

    model: str
    pool_idx: int
    images: int
    due: float               # seconds after the window opened
    submitted: float | None = None
    started: float | None = None
    finished: float | None = None
    status: str | None = None
    output: object = None


@dataclasses.dataclass
class Run:
    """What a metric's ``read(run)`` sees."""

    cell: dict
    config: dict
    traffic: dict
    chips: int
    peaks: dict
    setup_s: float
    window_s: float
    records: list[Rec]
    flops_per_image: dict[str, int]
    calls: dict[str, list[work.CallWork]]
    host_advance_s: float | None = None
    dispatches: int | None = None
    trace: trace_reduce.Summary | None = None

    @property
    def open_loop(self) -> bool:
        """True for an open-loop traffic mix."""
        return self.traffic["loop"] == "open"

    @property
    def batch(self) -> int:
        """Images per request."""
        return self.traffic["images_per_request"]

    def ok_in_window(self) -> list[Rec]:
        """Requests completed ``ok`` by the window's close."""
        return [r for r in self.records if r.status == "ok"
                and r.finished is not None and r.finished <= self.window_s]

    def latencies_ms(self) -> list[float]:
        """Due-to-output latency of every request served."""
        return [(r.finished - r.due) * 1e3 for r in self.records
                if r.status == "ok" and r.finished is not None]


def percentile(xs, q: float) -> float | None:
    """Linear-interpolated percentile ``q`` (0-100); None when empty."""
    if not len(xs):
        return None
    s = sorted(xs)
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Backend compilations and their seconds, and persistent-cache hits,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0

        def on_duration(event, secs, **_):
            if event == BACKEND_COMPILE:
                self.compiles += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def stop(self) -> None:
        """Stop listening."""
        import jax

        on_duration, on_event = self._listeners
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def use_compile_cache(path: str) -> None:
    """JAX's persistent compilation cache at ``path`` for every program,
    however fast it compiled, with no size limit: a limit turns on
    eviction, whose access-time files an entry written without one lacks,
    and every later write then fails."""
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def device_info(devices) -> dict:
    """Platform, kind and count of ``devices`` as JAX reports them."""
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def make_params(bench, config: dict, key_word: int) -> dict:
    """Every model's weights from the seed, each pytree in one jitted
    call on the default device."""
    import jax

    out = {}
    for i, (model, arch) in enumerate(sorted(config["models"].items())):
        key = jax.random.fold_in(jax.random.PRNGKey(key_word), i)
        layers = bench.model(arch["family"]).layers(arch_at(config, arch))
        out[model] = jax.jit(functools.partial(init_params, layers))(key)
    return jax.block_until_ready(out)


def arch_at(config: dict, arch: dict) -> dict:
    """A model's architecture at the configuration's image size."""
    return dict(arch, image_size=config["image_size"])


class WindowMarks:
    """The tiny device program (``trace_reduce.MARK``) that marks a traced
    window's ends in the device trace, compiled at set-up; :meth:`mark`
    runs it and logs the host time around it."""

    def __init__(self, device, host: trace_reduce.HostLog):
        import jax
        import jax.numpy as jnp

        def mark(x):
            return x + 1.0
        mark.__name__ = mark.__qualname__ = trace_reduce.MARK

        self.host = host
        self.fn = jax.jit(mark)
        self.x = jax.device_put(jnp.zeros((8, 128), jnp.float32), device)
        self.fn(self.x).block_until_ready()

    def mark(self, name: str) -> None:
        """Run the mark to completion; log it as ``name``."""
        t = time.perf_counter_ns()
        self.fn(self.x).block_until_ready()
        self.host.marks[name] = (t, time.perf_counter_ns())


# --------------------------------------------------------------------------
# the measured window
# --------------------------------------------------------------------------
def _wait_until(t_abs: float, span) -> None:
    """Sleep to ``t_abs`` (perf_counter), spinning for the last 0.5 ms."""
    with span("gen.sleep"):
        while True:
            left = t_abs - time.perf_counter()
            if left <= 0:
                return
            if left > 1e-3:
                time.sleep(left - 5e-4)


def _file(done, by_rid: dict[int, Rec], t0: float) -> None:
    for c in done:
        rec = by_rid.pop(c.ticket.rid, None)
        if rec is None:      # a warm-up request
            continue
        m = c.metrics
        rec.status = m.status
        rec.started = None if m.started_at is None else m.started_at - t0
        rec.finished = None if m.finished_at is None else m.finished_at - t0
        rec.output = c.output


def drive_open(system, schedule, pool, seconds: float, span):
    """Submit each request when due, step the engine whenever it has
    work, and sleep to the next due time when it has none."""
    recs = [Rec(a.model, a.pool_idx, pool[0].shape[0], a.due)
            for a in schedule]
    by_rid: dict[int, Rec] = {}
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        if i < len(recs) and recs[i].due <= now:
            with span("submit"):
                while i < len(recs) and recs[i].due <= now:
                    r = recs[i]
                    rid = system.submit(pool[r.pool_idx], r.model)
                    r.submitted = time.perf_counter() - t0
                    by_rid[rid] = r
                    i += 1
        if now >= seconds:
            break
        if system.has_work:
            _file(system.step(), by_rid, t0)
        else:
            nxt = recs[i].due if i < len(recs) else seconds
            _wait_until(t0 + min(nxt, seconds), span)
    return t0, time.perf_counter() - t0, recs, by_rid


def drive_closed(system, source, pool, outstanding: int, seconds: float,
                 span):
    """``outstanding`` clients: each sends its next request the moment
    its last one completes, until the window closes."""
    recs: list[Rec] = []
    by_rid: dict[int, Rec] = {}
    t0 = time.perf_counter()

    def send(now):
        model, idx = source()
        r = Rec(model, idx, pool[0].shape[0], now)
        rid = system.submit(pool[idx], model)
        r.submitted = time.perf_counter() - t0
        by_rid[rid] = r
        recs.append(r)

    with span("submit"):
        for _ in range(outstanding):
            send(0.0)
    while True:
        done = system.step()
        now = time.perf_counter() - t0
        _file(done, by_rid, t0)
        if now >= seconds:
            break
        if done:
            with span("submit"):
                for _ in done:
                    send(now)
    return t0, now, recs, by_rid


def drain(system, by_rid, t0: float, span) -> None:
    """Step until nothing is in flight, or ``DRAIN_S`` past the close."""
    close = time.perf_counter()
    with span("drain"):
        while system.has_work and time.perf_counter() - close < DRAIN_S:
            _file(system.step(), by_rid, t0)


# --------------------------------------------------------------------------
# the check
# --------------------------------------------------------------------------
def rel_err(out: np.ndarray, ref: np.ndarray) -> float:
    """max |served - reference| over max |reference|, one request."""
    if out.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


def reference_logits(bench, config: dict, key_word: int, pool, wanted,
                     precision: str = "highest") -> dict:
    """``{(model, pool_idx): logits}`` of the plain reference, with its
    own weights made from the seed, one request payload per call."""
    import jax

    params = make_params(bench, config, key_word)
    out = {}
    for model in sorted({m for m, _ in wanted}):
        arch = arch_at(config, config["models"][model])
        fwd = jax.jit(functools.partial(bench.model(arch["family"]).forward,
                                        arch=arch, precision=precision))
        for m, idx in sorted(wanted):
            if m == model:
                out[(m, idx)] = np.asarray(fwd(params[m], pool[idx]))
    return out


def check(config: dict, recs: list[Rec], refs: dict) -> dict:
    """The numbers compared, each beside its limit: the widest relative
    error per model, and the requests that never came."""
    limits = config["check"]["max_rel_err"]
    checks = {}
    missing = 0
    worst: dict[str, float] = {m: 0.0 for m in config["models"]
                               if any(r.model == m for r in recs)}
    for r in recs:
        if r.status != "ok" or r.output is None:
            missing += 1
            continue
        e = rel_err(r.output, refs[(r.model, r.pool_idx)])
        w = worst[r.model]
        if w == w and not e <= w:            # a NaN, once read, stays
            worst[r.model] = e
    for m, e in worst.items():
        checks[f"max_rel_err.{m}"] = {"value": e, "limit": limits[m]}
    checks["missing"] = {"value": missing, "limit": 0}
    return checks


def passed(checks: dict) -> bool:
    """True when every number compared is within its limit (NaN fails)."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def build(bench, config: dict, devices, key_word: int, span):
    """The program's serving objects for ``config`` with the seed's
    weights, and the reference layer table and exec-plan calls of each
    model."""
    params = make_params(bench, config, key_word)
    system = bench.system(config["system"]).build(config, params, devices,
                                                  span)
    del params
    tables = {m: {l.name: l for l in
                  bench.model(a["family"]).layers(arch_at(config, a))}
              for m, a in config["models"].items()}
    calls = {m: work.plan_calls(r, tables[m])
             for m, r in system.runners.items()}
    return system, tables, calls


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def run_cell(root, workload: str, seed: int, seconds: float, traced: bool,
             *, t_start: float, require_tpu: bool = True,
             home=spec.HOME, trace_dir=None,
             control: str | None = None) -> dict:
    """Run ``workload`` once and return the result line's object.  With
    ``control`` (a precision of ``refops.PRECISIONS``) the check reads the
    control's answers in place of the program's."""
    bench = spec.Bench(root, home)
    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    import jax

    devices = jax.devices()
    dev = device_info(devices)
    if require_tpu and dev["platform"] != "tpu":
        raise spec.Refused(f"JAX platform is {dev['platform']!r} "
                           f"({dev['kind']}), not 'tpu': the benchmark "
                           f"measures the chip and has no fallback")
    if dev["count"] < cell["chips"]:
        raise spec.Refused(f"{dev['count']} device(s); the cell "
                           f"{workload!r} asks for {cell['chips']}")
    peaks = bench.peaks(dev["kind"])
    devices = devices[:cell["chips"]]
    w_weights, w_traffic, _ = gen.seed_words(seed, 3)
    rng = np.random.default_rng(w_traffic)
    clock = CompileClock()
    host = trace_reduce.HostLog() if traced else None
    span = host.span if traced else _no_span
    system, tables, calls = build(bench, config, devices, w_weights, span)
    pool = gen.payload_pool(mix, config["image_size"], rng)
    models = sorted(config["models"])
    if mix["loop"] == "open":
        schedule = gen.open_schedule(mix, models, rng, seconds)
    else:
        source = gen.ClosedSource(mix, models, rng)
    system.warm(pool[0])

    tdir = None
    if traced:
        marks = WindowMarks(devices[0], host)
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-",
                                dir=trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0        # the device alone (module doc)
        jax.profiler.start_trace(tdir, profiler_options=opts)
        marks.mark(trace_reduce.MARK_OPEN)
    compiles0, advance0 = clock.compiles, system.host_advance_s
    dispatch0 = system.dispatches
    gc.collect()
    gc.freeze()          # set-up's objects stay out of the window's scans
    setup_s = time.perf_counter() - t_start
    if mix["loop"] == "open":
        t0, window_s, recs, by_rid = drive_open(system, schedule, pool,
                                                seconds, span)
    else:
        t0, window_s, recs, by_rid = drive_closed(
            system, source, pool, mix["outstanding"], seconds, span)
    if traced:
        marks.mark(trace_reduce.MARK_CLOSE)
    window_compiles = clock.compiles - compiles0
    advance_s = (None if advance0 is None
                 else system.host_advance_s - advance0)
    dispatches = (None if dispatch0 is None
                  else system.dispatches - dispatch0)
    drain(system, by_rid, t0, span)
    gc.unfreeze()
    summary = None
    if traced:
        jax.profiler.stop_trace()
        ids = {d.id for d in devices}
        summary = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find(tdir)), host, ids)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
        else:
            host.dump(pathlib.Path(tdir) / "host.json")
    clock.stop()
    peak_mem = _memory_peak(devices)

    # the program's outputs to the host, its state freed, then the
    # reference
    for r in recs:
        if r.output is not None:
            r.output = np.asarray(r.output)
    del system
    gc.collect()
    wanted = {(r.model, r.pool_idx) for r in recs if r.output is not None}
    refs = reference_logits(bench, config, w_weights, pool, wanted)
    checks = program_checks = check(config, recs, refs)
    if control:
        low = reference_logits(bench, config, w_weights, pool, wanted,
                               precision=control)
        for r in recs:
            if r.output is not None:
                r.output = low[(r.model, r.pool_idx)]
        checks = check(config, recs, refs)

    run = Run(cell=cell, config=config, traffic=mix, chips=cell["chips"],
              peaks=peaks, setup_s=setup_s, window_s=window_s,
              records=recs,
              flops_per_image={m: work.model_flops(t)
                               for m, t in tables.items()},
              calls=calls, host_advance_s=advance_s, dispatches=dispatches,
              trace=summary)
    metrics = {}
    for entry in bench.metrics_for(cell, traced):
        value = bench.metric(entry["name"]).read(run, entry["name"])
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    dev["memory_peak_bytes"] = peak_mem
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
    failed = sum(1 for r in recs if r.status != "ok")
    _log(f"{workload} seed={seed} attempted={len(recs)} failed={failed} "
         f"window_s={window_s} setup_s={setup_s} "
         f"compiles_in_window={window_compiles} "
         f"compile_s={clock.seconds} cache_hits={clock.cache_hits}")
    if mix["loop"] == "open":
        lat = run.latencies_ms()
        _log("latency ms: " + " ".join(
            f"p{q}={percentile(lat, q)}" for q in (50, 90, 95, 99, 100)))
    if summary is not None:
        for c in summary.chips:
            _log(f"tpu{c.id}: idle {100 * summary.idle_share(c)} % of "
                 f"{summary.window_s} s")
    checks["compiles_in_window"] = {"value": window_compiles, "limit": 0}
    line = {"correct": passed(checks), "attempted": len(recs),
            "failed": failed, "metrics": metrics, "device": dev}
    if summary is not None:
        line["breakdown"] = {"device_ops": summary.top_ops(),
                             "idle_gaps": summary.idle_gaps()}
    if control:
        line["control"] = control
        line["program_checks"] = program_checks
    line["checks"] = checks
    return line


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _no_span(label: str):
    """An untraced run logs no spans."""
    return contextlib.nullcontext()
