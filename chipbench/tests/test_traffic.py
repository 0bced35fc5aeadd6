"""The wall-clock generator: repeatable per seed, the same work for every
seed, and latency measured from the due time."""
import time

import numpy as np
import pytest

from chipbench import harness, traffic as gen

OPEN = {"loop": "open", "arrival": "poisson", "rate_img_s": 400,
        "images_per_request": 1, "payload_pool": 4,
        "mix": {"a": 0.5, "b": 0.3, "c": 0.2}}


def schedule(seed, seconds=2.0, mix=OPEN):
    """The open-loop schedule of ``seed``."""
    _, w, _ = gen.seed_words(seed, 3)
    return gen.open_schedule(mix, ["a", "b", "c"],
                             np.random.default_rng(w), seconds)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 40 + 3])
def test_schedule_repeats_for_a_seed(seed):
    """One seed, one schedule."""
    assert schedule(seed) == schedule(seed)


def test_seeds_change_the_order_not_the_work():
    """Two seeds: the same gaps and model counts, in another order."""
    a, b = schedule(1), schedule(2 ** 33 + 1)
    assert a != b
    assert len(a) == len(b) == 800
    # the same gaps in another order (the first due time is 0)
    assert np.allclose(sorted(np.diff([x.due for x in a])
                              .tolist() + [2.0 - a[-1].due]),
                       sorted(np.diff([x.due for x in b])
                              .tolist() + [2.0 - b[-1].due]))
    count = lambda s: {m: sum(x.model == m for x in s) for m in "abc"}  # noqa
    assert count(a) == count(b) == {"a": 400, "b": 240, "c": 160}
    assert all(0 <= x.due < 2.0 for x in a)


def test_seed_words_use_every_bit():
    """Seeds past 32 bits stay distinct; negative seeds are refused."""
    assert gen.seed_words(3) != gen.seed_words(3 + 2 ** 40)
    with pytest.raises(ValueError):
        gen.seed_words(-1)


class StubSystem:
    """Serves each request in one step of ``step_s``; one step, the
    ``stall_at``-th, takes ``stall_s`` instead: a stall of the loop."""

    def __init__(self, step_s=1e-4, stall_at=50, stall_s=0.3):
        self.queue, self.rid = [], 0
        self.step_s, self.stall_at, self.stall_s = step_s, stall_at, stall_s
        self.steps = 0

    @property
    def has_work(self):
        """True while a request waits."""
        return bool(self.queue)

    def submit(self, payload, model):
        """Queue one request."""
        from repro.serving.api import RequestMetrics

        self.rid += 1
        self.queue.append(RequestMetrics(rid=self.rid,
                                         submitted_at=time.perf_counter(),
                                         model=model))
        return self.rid

    def step(self):
        """Serve every queued request, or stall once."""
        from repro.serving.api import Completion, Ticket

        self.steps += 1
        time.sleep(self.stall_s if self.steps == self.stall_at
                   else self.step_s)
        out = []
        for m in self.queue:
            m.started_at = m.started_at or time.perf_counter()
            m.finished_at = time.perf_counter()
            out.append(Completion(Ticket(m.rid, m.submitted_at), None, m))
        self.queue = []
        return out


def test_a_stall_of_the_loop_shows_in_p95_from_the_due_time():
    """Requests due while the loop is stalled are submitted late; their
    latency counts the wait from when they were due, which a latency
    taken from submit would hide."""
    sched = schedule(11, seconds=1.0,
                     mix=dict(OPEN, rate_img_s=200, mix={"a": 1}))
    pool = [np.zeros((1, 2, 2, 3), np.float32)] * 4
    span = harness._no_span
    runs = {}
    for stall in (0.0, 0.3):
        sys_ = StubSystem(stall_s=stall)
        t0, window_s, recs, by_rid = harness.drive_open(
            sys_, sched, pool, 1.0, span)
        harness.drain(sys_, by_rid, t0, span)
        run = harness.Run(cell={}, config={}, traffic={"loop": "open"},
                          chips=1, peaks={}, setup_s=0.0,
                          window_s=window_s, records=recs,
                          flops_per_image={}, calls={})
        for r in recs:
            r.status = r.status or "missing"
        p95 = harness.percentile(run.latencies_ms(), 95)
        from_submit = harness.percentile(
            [(r.finished - r.submitted) * 1e3 for r in recs], 95)
        runs[stall] = (p95, from_submit)
    assert runs[0.0][0] < 20
    # >= 5% of the requests were due during the 300 ms stall
    assert runs[0.3][0] > 100
    assert runs[0.3][1] < 50
