"""The knee of a rate sweep: the highest rate below which every rate was
served with no growing backlog."""
from chipbench.calibrate import knee_of


def row(rate, done, waiting, requests=1000):
    """One swept rate's reading."""
    return {"rate_img_s": rate, "throughput_img_s": done,
            "not_admitted_at_close": waiting, "requests": requests}


def test_knee_is_the_last_rate_served_in_full():
    """A rate that completes under 98% of what it offered ends the
    sweep, and so does a backlog at the close."""
    assert knee_of([row(100, 100, 0), row(200, 199, 1),
                    row(300, 280, 0), row(400, 400, 0)]) == 200
    assert knee_of([row(100, 100, 0), row(200, 200, 40)]) == 100
    assert knee_of([row(100, 90, 0)]) is None
