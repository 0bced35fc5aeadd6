"""The one traffic generator: reads a mix file of parameters, draws from the
seed.

A traffic file (``chipbench/traffic/<name>.json``) states:

* ``loop``: ``"open"`` (requests due on a wall-clock schedule, whether or
  not earlier ones finished: independent users) or ``"closed"``
  (``outstanding`` clients, each sending its next request when its last
  one completes);
* ``arrival`` (open loop): ``"poisson"``, at ``rate_img_s`` images per
  second;
* ``images_per_request``: the batch of every request;
* ``mix`` (optional): request shares per model; without it, every model
  of the configuration in equal shares;
* ``payload_pool``: how many distinct request payloads the seed makes.

Every seed gets the same work in another order.  An open-loop window of
``seconds`` holds exactly ``round(rate * seconds)`` requests whose gaps are
the same set of exponential quantiles, shuffled, and scaled so that the
rate is exact; the models are drawn as fixed counts, shuffled.  So runs
with different seeds differ by the order of arrivals, not by how many.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request on the generator's schedule."""

    due: float                   # seconds after the window opens
    model: str
    pool_idx: int


def seed_words(seed: int, n: int = 4) -> list[int]:
    """``n`` 32-bit words mixed from the whole seed (any size): every
    bit of ``--seed`` matters, unlike a bare ``PRNGKey(seed)``."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0 (got {seed})")
    return [int(w) for w in np.random.SeedSequence(seed).generate_state(n)]


def shares(traffic: dict, models: list[str]) -> dict[str, float]:
    """Normalised request shares of the mix over the configuration's models."""
    mix = traffic.get("mix") or {m: 1.0 for m in models}
    unknown = sorted(set(mix) - set(models))
    if unknown:
        raise ValueError(f"traffic mix names models {unknown} that the "
                         f"configuration does not serve ({models})")
    total = float(sum(mix.values()))
    return {m: mix[m] / total for m in models if mix.get(m, 0) > 0}


def _counts(share: dict[str, float], n: int) -> dict[str, int]:
    """Largest-remainder split of ``n`` requests by ``share``."""
    raw = {m: s * n for m, s in share.items()}
    out = {m: int(v) for m, v in raw.items()}
    rest = sorted(raw, key=lambda m: raw[m] - out[m], reverse=True)
    for m in rest[:n - sum(out.values())]:
        out[m] += 1
    return out


def open_schedule(traffic: dict, models: list[str], rng: np.random.Generator,
                  seconds: float) -> list[Arrival]:
    """The open-loop schedule of one window, due times in ``[0, seconds)``."""
    if traffic["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrival']!r}")
    per_req = traffic["rate_img_s"] / traffic["images_per_request"]
    n = max(1, round(per_req * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    rng.shuffle(gaps)
    dues = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    tags = [m for m, c in _counts(shares(traffic, models), n).items()
            for _ in range(c)]
    rng.shuffle(tags)
    pool = rng.integers(traffic["payload_pool"], size=n)
    return [Arrival(float(d), t, int(p)) for d, t, p in zip(dues, tags, pool)]


class ClosedSource:
    """Next (model, payload) of a closed loop, drawn from the seed."""

    def __init__(self, traffic: dict, models: list[str],
                 rng: np.random.Generator):
        share = shares(traffic, models)
        self._models = list(share)
        self._p = np.array([share[m] for m in self._models])
        self._pool = traffic["payload_pool"]
        self._rng = rng

    def __call__(self) -> tuple[str, int]:
        i = self._rng.choice(len(self._models), p=self._p)
        return self._models[i], int(self._rng.integers(self._pool))


def payload_pool(traffic: dict, image_size: int,
                 rng: np.random.Generator) -> list[np.ndarray]:
    """The distinct request payloads: ``(images, size, size, 3)`` f32
    standard-normal images, made on the host as a client would send
    them."""
    shape = (traffic["images_per_request"], image_size, image_size, 3)
    return [rng.standard_normal(shape, dtype=np.float32)
            for _ in range(traffic["payload_pool"])]
