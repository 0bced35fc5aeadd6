"""Faults planted underneath the timed path, for the tests that show the
check comes out false: each wraps the last exec group of one model, where
the logits are produced, in every system the harness builds."""
from __future__ import annotations

from chipbench import harness


def plant(monkeypatch, fault) -> None:
    """Make ``harness.build`` hand out systems with ``fault`` applied."""
    build = harness.build

    def broken(*args, **kwargs):
        system, tables, calls = build(*args, **kwargs)
        fault(system)
        return system, tables, calls

    monkeypatch.setattr(harness, "build", broken)


def _wrap_last(system, model: str, change) -> None:
    runner = system.runners[model]
    fn = runner._fns[-1]

    def broken(params, env):
        out = fn(params, env)
        return dict(out, out=change(out["out"]))

    runner._fns[-1] = broken


def answer_altered(model: str):
    """One logit of every answer moved by 1e-3 of the largest."""
    import jax.numpy as jnp

    def change(out):
        return out.at[:, 0].add(1e-3 * jnp.max(jnp.abs(out)))

    return lambda system: _wrap_last(system, model, change)


def half_batch_left_out(model: str):
    """The second half of every request's images served the first half's
    answers: half of the batch left out."""

    def change(out):
        h = out.shape[0] // 2
        return out.at[h:].set(out[:h])

    return lambda system: _wrap_last(system, model, change)
