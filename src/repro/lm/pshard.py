"""Activation sharding hints (MaxText-style logical constraints).

``hint(x, 'batch', None, 'model')`` applies a with_sharding_constraint
resolved against the ambient mesh (entered with ``with jax.set_mesh(m):``,
read with ``jax.sharding.get_abstract_mesh``).  Outside any mesh (CPU
smoke tests) it is a no-op; axes that are missing from the mesh or do not
divide the dimension are dropped (same fallback policy as
repro.launch.sharding).

These hints pin the canonical layout — activations (batch->data, d
replicated), projections (batch->data, features->model) — so GSPMD
all-gathers the FSDP-sharded *weights* instead of partial-summing
activations over the data axis (which costs an all-reduce of a full
activation tensor per matmul; observed 10 TB/step before the hints).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P

BATCH = "batch"
MODEL = "model"

# dp_only mode (hillclimb knob): batch spans every mesh axis and 'model'
# resolves to nothing — pure data parallelism with replicated weights.
_DP_ONLY = False


def set_dp_only(flag: bool):
    global _DP_ONLY
    _DP_ONLY = flag


def dp_only() -> bool:
    return _DP_ONLY


def _mesh():
    """The ambient mesh, or None outside any ``jax.set_mesh`` context."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def hint(x, *logical):
    mesh = _mesh()
    if mesh is None:
        return x
    names = mesh.axis_names
    shape = dict(zip(names, (mesh.shape[n] for n in names)))
    spec = []
    for dim, want in zip(x.shape, logical):
        ax = None
        if want == BATCH:
            cand = (tuple(n for n in ("pod", "data", "model")
                          if n in names) if _DP_ONLY else
                    tuple(n for n in ("pod", "data") if n in names))
            while cand:
                size = math.prod(shape[n] for n in cand)
                if dim % size == 0:
                    ax = cand if len(cand) > 1 else cand[0]
                    break
                cand = cand[:-1]
        elif want == MODEL and not _DP_ONLY:
            if "model" in names and dim % shape["model"] == 0:
                ax = "model"
        spec.append(ax)
    # pad remaining dims with None
    spec += [None] * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(x, P(*spec))
