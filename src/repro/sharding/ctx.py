"""Ambient-mesh activation sharding constraints (MaxText-style logical axes).

Model code calls ``constrain(x, "dp", None, "model", ...)`` at key points;
under a ``with jax.set_mesh(mesh):`` lowering context this pins the
activation layout so GSPMD cannot drift into batch-replicated layouts
inside scan bodies (observed failure mode: 25 GB/device of batch-replicated
attention residuals — see EXPERIMENTS.md §Perf iteration 0). Outside any
mesh (CPU smoke tests) it is an identity, keeping the model code
mesh-agnostic.

Dim tokens:
    "dp"    — shard over the data-parallel axes (pod+data) if divisible
    "model" — shard over the model axis if divisible
    None    — leave unsharded
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import PartitionSpec as P


def current_mesh():
    """The (abstract) mesh of the enclosing ``jax.set_mesh`` context, or
    None outside one. Readable while tracing, unlike ``get_mesh``."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def constrain(x, *dims):
    mesh = current_mesh()
    if mesh is None:
        return x
    if len(dims) != x.ndim:
        raise ValueError(f"constrain: {len(dims)} dims for rank-{x.ndim}")
    axes = mesh.axis_names
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    spec = []
    for i, d in enumerate(dims):
        if d == "dp" and dp and x.shape[i] % dp_size == 0:
            spec.append(dp if len(dp) > 1 else dp[0])
        elif d == "model" and "model" in axes and \
                x.shape[i] % mesh.shape["model"] == 0:
            spec.append("model")
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
