"""Random weights from the run's seed, made on the device in one jitted
call, in the dtypes they are served in.

The layout (leaf names, shapes, dtypes) is the served model's parameter
tree; the values are drawn here, by a rule per leaf name, so the plain
reference and the served engines read the same weights without the
reference taking anything the program made. A leaf whose name has no rule
is an error: the reference would not know what it computes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_MATRICES = ("embed", "lm_head", "in_proj", "out_proj", "w_q", "w_k", "w_v",
             "w_o", "w_gate", "w_up", "w_down")


def key_for(seed: int):
    """A PRNG key from a seed of up to 64 bits. The generator is XLA's
    RngBitGenerator ("rbg"): on the TPU a table of a billion values compiles
    in seconds, where the threefry hash takes minutes."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _path_names(path):
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


def _normal(key, shape, dtype, scale):
    """N(0, scale^2) values drawn in the served dtype itself, so no float32
    temporary of a table's size exists."""
    return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)


def _leaf(key, names, shape, dtype):
    name = names[-1]
    parent = names[-2] if len(names) > 1 else ""
    if name == "scale":                              # RMSNorm gains
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    if name == "A_log":                              # A = -exp(A_log)
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0)).astype(dtype)
    if name == "D":
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    if name == "dt_bias":                            # softplus^-1 of dt
        u = jax.random.uniform(key, shape, jnp.float32, math.log(1e-3),
                               math.log(1e-1))
        dt = jnp.exp(u)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name == "conv" and parent == "ssd":           # [.., K, channels]
        return _normal(key, shape, dtype, 1.0 / math.sqrt(shape[-2]))
    if name == "embed":
        return _normal(key, shape, dtype, 0.02)
    if name in _MATRICES:                            # [.., fan_in, fan_out]
        return _normal(key, shape, dtype, 1.0 / math.sqrt(shape[-2]))
    raise KeyError(f"no weight rule for leaf {'/'.join(names)}")


def make_weights(shapes, seed: int):
    """Fill the parameter tree ``shapes`` (ShapeDtypeStructs) from
    ``seed`` in one jitted call on the default device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path_names(p) for p, _ in flat]
    specs = [(tuple(s.shape), s.dtype) for _, s in flat]

    @jax.jit
    def build(key):
        leaves = [_leaf(jax.random.fold_in(key, i), nm, shp, dt)
                  for i, (nm, (shp, dt)) in enumerate(zip(names, specs))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(key_for(seed))


def weight_bytes(params) -> int:
    return int(sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(params)))
