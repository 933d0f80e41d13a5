"""Shared neural-net layers (pure-functional, pytree params).

Conventions
-----------
* Params are nested dicts of jnp arrays; weights stored in ``cfg.dtype``
  (bf16 by default), norm scales in f32.
* Every ``*_init`` returns params; every ``*_apply`` is a pure function.
* Matmul-heavy ops run in bf16 with f32 accumulation via
  ``preferred_element_type``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.sharding.ctx import constrain
from repro.models.quant import as_weight


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

# jitted so the f32 draw fuses into the cast: the weight is written once in
# its own dtype (eagerly, a 256000 x 4096 table holds 8.4 GB of f32
# temporaries on the way to 2.1 GB of bf16)
@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def embed_init(key, vocab: int, dim: int, dtype):
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int):
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rmsnorm_apply(p, x, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


def layernorm_init(dim: int):
    return {"scale": jnp.ones((dim,), jnp.float32), "bias": jnp.zeros((dim,), jnp.float32)}


def layernorm_apply(p, x, eps: float):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # [head_dim//2]


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate pairs. x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
                sections: tuple) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE.

    positions: [3, ..., seq] — temporal / height / width position streams.
    ``sections`` are half-dim section sizes that sum to head_dim//2; section i
    takes its rotation angle from position stream i.
    """
    freqs = rope_frequencies(x.shape[-1], theta)  # [half]
    # pick, per frequency index, which position stream feeds it
    sec_ids = np.repeat(np.arange(len(sections)), sections)  # [half]
    # gather the right stream per section: positions[sec_ids[j], ..., seq]
    pos_sel = positions.astype(jnp.float32)[sec_ids]          # [half, ..., seq]
    pos_sel = jnp.moveaxis(pos_sel, 0, -1)                    # [..., seq, half]
    angles = pos_sel * freqs                                   # [..., seq, half]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rope_for(cfg: ModelConfig, x, positions):
    """Dispatch RoPE vs M-RoPE. positions: [b, s] or [3, b, s] for mrope."""
    if cfg.mrope_sections:
        if positions.ndim == 2:  # text-only: duplicate stream
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    if positions.ndim == 3:
        positions = positions[0]
    return apply_rope(x, positions, cfg.rope_theta)


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None):
    d_ff = d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, cfg.d_model, d_ff, dt),
        "w_up": dense_init(k2, cfg.d_model, d_ff, dt),
        "w_down": dense_init(k3, d_ff, cfg.d_model, dt),
    }


def mlp_apply(p, x):
    gate = jnp.einsum("...d,df->...f", x, as_weight(p["w_gate"]),
                      preferred_element_type=jnp.float32)
    up = jnp.einsum("...d,df->...f", x, as_weight(p["w_up"]),
                    preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    h = constrain(h, *(["dp"] + [None] * (h.ndim - 2) + ["model"]))
    return jnp.einsum("...f,fd->...d", h, as_weight(p["w_down"]),
                      preferred_element_type=jnp.float32).astype(x.dtype)


# ---------------------------------------------------------------------------
# softcap
# ---------------------------------------------------------------------------

def softcap(x, cap: float):
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap
