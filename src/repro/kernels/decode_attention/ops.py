"""Jit'd public wrapper for flash-decode."""

from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention.decode_attention import (
    decode_attention, paged_decode_attention)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("block_kv",))
def decode(q, k, v, lengths, layer=0, *, block_kv: int | None = None):
    """q: [B, Hq, D]; k/v: the stacked cache [L, B, S, Hkv, D]; lengths:
    [B]; layer: the stack's layer to attend -> [B, Hq, D]. One layer's
    buffer [B, S, Hkv, D] is passed as ``k[None]`` with layer 0."""
    return decode_attention(q, k, v, lengths, layer, block_kv=block_kv,
                            interpret=not _on_tpu())


@jax.jit
def paged_decode(q, k_pages, v_pages, lengths, block_tables):
    return paged_decode_attention(q, k_pages, v_pages, lengths, block_tables,
                                  interpret=not _on_tpu())
