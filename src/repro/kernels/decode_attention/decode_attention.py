"""Flash-decode GQA attention — Pallas TPU kernel for the serving hot path.

One new token per sequence against a long KV cache: the workload is
memory-bound (read the whole cache once), so the kernel's job is to stream
KV through VMEM at full HBM bandwidth. The kernel reads the model's stacked
cache in place: its K/V operands are the whole ``[L, B, S, Hkv, D]`` stack
and a layer index rides scalar prefetch into the kv index map, so the
layer's rows reach VMEM straight from the stack — no per-layer slice and no
relayout is ever materialised (a custom call's operand is a buffer of its
own, so a slice or a transposed view would be a copy of the layer).

Grid = (batch, kv-block) with the kv-block dim innermost/sequential; each
step brings one ``[block_kv, Hkv, D]`` tile holding every kv head in one
DMA. Per kv head the [g, D] query tile issues a [g, D] × [D, block_kv]
MXU matmul on the head's strided rows — for GQA g = 4–8 this also
amortises each KV byte over g queries (the reason GQA exists) — and the
online-softmax state of all heads ([Hkv, g] in VMEM scratch) is updated
in one pass.

Per-row ``lengths`` masks ragged sessions (continuous batching: every slot
sits at a different position).

Layouts: q [B, Hq, D]; k/v [L, B, S, Hkv, D] (``models.kvcache``'s
stacked layout; a single layer's buffer is passed as ``k[None]``); layer
int32 scalar; lengths [B] -> out [B, Hq, D].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def kv_block(S: int, target: int = 512) -> int:
    """Rows per kv block for a cache of ``S`` rows: the whole buffer when
    it fits ``target``, else the largest divisor of ``S`` in
    ``[target / 4, target]`` (no partial block), else ``target`` with a
    partial last block that the kernel masks. The stack is never padded:
    a pad would copy the whole cache."""
    if S <= target:
        return S
    for bk in range(target, target // 4 - 1, -1):
        if S % bk == 0:
            return bk
    return target


def _kernel(layer_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, block_kv: int,
            heads: int, partial: bool):
    del layer_ref                     # consumed by the index maps
    b = pl.program_id(0)
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    kv_start = ik * block_kv

    @pl.when(kv_start < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [h, g, d]
        # per kv head, one [g, d] x [d, bk] matmul on its strided
        # [bk, d] slice of the block; the softmax runs on all heads at once
        s = jnp.stack([
            jax.lax.dot_general(q[h], k_ref[0, 0, :, h].astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for h in range(heads)]) * scale            # [h, g, bk]
        k_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_ref[...]                            # [h, g]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2)
        p = p.astype(v_ref.dtype)
        if partial:
            # the last block runs past the buffer: its tail rows are not
            # cache memory and may hold NaN, which 0 * NaN in the values
            # matmul would carry into the output
            live_row = kv_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, 1), 0) < length
        pv = []
        for h in range(heads):
            v = v_ref[0, 0, :, h]                      # [bk, d]
            if partial:
                v = jnp.where(live_row, v, jnp.zeros_like(v))
            pv.append(jax.lax.dot_general(
                p[h], v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.stack(pv)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention(q, k, v, lengths, layer=0, *, block_kv: int | None = None,
                     interpret: bool = True):
    """q: [B, Hq, D]; k/v: [L, B, S, Hkv, D]; layer: int32 scalar, the
    stack's layer to attend; lengths: [B] (at most S) -> [B, Hq, D].

    ``block_kv`` defaults to ``kv_block(S)``. Grid step (b, ik) streams
    rows ``[ik * block_kv, (ik + 1) * block_kv)`` of row ``b`` of layer
    ``layer`` with all its kv heads (block ``(1, 1, block_kv, Hkv, D)``:
    the last two dims are whole, so any ``block_kv`` tiles legally).

    Jitted so that every fused decode program of every engine of a
    process reuses one trace of the kernel at the same shapes.
    """
    B, Hq, D = q.shape
    S, Hkv = k.shape[2], k.shape[3]
    g = Hq // Hkv
    scale = 1.0 / (D ** 0.5)
    bk = min(block_kv or kv_block(S), S)
    nk = pl.cdiv(S, bk)

    # group q by kv head: [B, Hkv, g, D]
    qg = q.reshape(B, Hkv, g, D)
    kern = functools.partial(_kernel, scale=scale, block_kv=bk, heads=Hkv,
                             partial=S % bk != 0)

    def kv_map(b, ik, li, lens):
        del lens
        return (li[0], b, ik, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # layer, lengths
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, Hkv, g, D), lambda b, ik, li, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, bk, Hkv, D), kv_map),
            pl.BlockSpec((1, 1, bk, Hkv, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, Hkv, g, D),
                               lambda b, ik, li, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, g), jnp.float32),
            pltpu.VMEM((Hkv, g), jnp.float32),
            pltpu.VMEM((Hkv, g, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(B, Hq, D)


# ---------------------------------------------------------------------------
# paged flash-decode: gather K/V through a block table
# ---------------------------------------------------------------------------
#
# Same online-softmax core as the dense kernel above, but K/V live in a
# global page pool shared by every sequence ([P, page, Hkv, D]) and each
# sequence owns a block table of page ids. The table rides scalar prefetch
# (PrefetchScalarGridSpec): the kv-block index maps read ``tbl[b, ip]`` to
# pick which POOL page each grid step streams into VMEM — the gather happens
# in the DMA engine's addressing, so the [B, S] linear view the pure-XLA
# fallback materialises never exists.


def _paged_kernel(len_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, page: int,
                  heads: int):
    del tbl_ref                       # consumed by the index maps
    b = pl.program_id(0)
    ip = pl.program_id(1)
    npg = pl.num_programs(1)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    kv_start = ip * page

    @pl.when(kv_start < length)
    def _compute():
        for h in range(heads):                 # static: every kv head of
            q = q_ref[0, h].astype(jnp.float32)       # this page, one DMA
            k = k_ref[0, :, h].astype(jnp.float32)    # [page, d]
            v = v_ref[0, :, h]                        # [page, d]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            k_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < length, s, NEG_INF)  # [g, page]
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(ip == npg - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, lengths, block_tables, *,
                           interpret: bool = True):
    """q: [B, Hq, D]; k/v_pages: [P, page, Hkv, D]; lengths: [B];
    block_tables: [B, PPS] int32 page ids -> out [B, Hq, D].

    The kv-block grid dim is the block-table column: grid step (b, ip)
    streams pool page ``block_tables[b, ip]`` with ALL its kv heads in one
    DMA (block ``(1, page, Hkv, D)`` — the TPU lowering refuses a block
    that slices the second-minor Hkv axis to 1), so each page is read once.
    Pages past a sequence's length are still DMA'd (whatever the stale
    table entry points at) but their compute is skipped by the
    ``kv_start < length`` gate, so garbage and scratch pages never touch
    the softmax state.
    """
    B, Hq, D = q.shape
    page, Hkv = k_pages.shape[1], k_pages.shape[2]
    PPS = block_tables.shape[1]
    g = Hq // Hkv
    scale = 1.0 / (D ** 0.5)

    qg = q.reshape(B, Hkv, g, D)
    grid = (B, PPS)
    kern = functools.partial(_paged_kernel, scale=scale, page=page,
                             heads=Hkv)

    def kv_map(b, ip, lens, tbl):
        del lens
        return (tbl[b, ip], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # lengths, block table
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hkv, g, D),
                         lambda b, ip, lens, tbl: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, D), kv_map),
            pl.BlockSpec((1, page, Hkv, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, Hkv, g, D),
                               lambda b, ip, lens, tbl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, g), jnp.float32),
            pltpu.VMEM((Hkv, g), jnp.float32),
            pltpu.VMEM((Hkv, g, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        interpret=interpret,
        name="paged_flash_decode",
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(B, Hq, D)
