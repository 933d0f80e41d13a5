"""Attention layers: GQA, sliding-window, cross-attention, cached decode.

Memory discipline: full-sequence attention never materialises the
``[b, h, s, s]`` score tensor. Training/prefill paths run a blocked
online-softmax (flash-style) implemented with ``lax.scan`` so compiled
peak memory stays ``O(b · h · block_q · block_kv)`` per step. Sliding-window
prefill slices a static-width band with ``lax.dynamic_slice`` so FLOPs are
``O(s · (window + block_q))`` rather than ``O(s²)``.

These are the pure-jnp reference paths used by the dry-run lowering; the
Pallas kernels in ``repro.kernels`` implement the same math for TPU with
explicit VMEM BlockSpecs and causal block skipping.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models import layers as L
from repro.sharding.ctx import constrain
from repro.models.quant import as_weight

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ModelConfig, *, cross: bool = False):
    dt = L.dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "w_q": L.dense_init(k1, cfg.d_model, cfg.q_dim, dt),
        "w_k": L.dense_init(k2, cfg.d_model, cfg.kv_dim, dt),
        "w_v": L.dense_init(k3, cfg.d_model, cfg.kv_dim, dt),
        "w_o": L.dense_init(k4, cfg.q_dim, cfg.d_model, dt),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = L.rmsnorm_init(cfg.head_dim)
        p["k_norm"] = L.rmsnorm_init(cfg.head_dim)
    return p


def _project_q(p, cfg: ModelConfig, x, positions):
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dq->bsq", x, as_weight(p["w_q"]),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    q = constrain(q, "dp", None, "model", None)
    if cfg.use_qk_norm:
        q = L.rmsnorm_apply(p["q_norm"], q, cfg.norm_eps)
    if positions is not None:
        q = L.rope_for(cfg, q, positions)
    return q


def _project_kv(p, cfg: ModelConfig, x, positions):
    b, s, _ = x.shape
    k = jnp.einsum("bsd,dq->bsq", x, as_weight(p["w_k"]),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bsd,dq->bsq", x, as_weight(p["w_v"]),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    k = constrain(k, "dp", None, "model", None)
    v = constrain(v, "dp", None, "model", None)
    if cfg.use_qk_norm:
        k = L.rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    if positions is not None:
        k = L.rope_for(cfg, k, positions)
    return k, v


# ---------------------------------------------------------------------------
# blocked online-softmax core
# ---------------------------------------------------------------------------

def _block_attend(q, k, v, q_pos, k_pos, *, causal, window, scale, softcap):
    """One (q-block × kv-block) tile. q: [b, bq, kh, g, d]; k/v: [b, bk, kh, d].

    Returns per-tile scores statistics for the online-softmax combine.
    """
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = L.softcap(s, softcap)
    valid = (k_pos[None, :] >= 0)
    if causal:
        valid = valid & (k_pos[None, :] <= q_pos[:, None])
    if window:
        valid = valid & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    return s


def _online_softmax_scan(q, kv_blocks_iter, q_pos, *, causal, window, scale,
                         softcap, out_dtype, remat=False):
    """Scan over kv blocks maintaining (m, l, o) running statistics.

    q: [b, bq, kh, g, d]. kv_blocks_iter yields (k_blk, v_blk, k_pos_blk).

    ``remat=True`` checkpoints the per-tile body so the backward pass
    recomputes the P tile instead of saving it — the flash-attention
    memory discipline (saving P tiles for every (q, kv) block pair costs
    O(b·h·s²) f32/device: 17–84 GB observed on the train_4k cells).
    """
    b, bq, kh, g, d = q.shape

    def step(carry, blk):
        m, l, o = carry
        k_blk, v_blk, kpos = blk
        s = _block_attend(q, k_blk, v_blk, q_pos, kpos, causal=causal,
                          window=window, scale=scale, softcap=softcap)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk,
                        preferred_element_type=jnp.float32)
        o = o * alpha[..., None] + pv
        return (m_new, l, o), None

    m0 = jnp.full((b, kh, g, bq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, bq), jnp.float32)
    o0 = jnp.zeros((b, kh, g, bq, d), jnp.float32)
    body = jax.checkpoint(step) if remat else step
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0), kv_blocks_iter)
    o = o / jnp.maximum(l[..., None], 1e-37)
    # [b, kh, g, bq, d] -> [b, bq, kh*g, d]
    o = jnp.moveaxis(o, 3, 1).reshape(b, bq, kh * g, d)
    return o.astype(out_dtype)


def _pad_to(x, axis, multiple):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def blocked_attention(q, k, v, q_positions, k_positions, *, causal: bool,
                      window: int, block_q: int, block_kv: int,
                      softcap: float = 0.0, remat: bool = False):
    """Flash-style attention. q: [b, sq, hq, d]; k/v: [b, skv, kh, d].

    ``q_positions``/``k_positions``: [sq] / [skv] absolute positions (shared
    across batch; ragged batches are handled by -1 sentinels in k_positions).
    """
    b, sq, hq, d = q.shape
    kh = k.shape[2]
    g = hq // kh
    scale = 1.0 / np.sqrt(d)

    q, sq0 = _pad_to(q, 1, block_q)
    qp, _ = _pad_to(q_positions, 0, block_q)
    k, _ = _pad_to(k, 1, block_kv)
    v, _ = _pad_to(v, 1, block_kv)
    kp = jnp.pad(k_positions, (0, k.shape[1] - k_positions.shape[0]),
                 constant_values=-1)

    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_kv
    qb = q.reshape(b, nq, block_q, kh, g, d)
    qpb = qp.reshape(nq, block_q)
    kb = jnp.moveaxis(k.reshape(b, nk, block_kv, kh, d), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nk, block_kv, kh, d), 1, 0)
    kpb = kp.reshape(nk, block_kv)

    def per_q_block(carry, xs):
        qblk, qpos = xs
        o = _online_softmax_scan(qblk, (kb, vb, kpb), qpos, causal=causal,
                                 window=window, scale=scale, softcap=softcap,
                                 out_dtype=q.dtype, remat=remat)
        return carry, o

    body = jax.checkpoint(per_q_block) if remat else per_q_block
    _, outs = jax.lax.scan(body, (), (jnp.moveaxis(qb, 1, 0), qpb))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, -1, hq, d)
    return out[:, :sq0]


def banded_attention(q, k, v, q_positions, k_positions, *, window: int,
                     block_q: int, softcap: float = 0.0,
                     remat: bool = False):
    """Sliding-window causal attention with O(s·window) FLOPs.

    For q block starting at position p, only the KV band
    ``[p + block_q - band, p + block_q)`` can be visible, with
    ``band = window + block_q`` (static size) sliced via dynamic_slice.
    """
    b, sq, hq, d = q.shape
    kh = k.shape[2]
    g = hq // kh
    scale = 1.0 / np.sqrt(d)
    band = window + block_q

    q, sq0 = _pad_to(q, 1, block_q)
    qp, _ = _pad_to(q_positions, 0, block_q)
    nq = q.shape[1] // block_q
    skv = k.shape[1]
    # left-pad KV by band so every dynamic_slice stays in range
    k = jnp.pad(k, ((0, 0), (band, 0), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (band, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k_positions, (band, 0), constant_values=-1)
    k, _ = _pad_to(k, 1, block_q)
    v, _ = _pad_to(v, 1, block_q)
    kp, _ = _pad_to(kp, 0, block_q)
    # both pads (left band, right round-up) must read as invalid positions
    ar = jnp.arange(kp.shape[0])
    kp = jnp.where((ar < band) | (ar >= band + skv), -1, kp)

    qb = jnp.moveaxis(q.reshape(b, nq, block_q, kh, g, d), 1, 0)
    qpb = qp.reshape(nq, block_q)

    def per_q_block(carry, xs):
        i, qblk, qpos = xs
        start = i * block_q  # band end aligns with q block end (+band offset)
        k_band = jax.lax.dynamic_slice_in_dim(k, start, band + block_q, axis=1)
        v_band = jax.lax.dynamic_slice_in_dim(v, start, band + block_q, axis=1)
        kp_band = jax.lax.dynamic_slice_in_dim(kp, start, band + block_q, axis=0)
        s = _block_attend(qblk, k_band, v_band, qpos, kp_band, causal=True,
                          window=window, scale=scale, softcap=softcap)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", (p / jnp.maximum(l, 1e-37)).astype(v_band.dtype),
                       v_band, preferred_element_type=jnp.float32)
        o = jnp.moveaxis(o, 3, 1).reshape(qblk.shape[0], block_q, kh * g, d)
        return carry, o.astype(qblk.dtype)

    idx = jnp.arange(nq)
    body = jax.checkpoint(per_q_block) if remat else per_q_block
    _, outs = jax.lax.scan(body, (), (idx, qb, qpb))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, -1, hq, d)
    return out[:, :sq0]


def qwhole_attention(q, k, v, q_positions, k_positions, *, causal: bool,
                     window: int, block_kv: int, softcap: float = 0.0,
                     remat: bool = False):
    """Sequence-parallel flash attention: q kept whole (its seq dim carries
    the model-axis sharding), single online-softmax scan over KV blocks.

    Used when the head counts don't divide the model axis (e.g. phi3 40H/10KV
    on a 16-way axis): head-sharded tiles would be batch/head-replicated and
    the nested-scan residuals blow past HBM (33 GB/device observed). Here the
    per-step score tile is [b, kh, g, s_local, block_kv].
    """
    b, sq, hq, d = q.shape
    kh = k.shape[2]
    g = hq // kh
    scale = 1.0 / np.sqrt(d)
    q5 = q.reshape(b, sq, kh, g, d)
    k, _ = _pad_to(k, 1, block_kv)
    v, _ = _pad_to(v, 1, block_kv)
    kp = jnp.pad(k_positions, (0, k.shape[1] - k_positions.shape[0]),
                 constant_values=-1)
    nk = k.shape[1] // block_kv
    kb = jnp.moveaxis(k.reshape(b, nk, block_kv, kh, d), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nk, block_kv, kh, d), 1, 0)
    kpb = kp.reshape(nk, block_kv)
    return _online_softmax_scan(q5, (kb, vb, kpb), q_positions, causal=causal,
                                window=window, scale=scale, softcap=softcap,
                                out_dtype=q.dtype, remat=remat)


def _heads_shardable(cfg: ModelConfig) -> bool:
    from repro.sharding.ctx import current_mesh
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return True
    m = mesh.shape["model"]
    return cfg.num_heads % m == 0


def full_attention(q, k, v, qpos, kpos, cfg: ModelConfig, *, causal=True):
    """Dispatch between the blocked / banded / sequence-parallel paths."""
    remat = cfg.remat != "none"
    if cfg.sliding_window and causal:
        return banded_attention(q, k, v, qpos, kpos,
                                window=cfg.sliding_window,
                                block_q=cfg.attn_block_q,
                                softcap=cfg.attn_logits_softcap, remat=remat)
    if not _heads_shardable(cfg):
        q = constrain(q, "dp", "model", None, None)
        return qwhole_attention(q, k, v, qpos, kpos, causal=causal,
                                window=cfg.sliding_window,
                                block_kv=cfg.attn_block_kv,
                                softcap=cfg.attn_logits_softcap, remat=remat)
    return blocked_attention(q, k, v, qpos, kpos, causal=causal,
                             window=cfg.sliding_window,
                             block_q=cfg.attn_block_q,
                             block_kv=cfg.attn_block_kv,
                             softcap=cfg.attn_logits_softcap, remat=remat)


# ---------------------------------------------------------------------------
# public layer entry points
# ---------------------------------------------------------------------------

def self_attention(p, cfg: ModelConfig, x, positions, *, causal=True):
    """Full-sequence self attention (training / encoder)."""
    pos1d = positions[0] if positions.ndim == 3 else positions
    q = _project_q(p, cfg, x, positions)
    k, v = _project_kv(p, cfg, x, positions)
    qpos = pos1d[0] if pos1d.ndim == 2 else pos1d
    kpos = qpos
    o = full_attention(q, k, v, qpos, kpos, cfg, causal=causal)
    b, s, _, _ = o.shape
    return jnp.einsum("bsq,qd->bsd", o.reshape(b, s, cfg.q_dim), as_weight(p["w_o"]),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def cross_attention(p, cfg: ModelConfig, x, memory, mem_positions):
    """Decoder→encoder attention (no causal mask, no RoPE on memory)."""
    q = _project_q(p, cfg, x, None)
    k, v = _project_kv(p, cfg, memory, None)
    sq = x.shape[1]
    qpos = jnp.arange(sq)
    kpos = mem_positions
    o = blocked_attention(q, k, v, qpos, kpos, causal=False, window=0,
                          block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv,
                          remat=cfg.remat != "none")
    b, s, _, _ = o.shape
    return jnp.einsum("bsq,qd->bsd", o.reshape(b, s, cfg.q_dim), as_weight(p["w_o"]),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def decode_self_attention(p, cfg: ModelConfig, x, cache_k, cache_v, position,
                          *, window: int = 0, active=None, layer=None):
    """Single-token decode against a KV cache ring/linear buffer.

    x: [b, 1, d]; cache_k/v: [b, S, kh, hd]; position: [b] int32 — the
    absolute position of each row's new token (per-slot positions enable
    continuous batching: sessions in the same decode batch sit at different
    offsets). For sliding-window caches the buffer is a ring of size
    ``window`` indexed modulo.

    With ``layer`` (int32 scalar), cache_k/v are the whole layer stacks
    [L, b, S, kh, hd]: the new row is written into layer ``layer`` of the
    stack, the Pallas kernel then reads the updated stack in place, and the
    stacks are returned. Without it the layer's own buffers are returned.

    ``active`` ([b] bool, optional) suppresses the cache write for inactive
    rows: parked (idle-resident) sessions ride the fused batch without their
    state advancing, which is what makes in-place hibernation-tier parking
    safe for ring buffers (a masked row would otherwise overwrite a live
    in-window entry). The mask selects between each row's new and old
    entry, [b, kh, hd], before the write.
    """
    b = x.shape[0]
    position = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (b,))
    with jax.named_scope("qkv"):
        q = _project_q(p, cfg, x, position[:, None])
        k_new, v_new = _project_kv(p, cfg, x, position[:, None])
    stacked = layer is not None
    if not stacked:                       # one layer's buffers: a stack of 1
        cache_k, cache_v, layer = cache_k[None], cache_v[None], 0
    with jax.named_scope("kv_write"):
        cache_k = _decode_cache_write(cache_k, k_new, layer, position, window,
                                      active)
        cache_v = _decode_cache_write(cache_v, v_new, layer, position, window,
                                      active)
    with jax.named_scope("attn"):
        if cfg.use_pallas_decode and not window \
                and not cfg.attn_logits_softcap:
            out = _decode_attend_kernel(p, cfg, x, q, cache_k, cache_v,
                                        layer, position)
        else:
            out = _decode_attend(
                p, cfg, x, q,
                jax.lax.dynamic_index_in_dim(cache_k, layer, 0, False),
                jax.lax.dynamic_index_in_dim(cache_v, layer, 0, False),
                position, window)
    if not stacked:
        return out, cache_k[0], cache_v[0]
    return out, cache_k, cache_v


def _decode_cache_write(stack, new, layer, position, window, active):
    """Write each row's new entry ``new`` [b, 1, kh, hd] into layer
    ``layer`` of ``stack`` [L, b, S, kh, hd] at the row's slot: one row per
    batch row, so the stack is updated in place and nothing else of it is
    read or written. Ring buffers take slot ``position % S``, linear ones
    clamp at ``S - 1``."""
    b, S = stack.shape[1], stack.shape[2]
    slot = (position % S) if window else jnp.minimum(position, S - 1)
    rows = jnp.arange(b)
    new = new[:, 0].astype(stack.dtype)
    if active is not None:
        new = jnp.where(active[:, None, None], new, stack[layer, rows, slot])
    # (layer, row, slot) index triples are unique and sorted: rows differ
    return stack.at[layer, rows, slot].set(new, unique_indices=True,
                                           indices_are_sorted=True)


def _decode_attend_kernel(p, cfg: ModelConfig, x, q, stack_k, stack_v, layer,
                          position):
    """The flash-decode Pallas kernel over layer ``layer`` of the written
    stack [L, b, S, kh, hd], through the output projection. Linear buffer
    only: the slot index IS the absolute position, so the kernel's
    ``kpos < length`` ragged mask is exactly the reference path's
    ``kpos <= position``; ring buffers and softcapped logits stay on the
    reference path."""
    from repro.kernels.decode_attention.decode_attention import \
        decode_attention
    b, S = x.shape[0], stack_k.shape[2]
    o = decode_attention(
        q[:, 0], stack_k, stack_v,                      # [b, hq, hd]
        # clamp at the buffer: past position S-1 the linear cache holds
        # exactly S valid rows (the reference mask is slot <= position
        # over slots [0, S))
        jnp.minimum(position + 1, S), layer,
        interpret=jax.default_backend() != "tpu")
    o = o.reshape(b, 1, cfg.q_dim).astype(x.dtype)
    return jnp.einsum("bsq,qd->bsd", o, as_weight(p["w_o"]),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _decode_attend(p, cfg: ModelConfig, x, q, cache_k, cache_v, position,
                   window):
    """The reference path: the new token's attention over the layer's
    written buffer, through the output projection."""
    b, S = x.shape[0], cache_k.shape[1]
    kh, hd = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // kh
    # absolute position of every cache slot, per row: [b, S]
    idx = jnp.arange(S, dtype=jnp.int32)
    if window:
        # ring buffer: slot i holds the latest position ≡ i (mod S) ≤ pos
        kpos = position[:, None] - ((position[:, None] - idx[None, :]) % S)
        valid = (kpos >= 0) & (kpos > position[:, None] - window)
    else:
        kpos = idx[None, :]
        valid = kpos <= position[:, None]

    qh = q.reshape(b, 1, kh, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh, cache_k,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    if cfg.attn_logits_softcap:
        s = L.softcap(s, cfg.attn_logits_softcap)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", w.astype(cache_v.dtype), cache_v,
                   preferred_element_type=jnp.float32)
    o = jnp.moveaxis(o, 3, 1).reshape(b, 1, cfg.q_dim).astype(x.dtype)
    return jnp.einsum("bsq,qd->bsd", o, as_weight(p["w_o"]),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def paged_decode_self_attention(p, cfg: ModelConfig, x, k_pages, v_pages,
                                block, position, *, active=None):
    """Single-token decode against a block-table paged KV pool.

    x: [b, 1, d]; k_pages/v_pages: [P, page, kh, hd] — this layer's slice of
    the global page pool; block: [b, PPS] int32 page ids per slot (page 0 is
    the shared scratch page — see ``repro.models.kvcache``); position: [b].

    Bit-compatibility contract with the dense path: when ``PPS * page`` equals
    the dense buffer length S, the gathered K/V rows are exactly the dense
    buffer rows and the masked-softmax math below is the same expression, so
    greedy decode is token-identical. Writes of inactive rows (and positions
    past the table) are routed to the scratch page, which is never read.
    """
    b = x.shape[0]
    page = k_pages.shape[1]
    S = block.shape[1] * page
    position = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (b,))
    with jax.named_scope("qkv"):
        q = _project_q(p, cfg, x, position[:, None])
        k_new, v_new = _project_kv(p, cfg, x, position[:, None])

    # write the new token's K/V through the block table (one page row per
    # batch row — distinct active slots never share a page, so the batched
    # scatter has no write conflicts outside the scratch page)
    with jax.named_scope("kv_write"):
        posc = jnp.minimum(position, S - 1)
        pid = jnp.take_along_axis(block, (posc // page)[:, None],
                                  axis=1)[:, 0]
        if active is not None:
            pid = jnp.where(active, pid, 0)
        off = posc % page
        k_pages = k_pages.at[pid, off].set(
            k_new[:, 0].astype(k_pages.dtype))
        v_pages = v_pages.at[pid, off].set(
            v_new[:, 0].astype(v_pages.dtype))
    with jax.named_scope("attn"):
        return _paged_attend(p, cfg, x, q, k_pages, v_pages, block,
                             position)


def _paged_attend(p, cfg: ModelConfig, x, q, k_pages, v_pages, block,
                  position):
    """The new token's attention over its slot's pages, through the
    output projection."""
    b, S = x.shape[0], block.shape[1] * k_pages.shape[1]
    kh, hd, hq = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    g = hq // kh
    if cfg.use_pallas_decode and not cfg.attn_logits_softcap:
        # paged flash-decode kernel: gathers K/V pages through the block
        # table with scalar-prefetch index maps (no [b, S] materialisation)
        from repro.kernels.decode_attention.decode_attention import \
            paged_decode_attention
        o = paged_decode_attention(
            q[:, 0], k_pages, v_pages,
            jnp.minimum(position + 1, S), block,
            interpret=jax.default_backend() != "tpu")
        o = o.reshape(b, 1, cfg.q_dim).astype(x.dtype)
        out = jnp.einsum("bsq,qd->bsd", o, as_weight(p["w_o"]),
                         preferred_element_type=jnp.float32).astype(x.dtype)
        return out, k_pages, v_pages

    # pure-XLA fallback: gather the slot's pages into a linear view, then
    # the same masked-softmax expression as the dense reference path
    k = k_pages[block].reshape(b, S, kh, hd)
    v = v_pages[block].reshape(b, S, kh, hd)
    idx = jnp.arange(S, dtype=jnp.int32)
    valid = idx[None, :] <= position[:, None]
    qh = q.reshape(b, 1, kh, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh, k,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    if cfg.attn_logits_softcap:
        s = L.softcap(s, cfg.attn_logits_softcap)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = jnp.moveaxis(o, 3, 1).reshape(b, 1, cfg.q_dim).astype(x.dtype)
    out = jnp.einsum("bsq,qd->bsd", o, as_weight(p["w_o"]),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return out, k_pages, v_pages


def decode_cross_attention(p, cfg: ModelConfig, x, mem_k, mem_v, mem_positions):
    """Cached cross attention: encoder K/V precomputed at session prefill."""
    b = x.shape[0]
    kh, hd, hq = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    g = hq // kh
    q = _project_q(p, cfg, x, None)
    qh = q.reshape(b, 1, kh, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh, mem_k,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    s = jnp.where((mem_positions >= 0)[None, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", w.astype(mem_v.dtype), mem_v,
                   preferred_element_type=jnp.float32)
    o = jnp.moveaxis(o, 3, 1).reshape(b, 1, cfg.q_dim).astype(x.dtype)
    return jnp.einsum("bsq,qd->bsd", o, as_weight(p["w_o"]),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def project_cross_kv(p, cfg: ModelConfig, memory):
    """Precompute encoder-side K/V once per session (seamless decode path)."""
    return _project_kv(p, cfg, memory, None)
