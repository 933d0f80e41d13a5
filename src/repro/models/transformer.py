"""Decoder-only / encoder-decoder LM covering all assigned families.

Layer stacking uses ``lax.scan`` over stacked layer params for homogeneous
stacks (dense / moe / ssm / encdec) to keep HLO size and compile time bounded
at production depth, and an unrolled loop for the heterogeneous hybrid
(RG-LRU) pattern. Activation rematerialisation is applied per layer according
to ``cfg.remat``.

Public surface (all pure functions of (params, batch)):
    init(key)                       -> params
    forward(params, batch)          -> (logits [b,s,V], aux)
    loss(params, batch)             -> (scalar, metrics)
    prefill(params, batch, max_len) -> (last_logits [b,V], cache)
    decode_step(params, cache, tokens [b,1]) -> (logits [b,1,V], cache)
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig, validate
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import ssd as SSD
from repro.models import kvcache as KV
from repro.sharding.ctx import constrain
from repro.models.quant import as_weight


# ---------------------------------------------------------------------------
# per-layer blocks
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, kind: str):
    ks = jax.random.split(key, 4)
    if kind == "ssm":
        return {"norm1": L.rmsnorm_init(cfg.d_model), "ssd": SSD.ssd_init(ks[0], cfg)}
    if kind == "rec":
        return {"norm1": L.rmsnorm_init(cfg.d_model),
                "rec": RG.rglru_init(ks[0], cfg),
                "norm2": L.rmsnorm_init(cfg.d_model),
                "mlp": L.mlp_init(ks[1], cfg)}
    p = {"norm1": L.rmsnorm_init(cfg.d_model),
         "attn": A.attention_init(ks[0], cfg),
         "norm2": L.rmsnorm_init(cfg.d_model)}
    if kind == "attn_moe":
        p["moe"] = MOE.moe_init(ks[1], cfg)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg)
    if kind == "attn_cross":
        p["norm_x"] = L.rmsnorm_init(cfg.d_model)
        p["xattn"] = A.attention_init(ks[2], cfg)
    return p


def _block_seq(p, cfg: ModelConfig, kind: str, x, positions, memory=None,
               mem_positions=None, causal=True):
    """Full-sequence block. Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    x = constrain(x, "dp", None, None)
    h = L.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    if kind == "ssm":
        y, _ = SSD.ssd_apply(p["ssd"], cfg, h)
        return x + y, aux
    if kind == "rec":
        y = RG.rglru_block_apply(p["rec"], cfg, h)
    else:
        y = A.self_attention(p["attn"], cfg, h, positions, causal=causal)
    x = x + y
    if kind == "attn_cross":
        hx = L.rmsnorm_apply(p["norm_x"], x, cfg.norm_eps)
        x = x + A.cross_attention(p["xattn"], cfg, hx, memory, mem_positions)
    h2 = L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
    if kind == "attn_moe":
        y2, aux = MOE.moe_apply(p["moe"], cfg, h2)
    else:
        y2 = L.mlp_apply(p["mlp"], h2)
    return constrain(x + y2, "dp", None, None), aux


def _block_prefill(p, cfg: ModelConfig, kind: str, x, positions, S,
                   memory=None, mem_positions=None, length=None):
    """Sequence pass that also emits the decode cache for this layer.

    ``length`` (traced scalar) is the true prompt length when ``x`` is
    right-padded to a compile bucket: the recurrent families force their
    per-step update to the identity on padded steps and take conv states
    at ``length``, attention relies on causality (padded keys sit strictly
    after every real query) plus decode-side position masking of the buffer
    tail — either way the emitted cache equals the exact-length cache.
    """
    aux = jnp.zeros((), jnp.float32)
    x = constrain(x, "dp", None, None)
    h = L.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    if kind == "ssm":
        y, (conv, ssm) = SSD.ssd_apply(p["ssd"], cfg, h, length=length)
        return x + y, {"conv": conv, "ssm": ssm}, aux
    if kind == "rec":
        # rerun block capturing final recurrence state
        xw = jnp.einsum("bld,dw->blw", h, as_weight(p["rec"]["w_x"]),
                        preferred_element_type=jnp.float32).astype(h.dtype)
        xw, conv_state = RG._causal_conv(p["rec"], xw, length=length)
        a, mult = RG._gates(p["rec"], xw)
        b0 = mult * xw.astype(jnp.float32)
        if length is not None:
            # padded steps: a=1, b=0 — the recurrence is an exact identity,
            # so hs[:, -1] is the state at the true end of the prompt
            valid = jnp.arange(xw.shape[1], dtype=jnp.int32) < length
            a = jnp.where(valid[None, :, None], a, 1.0)
            b0 = jnp.where(valid[None, :, None], b0, 0.0)
        h0 = jnp.zeros((h.shape[0], xw.shape[-1]), jnp.float32)
        hs = RG._scan_lru(a, b0, h0)
        gate = jax.nn.gelu(jnp.einsum("bld,dw->blw", h,
                                      as_weight(p["rec"]["w_gate"]),
                                      preferred_element_type=jnp.float32))
        out = (hs * gate).astype(h.dtype)
        y = jnp.einsum("blw,wd->bld", out, as_weight(p["rec"]["w_out"]),
                       preferred_element_type=jnp.float32).astype(h.dtype)
        x = x + y
        h2 = L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        x = x + L.mlp_apply(p["mlp"], h2)
        return x, {"conv": conv_state, "h": hs[:, -1]}, aux
    # attention kinds
    pos1d = positions[0] if positions.ndim == 3 else positions
    qpos = pos1d[0] if pos1d.ndim == 2 else pos1d
    with jax.named_scope("qkv"):
        k, v = A._project_kv(p["attn"], cfg, h, positions)
        q = A._project_q(p["attn"], cfg, h, positions)
    with jax.named_scope("attn"):
        o = A.full_attention(q, k, v, qpos, qpos, cfg, causal=True)
        b, s = x.shape[0], x.shape[1]
        y = jnp.einsum("bsq,qd->bsd", o.reshape(b, s, cfg.q_dim),
                       as_weight(p["attn"]["w_o"]),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    x = x + y
    with jax.named_scope("kv_write"):
        cache = _kv_to_buffer(cfg, k, v, S, length=length)
    if kind == "attn_cross":
        hx = L.rmsnorm_apply(p["norm_x"], x, cfg.norm_eps)
        x = x + A.cross_attention(p["xattn"], cfg, hx, memory, mem_positions)
        ck, cv = A.project_cross_kv(p["xattn"], cfg, memory)
        cache["cross_k"], cache["cross_v"] = ck, cv
    h2 = L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
    with jax.named_scope("mlp"):
        if kind == "attn_moe":
            y2, aux = MOE.moe_apply(p["moe"], cfg, h2)
        else:
            y2 = L.mlp_apply(p["mlp"], h2)
    return x + y2, cache, aux


def _kv_to_buffer(cfg: ModelConfig, k, v, S, length=None):
    """Place prefill K/V [b, s, kh, hd] into the decode buffer of length S.

    Full attention: slots [0, s). Sliding window: ring layout — token at
    absolute position p lives in slot p % S.

    ``length`` (traced scalar): true prompt length of a right-padded bucket.
    Full attention needs no masking here — buffer rows past ``length`` hold
    padded-K/V garbage that decode never attends (its validity test is
    ``slot index <= position``). The ring layout DOES mask: only positions
    in ``[length - S, length)`` may land in the ring; padded and evicted
    positions are routed to a discard row so they cannot clobber live slots.
    """
    b, s = k.shape[0], k.shape[1]
    if not cfg.sliding_window:
        padk = jnp.zeros((b, S, k.shape[2], k.shape[3]), k.dtype)
        return {"k": jax.lax.dynamic_update_slice_in_dim(padk, k[:, :S], 0, 1),
                "v": jax.lax.dynamic_update_slice_in_dim(padk, v[:, :S], 0, 1)}
    if length is not None:
        pos = jnp.arange(s)
        live = (pos < length) & (pos >= length - S)
        slots = jnp.where(live, pos % S, S)       # S = discard row
        bufk = jnp.zeros((b, S + 1, k.shape[2], k.shape[3]), k.dtype)
        bufv = jnp.zeros_like(bufk)
        bufk = bufk.at[:, slots].set(k)
        bufv = bufv.at[:, slots].set(v)
        return {"k": bufk[:, :S], "v": bufv[:, :S]}
    take = min(s, S)
    ks, vs = k[:, -take:], v[:, -take:]
    slots = (jnp.arange(s - take, s)) % S
    bufk = jnp.zeros((b, S, k.shape[2], k.shape[3]), k.dtype)
    bufv = jnp.zeros_like(bufk)
    bufk = bufk.at[:, slots].set(ks)
    bufv = bufv.at[:, slots].set(vs)
    return {"k": bufk, "v": bufv}


def _keep_active(new, old, active):
    """Freeze a recurrent state leaf for inactive batch rows: parked
    (hibernation-tier) sessions share the fused decode batch but their
    state must not advance — recurrent updates, unlike position-indexed
    KV writes, mutate every row unconditionally."""
    if active is None:
        return new
    a = active.reshape(active.shape + (1,) * (new.ndim - 1))
    return jnp.where(a, new, old.astype(new.dtype))


def _block_decode(p, cfg: ModelConfig, kind: str, x, cache_layer, position,
                  active=None, block=None, layer=None):
    """Single-token block. Returns (x, new_cache_layer).

    ``active`` ([b] bool) masks state updates of inactive rows; ``block``
    ([b, PPS] int32) routes attention K/V through the paged pool layout;
    ``layer`` (int32 scalar) marks ``cache_layer``'s "k"/"v" as the whole
    layer stacks, written and read in place at that layer (see
    ``attention.decode_self_attention``) and returned whole.
    """
    x = constrain(x, "dp", None, None)
    h = L.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    if kind == "ssm":
        y, (conv, ssm) = SSD.ssd_decode(p["ssd"], cfg, h, cache_layer["conv"],
                                        cache_layer["ssm"])
        return x + y, {"conv": _keep_active(conv, cache_layer["conv"], active),
                       "ssm": _keep_active(ssm, cache_layer["ssm"], active)}
    if kind == "rec":
        y, conv, hst = RG.rglru_block_decode(p["rec"], cfg, h,
                                             cache_layer["conv"], cache_layer["h"])
        x = x + y
        h2 = L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        x = x + L.mlp_apply(p["mlp"], h2)
        return x, {"conv": _keep_active(conv, cache_layer["conv"], active),
                   "h": _keep_active(hst, cache_layer["h"], active)}
    window = cfg.sliding_window
    if block is not None:
        y, ck, cv = A.paged_decode_self_attention(
            p["attn"], cfg, h, cache_layer["k"], cache_layer["v"],
            block, position, active=active)
    else:
        y, ck, cv = A.decode_self_attention(p["attn"], cfg, h,
                                            cache_layer["k"],
                                            cache_layer["v"], position,
                                            window=window, active=active,
                                            layer=layer)
    x = x + y
    new_cache = dict(cache_layer)
    new_cache["k"], new_cache["v"] = ck, cv
    if kind == "attn_cross":
        hx = L.rmsnorm_apply(p["norm_x"], x, cfg.norm_eps)
        src = cache_layer["cross_k"].shape[1]
        x = x + A.decode_cross_attention(p["xattn"], cfg, hx,
                                         cache_layer["cross_k"],
                                         cache_layer["cross_v"],
                                         jnp.arange(src))
    h2 = L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
    with jax.named_scope("mlp"):
        if kind == "attn_moe":
            y2, _ = MOE.moe_apply(p["moe"], cfg, h2)
        else:
            y2 = L.mlp_apply(p["mlp"], h2)
    return x + y2, new_cache


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _scan_groups(cfg: ModelConfig) -> int:
    """Two-level scan group count: deep stacks checkpoint √L boundaries."""
    if cfg.remat == "none" or cfg.num_layers < 48:
        return 1
    for g in (8, 6, 4, 3, 2):
        if cfg.num_layers % g == 0:
            return g
    return 1


# ---------------------------------------------------------------------------
# the LM
# ---------------------------------------------------------------------------

class LM:
    """Functional language model. Hold no arrays — just the config."""

    def __init__(self, cfg: ModelConfig):
        validate(cfg)
        self.cfg = cfg

    # -- param init -----------------------------------------------------
    def _trunk_kind(self) -> str:
        if self.cfg.family == "ssm":
            return "ssm"
        if self.cfg.is_moe:
            return "attn_moe"
        return "attn"

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dt = L.dtype_of(cfg)
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": L.embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dt),
            "final_norm": L.rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(keys[1], cfg.d_model,
                                             cfg.padded_vocab, dt)
        if cfg.family == "hybrid":
            lkeys = jax.random.split(keys[2], cfg.num_layers)
            params["layers"] = tuple(
                _block_init(lkeys[i], cfg, "rec" if k == "rec" else "attn")
                for i, k in enumerate(cfg._pattern()))
        elif cfg.family == "encdec":
            ekeys = jax.random.split(keys[2], cfg.encoder_layers)
            dkeys = jax.random.split(keys[3], cfg.num_layers)
            params["enc_layers"] = jax.vmap(
                lambda k: _block_init(k, cfg, "attn"))(ekeys)
            params["layers"] = jax.vmap(
                lambda k: _block_init(k, cfg, "attn_cross"))(dkeys)
            params["enc_norm"] = L.rmsnorm_init(cfg.d_model)
            params["adapter"] = L.dense_init(keys[4], cfg.d_model, cfg.d_model, dt)
        else:
            kind = self._trunk_kind()
            lkeys = jax.random.split(keys[2], cfg.num_layers)
            params["layers"] = jax.vmap(
                lambda k: _block_init(k, cfg, kind))(lkeys)
        if cfg.frontend == "vision":
            params["vision_adapter"] = L.dense_init(keys[5], cfg.d_model,
                                                    cfg.d_model, dt)
        return params

    def param_specs(self):
        return jax.eval_shape(lambda k: self.init(k), jax.random.key(0))

    # -- input embedding --------------------------------------------------
    def _embed(self, params, batch):
        cfg = self.cfg
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        x = constrain(x, "dp", None, None)
        if cfg.frontend == "vision" and "vision_embeds" in batch:
            nv = batch["vision_embeds"].shape[1]
            ve = jnp.einsum("bnd,de->bne", batch["vision_embeds"].astype(x.dtype),
                            as_weight(params["vision_adapter"]),
                            preferred_element_type=jnp.float32).astype(x.dtype)
            x = jax.lax.dynamic_update_slice_in_dim(x, ve, 0, axis=1)
        return x

    def _positions(self, batch, s):
        cfg = self.cfg
        if "positions" in batch:
            return batch["positions"]
        pos = jnp.arange(s, dtype=jnp.int32)
        if cfg.mrope_sections:
            return jnp.broadcast_to(pos[None, None], (3, 1, s))
        return pos

    def _logits(self, params, h):
        cfg = self.cfg
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        with jax.named_scope("head"):
            logits = jnp.einsum("...d,dv->...v", h, head,
                                preferred_element_type=jnp.float32)
            logits = constrain(logits, *(["dp"] + [None] * (logits.ndim - 2)
                                         + ["model"]))
            if cfg.padded_vocab != cfg.vocab_size:   # mask the padding tail
                pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
                logits = jnp.where(pad_mask, logits, -1e30)
            return L.softcap(logits, cfg.logits_softcap)

    # -- encoder ----------------------------------------------------------
    def _encode(self, params, frames):
        cfg = self.cfg
        x = jnp.einsum("bsd,de->bse", frames.astype(L.dtype_of(cfg)),
                       as_weight(params["adapter"]),
                       preferred_element_type=jnp.float32).astype(L.dtype_of(cfg))
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)

        def body(h, lp):
            h, _ = _block_seq(lp, cfg, "attn", h, pos, causal=False)
            return h, None

        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["enc_layers"])
        return L.rmsnorm_apply(params["enc_norm"], x, cfg.norm_eps)

    # -- full-sequence forward (training) ---------------------------------
    def forward(self, params, batch):
        h, aux = self.forward_hidden(params, batch)
        return self._logits(params, h), aux

    def forward_hidden(self, params, batch):
        cfg = self.cfg
        x = self._embed(params, batch)
        s = x.shape[1]
        pos = self._positions(batch, s)
        memory = mem_pos = None
        if cfg.family == "encdec":
            memory = self._encode(params, batch["frames"])
            mem_pos = jnp.arange(memory.shape[1], dtype=jnp.int32)

        aux = jnp.zeros((), jnp.float32)
        if cfg.family == "hybrid":
            for lp, kind in zip(params["layers"], cfg._pattern()):
                def fn(lp_, h_, kind=kind):
                    return _block_seq(lp_, cfg, kind, h_, pos)
                x, a = _maybe_remat(fn, cfg)(lp, x)
                aux = aux + a
        else:
            kind = ("attn_cross" if cfg.family == "encdec"
                    else self._trunk_kind())

            def body(carry, lp):
                h, ax = carry
                h, a = _block_seq(lp, cfg, kind, h, pos, memory=memory,
                                  mem_positions=mem_pos)
                return (h, ax + a), None

            groups = _scan_groups(cfg)
            if groups > 1:
                # two-level (√L) checkpointing: only group boundaries are
                # saved in forward; one group's layer carries re-materialise
                # at a time in backward — stacked-carry footprint drops from
                # L·|x| to (G + L/G)·|x| (10.7 GB → ~2.4 GB for the 80-layer
                # qwen2-vl train cell).
                per = cfg.num_layers // groups
                grouped = jax.tree.map(
                    lambda p: p.reshape((groups, per) + p.shape[1:]),
                    params["layers"])

                def group_body(carry, glp):
                    out, _ = jax.lax.scan(_maybe_remat(body, cfg), carry, glp)
                    return out, None

                (x, aux), _ = jax.lax.scan(_maybe_remat(group_body, cfg),
                                           (x, aux), grouped)
            else:
                (x, aux), _ = jax.lax.scan(_maybe_remat(body, cfg), (x, aux),
                                           params["layers"])
        x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        return x, aux

    # -- loss ---------------------------------------------------------------
    def loss(self, params, batch, *, ce_chunk: int = 512):
        """Chunked cross-entropy: logits are materialised ``ce_chunk``
        positions at a time (scan + checkpoint), never the full [b, s, V']
        slab — the unfused f32 CE pipeline over a 16k-wide sharded vocab
        otherwise holds ~17 live 1 GB buffers (observed, recurrentgemma
        train_4k). Also a real perf win: the loss becomes bandwidth-, not
        capacity-, limited."""
        cfg = self.cfg
        h, aux = self.forward_hidden(params, batch)
        labels = batch["labels"]
        b, s, d = h.shape
        cs = min(ce_chunk, s)
        if s % cs:
            cs = next(c for c in range(cs, 0, -1) if s % c == 0)
        ns = s // cs

        def chunk_ce(hc, lc):
            logits = self._logits(params, hc)           # [b, cs, V'] f32
            mask = (lc >= 0).astype(jnp.float32)
            lcc = jnp.maximum(lc, 0)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, lcc[..., None], axis=-1)[..., 0]
            nll = (logz - gold) * mask
            return jnp.sum(nll), jnp.sum(mask)

        if ns == 1:
            tot, ntok = chunk_ce(h, labels)
        else:
            hc = jnp.moveaxis(h.reshape(b, ns, cs, d), 1, 0)
            lc = jnp.moveaxis(labels.reshape(b, ns, cs), 1, 0)

            def step(acc, xs):
                t, n = acc
                tt, nn = chunk_ce(*xs)
                return (t + tt, n + nn), None

            body = (jax.checkpoint(step) if cfg.remat != "none" else step)
            (tot, ntok), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                (hc, lc))
        ntok = jnp.maximum(ntok, 1.0)
        ce = tot / ntok
        total = ce + 0.01 * aux
        return total, {"ce": ce, "aux": aux, "ntok": ntok}

    # -- prefill ------------------------------------------------------------
    def prefill(self, params, batch, max_len: int, adapter=None):
        """Build the decode cache for one prompt.

        ``batch["length"]`` (optional traced int32 scalar) marks the true
        prompt length when ``batch["tokens"]`` is right-padded to a compile
        bucket: the cache position, final logits, and every family's carried
        state are taken at ``length`` rather than the padded width, so the
        engine compiles O(log max_len) prefill variants instead of one per
        distinct prompt length (see InferenceEngine.prefill_session).

        ``adapter`` (optional ``(A [d, r], B [r, d])``): per-session LoRA
        delta applied to the final hidden state before the LM head — the
        KV cache is adapter-free, so exported state stays shape-identical
        to the base model's.
        """
        cfg = self.cfg
        x = self._embed(params, batch)
        s = x.shape[1]
        S = KV.kv_buffer_len(cfg, max_len)
        pos = self._positions(batch, s)
        length = batch.get("length")
        memory = mem_pos = None
        if cfg.family == "encdec":
            memory = self._encode(params, batch["frames"])
            mem_pos = jnp.arange(memory.shape[1], dtype=jnp.int32)

        if cfg.family == "hybrid":
            layers_cache = []
            for lp, kind in zip(params["layers"], cfg._pattern()):
                kk = "rec" if kind == "rec" else "attn"

                def fn(lp_, h_, kk=kk):
                    return _block_prefill(lp_, cfg, kk, h_, pos, S,
                                          length=length)
                x, cl, _ = _maybe_remat(fn, cfg)(lp, x)
                layers_cache.append(cl)
            cache = {"layers": tuple(layers_cache),
                     "pos": self._prefill_pos(x, s, length)}
        elif cfg.family == "ssm":
            def body(h, lp):
                h, cl, _ = _block_prefill(lp, cfg, "ssm", h, pos, S,
                                          length=length)
                return h, cl

            x, stacked = jax.lax.scan(_maybe_remat(body, cfg), x,
                                      params["layers"])
            cache = {"layers": stacked, "pos": self._prefill_pos(x, s, length)}
        else:
            kind = ("attn_cross" if cfg.family == "encdec"
                    else self._trunk_kind())

            def body(h, lp):
                h, cl, _ = _block_prefill(lp, cfg, kind, h, pos, S,
                                          memory=memory, mem_positions=mem_pos,
                                          length=length)
                return h, cl

            x, stacked = jax.lax.scan(_maybe_remat(body, cfg), x,
                                      params["layers"])
            cache = {"layers": {"k": stacked["k"], "v": stacked["v"]},
                     "pos": self._prefill_pos(x, s, length)}
            if cfg.family == "encdec":
                cache["cross_k"] = stacked["cross_k"]
                cache["cross_v"] = stacked["cross_v"]
        if length is None:
            x_last = x[:, -1]
        else:
            x_last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
        x_last = L.rmsnorm_apply(params["final_norm"], x_last, cfg.norm_eps)
        if adapter is not None:
            from repro.adapters.runtime import lora_apply_rows
            x_last = x_last + lora_apply_rows(x_last, adapter[0], adapter[1])
        return self._logits(params, x_last), cache

    @staticmethod
    def _prefill_pos(x, s, length):
        if length is None:
            return jnp.full((x.shape[0],), s, jnp.int32)
        return jnp.broadcast_to(jnp.asarray(length, jnp.int32), (x.shape[0],))

    # -- decode ---------------------------------------------------------------
    def decode_step(self, params, cache, tokens, active=None, adapter=None):
        """tokens: [b, 1] -> (logits [b, 1, V], updated cache).

        ``adapter`` (optional ``(A [E, d, r], B [E, r, d], idx [b],
        route)``): stacked LoRA tables plus the per-slot int32 adapter
        table. Each row's delta is gathered by ``idx`` and added to the
        final hidden state before the LM head; index 0 is the null
        adapter (exact zero delta), so base sessions are bit-identical
        with or without the tables.

        ``active`` ([b] bool, optional): rows whose state may advance this
        step. Inactive rows (parked sessions, empty slots) still flow through
        the batch — their logits are computed and discarded — but every cache
        leaf they own is left bit-identical, so a session can idle inside the
        fused batch indefinitely and resume exactly where it stopped.

        A cache carrying a ``"block"`` leaf selects the paged-KV layout
        (``repro.models.kvcache.init_paged_cache``): per-layer K/V are page
        pools indexed through the per-slot block table instead of dense
        [b, S] buffers.
        """
        cfg = self.cfg
        position = cache["pos"]
        block = cache.get("block")
        x = jnp.take(params["embed"], tokens, axis=0)

        if cfg.family == "hybrid":
            new_layers = []
            for lp, cl, kind in zip(params["layers"], cache["layers"],
                                    cfg._pattern()):
                kk = "rec" if kind == "rec" else "attn"
                x, ncl = _block_decode(lp, cfg, kk, x, cl, position,
                                       active=active)
                new_layers.append(ncl)
            new_cache = {"layers": tuple(new_layers), "pos": position + 1}
        else:
            kind = ("attn_cross" if cfg.family == "encdec"
                    else ("ssm" if cfg.family == "ssm"
                          else self._trunk_kind()))
            # The stacked cache rides the scan CARRY (not xs/ys): per-layer
            # dynamic_index + in-place dynamic_update keep ONE buffer alive,
            # avoiding the xs→ys double-buffer copy of the whole KV cache
            # (~2× cache bytes of temp, observed 13–33 GB/device).
            layer_cache = dict(cache["layers"])
            if cfg.family == "encdec":
                layer_cache["cross_k"] = cache["cross_k"]
                layer_cache["cross_v"] = cache["cross_v"]
            L_layers = cfg.num_layers
            # dense K/V stay whole: the attention writes the layer's row
            # into the stack and its kernel reads the stack in place, so
            # no layer slice is handed to it (a custom call's operand is a
            # buffer of its own: a slice would be copied every layer)
            whole = () if block is not None or kind == "ssm" else ("k", "v")

            def body(carry, xs):
                h, cstack = carry
                lp, idx = xs
                cl = {key: c if key in whole else
                      jax.lax.dynamic_index_in_dim(c, idx, axis=0,
                                                   keepdims=False)
                      for key, c in cstack.items()}
                h, ncl = _block_decode(lp, cfg, kind, h, cl, position,
                                       active=active, block=block,
                                       layer=idx if whole else None)
                # write back only the mutated leaves (cross K/V are static)
                def upd(c, n):
                    return jax.lax.dynamic_update_index_in_dim(
                        c, n.astype(c.dtype), idx, axis=0)
                new_stack = dict(cstack)
                for key in ("k", "v", "conv", "ssm"):
                    if key in ncl and key in cstack:
                        new_stack[key] = (ncl[key] if key in whole
                                          else upd(cstack[key], ncl[key]))
                return (h, new_stack), None

            (x, stacked), _ = jax.lax.scan(
                body, (x, layer_cache),
                (params["layers"], jnp.arange(L_layers, dtype=jnp.int32)))
            new_cache = {"layers": {k: v for k, v in stacked.items()
                                    if not k.startswith("cross_")},
                         "pos": position + 1}
            if block is not None:
                new_cache["block"] = block
            if cfg.family == "encdec":
                new_cache["cross_k"] = cache["cross_k"]
                new_cache["cross_v"] = cache["cross_v"]
        x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        if adapter is not None:
            from repro.adapters.runtime import lora_delta
            adp_a, adp_b, adp_idx, route = adapter
            delta = lora_delta(x[:, 0], adp_a, adp_b, adp_idx, route=route)
            x = x + delta[:, None]
        return self._logits(params, x), new_cache

    # -- cache helpers ----------------------------------------------------
    def init_cache(self, batch: int, max_len: int, *, abstract=False):
        return KV.init_cache(self.cfg, batch, max_len, abstract=abstract)

    def init_paged_cache(self, slots: int, max_len: int, num_pages: int,
                         page_size: int, *, abstract=False):
        return KV.init_paged_cache(self.cfg, slots, max_len, num_pages,
                                   page_size, abstract=abstract)
