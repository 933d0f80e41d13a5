"""Plain float32 references of the served families, and the comparison
that decides ``correct``.

Each reference is the model's forward pass over a whole sequence in
straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``
with no cache, no kernels and no batching. It follows what the
configuration computes (``bench/configs/<name>.json`` lists every way that
differs from the published model) and reads its sizes from that file and its
weights from the tree ``benchlib.weights`` drew. It imports nothing of the
program.

The comparison teacher-forces the reference on a served request's prompt
and served tokens and reads, at each served position, the gap by which the
served token's logit lies below the reference's best logit there (0 where
the served token is the reference's own choice).

The control is the same reference with every matmul weight rounded to int8
(symmetric, per output channel), the step below the served bfloat16: at the
same positions it reads the gap of the token the int8 model puts first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: sequences are right-padded to a power of two of at least this, so a run
#: compiles a few reference programs rather than one per length (both
#: families are causal: padding after the last real token changes nothing
#: before it)
PAD = 256
#: bytes of one float32 block of the output head
_HEAD_BLOCK_BYTES = 1 << 30


def _int8(a, axis):
    """``a`` rounded to int8 with one symmetric scale per slice along
    ``axis`` (the values the int8 product sees, in float32)."""
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0,
                    1e-12)
    return jnp.clip(jnp.round(a / s), -127, 127) * s


def _w(a, int8: bool):
    """A weight in float32; with ``int8`` rounded to int8 per output
    channel (the last axis) first."""
    a = a.astype(jnp.float32)
    return _int8(a, -2) if int8 else a


def _mm(x, w, int8: bool):
    """x @ w in float32; with ``int8`` an int8 x int8 product: activations
    rounded per token, weights per output channel."""
    if int8:
        x = _int8(x, -1)
    return x @ _w(w, int8)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """Rotate halves: x [T, heads, hd] at positions 0..T-1."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dense_layer(m, int8, x, lp):
    T = x.shape[0]
    H, KH, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    a = lp["attn"]
    h = _rms(x, lp["norm1"]["scale"], eps)
    q = _rope(_mm(h, a["w_q"], int8).reshape(T, H, hd), m["rope_theta"])
    k = _rope(_mm(h, a["w_k"], int8).reshape(T, KH, hd), m["rope_theta"])
    v = _mm(h, a["w_v"], int8).reshape(T, KH, hd)
    q = q.reshape(T, KH, H // KH, hd)
    s = jnp.einsum("qhgd,khd->hgqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", p, v).reshape(T, H * hd)
    x = x + _mm(o, a["w_o"], int8)
    h = _rms(x, lp["norm2"]["scale"], eps)
    f = lp["mlp"]
    g = jax.nn.silu(_mm(h, f["w_gate"], int8)) * _mm(h, f["w_up"], int8)
    return x + _mm(g, f["w_down"], int8)


def _ssm_layer(m, int8, x, lp):
    T = x.shape[0]
    d, eps = m["d_model"], m["norm_eps"]
    di = m["ssm_expand"] * d
    n, hp, g = m["ssm_state"], m["ssm_headdim"], m.get("ssm_ngroups", 1)
    nh = di // hp
    p = lp["ssd"]
    h = _rms(x, lp["norm1"]["scale"], eps)
    zx = _mm(h, p["in_proj"], int8)
    z, xbc, dt = zx[:, :di], zx[:, di:2 * di + 2 * g * n], zx[:, 2 * di + 2 * g * n:]
    cw = p["conv"].astype(jnp.float32)                 # [K, channels]
    K = cw.shape[0]
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[i:i + T] * cw[i] for i in range(K)))
    xs = xbc[:, :di].reshape(T, nh, hp)
    B = jnp.repeat(xbc[:, di:di + g * n].reshape(T, g, n), nh // g, axis=1)
    C = jnp.repeat(xbc[:, di + g * n:].reshape(T, g, n), nh // g, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])            # [T, nh]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    def step(S, inp):                                  # one token
        dt_t, x_t, B_t, C_t = inp
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    _, y = jax.lax.scan(step, jnp.zeros((nh, hp, n), jnp.float32),
                        (dt, xs, B, C))
    y = (y + xs * p["D"].astype(jnp.float32)[None, :, None]).reshape(T, di)
    y = _rms(y * jax.nn.silu(z), p["norm"]["scale"], eps)
    return x + _mm(y, p["out_proj"], int8)


def _hidden(m, params, tokens, int8):
    """Final-normed hidden states [T, d] of the whole sequence."""
    layer = _ssm_layer if m["family"] == "ssm" else _dense_layer
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, lp):
        return layer(m, int8, x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _rms(x, params["final_norm"]["scale"], m["norm_eps"])


def _head(m, params):
    """The output head as [d, padded vocab] (tied: the embedding's
    transpose)."""
    if m.get("tie_embeddings"):
        return params["embed"].T
    return params["lm_head"]


def _blocks(padded: int, d: int) -> int:
    units = padded // 256
    for nb in range(1, units + 1):
        if units % nb == 0 and (padded // nb) * d * 4 <= _HEAD_BLOCK_BYTES:
            return nb
    return units


def _gaps(m, params, h, hc, targets, control):
    """Per-position gaps below the reference's best logit: of ``targets``
    (the served tokens), and of the control's first choice."""
    head = _head(m, params)
    d, padded = head.shape
    vocab = m["vocab_size"]
    nb = _blocks(padded, d)
    vb = padded // nb
    n = h.shape[0]

    def block(carry, i):
        best, tgt, cbest, cref = carry
        w = jax.lax.dynamic_slice_in_dim(head, i * vb, vb, axis=1)
        cols = i * vb + jnp.arange(vb)
        live = cols < vocab
        lg = jnp.where(live, h @ w.astype(jnp.float32), -jnp.inf)
        best = jnp.maximum(best, lg.max(-1))
        hit = cols[None, :] == targets[:, None]
        tgt = tgt + jnp.sum(jnp.where(hit, lg, 0.0), axis=-1)
        if control:
            lc = jnp.where(live, _mm(hc, w, True), -jnp.inf)
            j = jnp.argmax(lc, axis=-1)
            cmax = jnp.take_along_axis(lc, j[:, None], -1)[:, 0]
            at = jnp.take_along_axis(lg, j[:, None], -1)[:, 0]
            better = cmax > cbest
            cbest = jnp.where(better, cmax, cbest)
            cref = jnp.where(better, at, cref)
        return (best, tgt, cbest, cref), None

    init = (jnp.full((n,), -jnp.inf), jnp.zeros((n,)),
            jnp.full((n,), -jnp.inf), jnp.zeros((n,)))
    (best, tgt, _, cref), _ = jax.lax.scan(block, init, jnp.arange(nb))
    return best - tgt, (best - cref if control else None)


@functools.partial(jax.jit, static_argnames=("mkey", "control"))
def _score(params, tokens, targets, first, *, mkey, control):
    m = dict(mkey)
    with jax.default_matmul_precision("highest"):
        h = _hidden(m, params, tokens, False)
        hc = _hidden(m, params, tokens, True) if control else None
        idx = first + jnp.arange(targets.shape[0])
        hs = jnp.take(h, idx, axis=0, mode="clip")
        hcs = jnp.take(hc, idx, axis=0, mode="clip") if control else None
        return _gaps(m, params, hs, hcs, targets, control)


def model_key(model: dict) -> tuple:
    """The configuration's sizes as a hashable static argument. A feature
    the reference does not compute is an error, not a silent difference."""
    for k in ("sliding_window", "attn_logits_softcap", "logits_softcap",
              "use_qk_norm", "num_experts"):
        if model.get(k):
            raise NotImplementedError(f"the reference has no {k}")
    keep = ("family", "num_layers", "d_model", "num_heads", "num_kv_heads",
            "head_dim", "d_ff", "vocab_size", "rope_theta", "norm_eps",
            "tie_embeddings", "ssm_state", "ssm_headdim", "ssm_expand",
            "ssm_ngroups")
    return tuple(sorted((k, model[k]) for k in keep if k in model))


@functools.partial(jax.jit, static_argnames=("mkey",))
def _logits(params, tokens, *, mkey):
    m = dict(mkey)
    with jax.default_matmul_precision("highest"):
        h = _hidden(m, params, tokens, False)
        head = _head(m, params)[:, :m["vocab_size"]].astype(jnp.float32)
        return h @ head


def logits(model: dict, params, tokens):
    """Reference logits [T, vocab] at every position of ``tokens``."""
    return _logits(params, jnp.asarray(tokens, jnp.int32),
                   mkey=model_key(model))


def _pow2(n: int, least: int) -> int:
    p = least
    while p < n:
        p *= 2
    return p


def score_request(model: dict, params, prompt, served, *, control=False):
    """Gaps at every served position of one request.

    ``prompt`` and ``served`` are token ids; served[i] was produced from
    prompt + served[:i]. Returns (served_gaps, control_gaps or None), one
    float per served token."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    T = _pow2(len(seq), PAD)
    toks = np.zeros(T, np.int32)
    toks[:len(seq)] = seq
    G = len(served)
    Gp = _pow2(G, PAD // 4)
    tg = np.zeros(Gp, np.int32)
    tg[:G] = served
    g, c = _score(params, jnp.asarray(toks), jnp.asarray(tg),
                  jnp.int32(len(prompt) - 1), mkey=model_key(model),
                  control=control)
    g = np.asarray(g, np.float64)[:G]
    c = None if c is None else np.asarray(c, np.float64)[:G]
    return g, c
