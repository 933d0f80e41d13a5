"""Operations and bytes of the served work, from the configuration's
shapes alone: the same numbers whatever implements the kernels.

Counts are the model's: 2 operations per multiply-add of every matmul a
token needs, the attention scores and values over the cached context, and
the output head over the padded vocabulary the served model computes.
"""

from __future__ import annotations


def _padded_vocab(m: dict) -> int:
    return -(-m["vocab_size"] // 256) * 256


def matmul_params(m: dict) -> int:
    """Weights one token multiplies through: every layer's projections and
    the output head (embedding lookups are gathers, not matmuls)."""
    d = m["d_model"]
    if m["family"] == "ssm":
        di = m["ssm_expand"] * d
        g, n = m.get("ssm_ngroups", 1), m["ssm_state"]
        nh = di // m["ssm_headdim"]
        per = d * (2 * di + 2 * g * n + nh) + di * d
    else:
        q = m["num_heads"] * m["head_dim"]
        kv = m["num_kv_heads"] * m["head_dim"]
        per = d * q + 2 * d * kv + q * d + 3 * d * m["d_ff"]
    return m["num_layers"] * per + d * _padded_vocab(m)


def decode_work(m: dict, slot_steps: int, keys: int) -> float:
    """Operations of ``slot_steps`` decode steps (one sequence, one token
    each) that attend ``keys`` cached positions in all."""
    per_step = 2.0 * matmul_params(m)
    if m["family"] == "ssm":
        # state update dt*x*B and readout S.C: 2 ops each per state element
        di = m["ssm_expand"] * m["d_model"]
        return slot_steps * (per_step
                             + m["num_layers"] * 4.0 * di * m["ssm_state"])
    return slot_steps * per_step + attention_work(m, slot_steps, keys)[0]


def attention_work(m: dict, slot_steps: int, keys: int) -> tuple:
    """(operations, bytes) of the decode-attention calls of ``slot_steps``
    steps attending ``keys`` cached positions in all, every layer: scores
    and values (4 operations per query element and key), and the K and V
    rows read with each step's query and output (bfloat16)."""
    kv = m["num_kv_heads"] * m["head_dim"]
    q = m["num_heads"] * m["head_dim"]
    L = m["num_layers"]
    return (L * 4.0 * q * keys,
            L * 2.0 * (2.0 * kv * keys + 2.0 * q * slot_steps))
