"""The plain float32 references against the served engine, at a small size
on the CPU: prefill then decoding through the cache must agree with the
reference's whole-sequence forward."""

import numpy as np
import pytest

from benchlib import reference as R
from small import SMALL_MODEL, small_cell


def _engine(workload, seed=3):
    import jax
    import run as harness
    from benchlib.weights import make_weights
    from repro.models.transformer import LM
    from repro.serving.engine import InferenceEngine
    cell = small_cell(workload)
    cfg = harness.model_config(cell.config)
    params = make_weights(jax.eval_shape(LM(cfg).init, jax.random.key(0)),
                          seed)
    eng = InferenceEngine(cfg, params=params, slots=2, max_len=128)
    return cell.config["model"], params, eng


@pytest.mark.parametrize("workload", ["mamba2-1.3b.chat-burst",
                                      "minitron-8b-4L.long-decode"])
def test_prefill_and_cached_decode_match_reference(workload):
    model, params, eng = _engine(workload)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, model["vocab_size"], 37).astype(np.int32)
    first = eng.prefill_session("s", prompt)["first_token"]
    served = [first] + eng.decode_round(steps=12)["s"]
    seq = np.concatenate([prompt, served[:-1]])
    ref = np.asarray(R.logits(model, params, seq))
    # bf16 activations against float32: logits agree to a few hundredths
    # of their spread (|logit| ~ 1 with these weights)
    rows = ref[len(prompt) - 1:]
    picked = rows[np.arange(len(served)), served]
    gaps = rows.max(-1) - picked
    assert gaps.max() < 0.05, gaps
    g, _ = R.score_request(model, params, prompt, served)
    np.testing.assert_allclose(g, gaps, atol=1e-4)


@pytest.mark.parametrize("family", sorted(SMALL_MODEL))
def test_reference_is_causal_under_padding(family):
    """Padding after the last token (how score_request buckets lengths)
    changes no logit before it."""
    wl = {"ssm": "mamba2-1.3b.chat-burst",
          "dense": "minitron-8b-4L.long-decode"}[family]
    model, params, _ = _engine(wl)
    toks = np.arange(1, 30, dtype=np.int32)
    a = np.asarray(R.logits(model, params, toks))
    b = np.asarray(R.logits(model, params,
                            np.concatenate([toks, np.zeros(11, np.int32)])))
    np.testing.assert_allclose(a, b[:len(toks)], rtol=1e-5, atol=1e-5)


def test_control_rounds_weights_to_int8():
    import jax.numpy as jnp
    w = jnp.asarray(np.random.default_rng(1).normal(size=(64, 32)),
                    jnp.float32)
    q = np.asarray(R._w(w, True))
    step = np.abs(np.asarray(w)).max(0) / 127.0
    assert np.all(np.abs(q - np.asarray(w)) <= step / 2 + 1e-7)
    assert len(np.unique(np.round(q / step))) <= 255
