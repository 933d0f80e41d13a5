"""The control: the reference computed as int8 x int8 products (the step
below the served bfloat16), put in the program's place at the same
positions, must come out as not correct where the served program comes out
correct. At the cells' own sizes this is read on the chip by
``bench/calibrate.py limits`` (PERF.md gives the readings); here the same
comparison runs at CPU size on tokens the engine served."""

import jax
import numpy as np
import pytest

from benchlib import reference as R
from small import SMALL_LIMITS, small_cell


def _served(workload, seed, n=8, chunks=3):
    import run as harness
    from benchlib.weights import make_weights
    from repro.models.transformer import LM
    from repro.serving.engine import InferenceEngine
    cell = small_cell(workload)
    cfg = harness.model_config(cell.config)
    model = cell.config["model"]
    params = make_weights(jax.eval_shape(LM(cfg).init, jax.random.key(0)),
                          seed)
    eng = InferenceEngine(cfg, params=params, slots=n, max_len=128)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, model["vocab_size"],
                            int(rng.integers(8, 40))).astype(np.int32)
               for _ in range(n)]
    served = [[eng.prefill_session(f"s{i}", p)["first_token"]]
              for i, p in enumerate(prompts)]
    for _ in range(chunks):
        out = eng.decode_round(steps=16)
        for i in range(n):
            served[i] += out[f"s{i}"]
    return model, params, prompts, served


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("workload", ["mamba2-1.3b.chat-burst",
                                      "minitron-8b-4L.long-decode"])
def test_int8_control_fails_where_the_program_passes(workload, seed):
    import run as harness
    model, params, prompts, served = _served(workload, seed)
    g, c = [], []
    for p, s in zip(prompts, served):
        a, b = R.score_request(model, params, p, s, control=True)
        g.append(a)
        c.append(b)
    limits = SMALL_LIMITS[model["family"]]
    served_cmp = {"failed": {"value": 0, "limit": 0},
                  "malformed": {"value": 0, "limit": 0},
                  **harness.gap_numbers(g, limits)}
    assert harness.verdict(served_cmp, limits), served_cmp
    control_cmp = harness.gap_numbers(c, limits)
    assert not harness.verdict(control_cmp, limits, served=False), \
        control_cmp
