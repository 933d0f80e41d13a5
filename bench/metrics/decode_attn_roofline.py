"""The Pallas decode-attention kernel's share of its roofline: the least
time the chip could take for the kernel's work in the traced window (the
larger of its operations over peak FLOP/s and its bytes over peak HBM
bandwidth; the work is each active sequence's K and V rows up to its
position, with its query and output, every layer and step, from
``benchlib/flops.py``) over the kernel's device time in the trace, in
percent. Bytes bound it at these shapes (about 1 operation per byte)."""

from benchlib import trace as TR
from benchlib.flops import attention_work


def is_kernel(op: str, model: dict, slots: int) -> bool:
    """Whether an operation of the trace's op line is this kernel: by its
    own name once the Pallas call is given one, and until then as the
    Mosaic custom call whose output is the kernel's [slots, kv heads,
    query heads per kv head, head size] tile in bfloat16."""
    if "decode_attention" in op:
        return True
    kh, hd = model["num_kv_heads"], model["head_dim"]
    tile = f" = bf16[{slots},{kh},{model['num_heads'] // kh},{hd}]"
    return 'custom_call_target="tpu_custom_call"' in op and tile in op


def read(run):
    slots = run.serving["slots"]
    ks = TR.op_seconds(run.raw_trace, run.trace,
                       lambda op: is_kernel(op, run.model, slots))
    if not ks:
        return None
    rs = [c for c in run.driver.window_calls(run.driver.c.rounds) if c.steps]
    fl = by = 0.0
    for c in rs:
        f, b = attention_work(run.model, c.slot_steps, c.keys)
        fl, by = fl + f, by + b
    least = max(fl / run.peak["flops_bf16"], by / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / ks
