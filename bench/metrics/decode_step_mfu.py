"""The whole decode step's share of the chip's peak: the model operations
of the decode steps run in the window (active sequences only, from the
configuration's shapes by ``benchlib/flops.py``) over the device time of
the fused decode program that ran them (its runs on the trace's XLA
Modules line), over the bf16 peak, in percent."""

from benchlib import trace as TR
from benchlib.flops import decode_work

#: the fused K-step decode program (``InferenceEngine._fused_impl``) as the
#: trace names its runs: ``jit__fused_impl(<program id>)``
PROGRAM = "jit__fused_impl("


def read(run):
    dev = TR.module_seconds(run.raw_trace, run.trace,
                            lambda name: name.startswith(PROGRAM))
    if not dev:
        return None
    rs = [c for c in run.driver.window_calls(run.driver.c.rounds) if c.steps]
    work = sum(decode_work(run.model, c.slot_steps, c.keys) for c in rs)
    return 100.0 * work / dev / run.peak["flops_bf16"]
