"""Wall time of the fused decode rounds (``RealEngineBackend.decode_round``,
which blocks until the tokens are on the host) per decode step."""


def read(run):
    rs = [c for c in run.driver.window_calls(run.driver.c.rounds) if c.steps]
    steps = sum(c.steps for c in rs)
    return 1e3 * sum(c.t1 - c.t0 for c in rs) / steps if steps else None
