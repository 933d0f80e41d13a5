"""Share of the window the host spent admitting requests into the engine
(``RealEngineBackend.admit``: prefill and slot install), in percent."""


def read(run):
    d = run.driver
    lo, _ = run.window
    calls = d.window_calls(d.c.admits) + d.window_calls(d.c.rounds)
    if not calls:
        return None
    span = max(c.t1 for c in calls) - lo
    return 100.0 * sum(c.t1 - c.t0 for c in d.window_calls(d.c.admits)) / span
