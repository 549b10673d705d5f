"""Mean wall time of one batched decode step, from the engine's own
``decode_step_times`` (host clock around the step and its blocking copy of
the tokens to the host), over the window."""


def read(run):
    t = run.counters.get("decode_step_times")
    return 1e3 * sum(t) / len(t) if t else None
