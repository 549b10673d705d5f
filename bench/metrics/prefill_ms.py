"""Mean time from a request's admission to its first token (its prefill
and the slot copy in and out of the cache), from the engine's per-request
``admitted`` and ``first_token``."""


def read(run):
    res = run.counters.get("results")
    if not res:
        return None
    vals = [r["first_token"] - r["admitted"] for r in res.values()]
    return 1e3 * sum(vals) / len(vals)
