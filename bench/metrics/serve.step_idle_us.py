"""Device idle that the traced stretch puts under the engine's ``plan``
span and the spans nested in it (``prefill``, ``token_sync``,
``dispatch``), per decode step of the traced calls, in us.  A program
without the nested spans puts all of it under ``plan``."""
from bench.readers import traced_calls

PLAN = ("plan", "prefill", "token_sync", "dispatch")


def read(run):
    t = run.trace
    calls = traced_calls(run)
    if t is None or not calls:
        return None
    steps = len(calls) * run.driver.new_tokens
    return 1e6 * sum(t.idle.get(n, 0.0) for n in PLAN) / steps
