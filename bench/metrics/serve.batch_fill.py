"""Mean requests per served batch (the ``n`` of the engine's ``plan``
spans in the window) over the batch size, in %."""
from bench.readers import mean


def read(run):
    d = run.driver
    fills = [m["n"] / d.batch for _, _, m in d.window_spans("plan")]
    v = mean(fills)
    return None if v is None else 100.0 * v
