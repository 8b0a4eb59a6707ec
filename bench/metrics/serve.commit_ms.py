"""Mean self time of the serving engine's ``commit`` span per batch in
the window (its ``flush_fence`` child taken out), in ms."""
from bench.readers import mean


def read(run):
    d = run.driver
    fences = d.window_spans("flush_fence")
    selfs = []
    for s, e, _ in d.window_spans("commit"):
        inner = sum(fe - fs for fs, fe, _ in fences if fs >= s and fe <= e)
        selfs.append((e - s - inner) / 1e6)
    return mean(selfs)
