"""Mean duration of the dedup map's ``dedup_round`` spans that lie inside
the engine's ``commit`` spans in the window, in ms.  A program without
the span gives nothing to read."""
from bench.readers import mean


def read(run):
    d = run.driver
    commits = d.window_spans("commit")
    return mean([(e - s) / 1e6 for s, e, _ in d.window_spans("dedup_round")
                 if any(cs <= s and e <= ce for cs, ce, _ in commits)])
