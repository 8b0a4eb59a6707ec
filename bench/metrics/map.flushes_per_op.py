"""The program's coalesced flushes (``CommitStats.coalesced_flushes``,
summed per round) over every map operation of the window."""


def read(run):
    d = run.driver
    ops = len(d.records) * d.rounds.ops_per_round
    if not ops or not d.rounds.updates:
        return None
    return sum(fl for _, _, fl in d.records) / ops
