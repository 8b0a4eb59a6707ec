"""Device time of the routing collectives with no other operation beside
them, over the device's busy time, averaged over the chips, in %."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0 or t.collective_alone_s <= 0:
        return None
    return 100.0 * t.collective_alone_s / t.busy_s
