"""Share of the traced stretch of a map cell in which no operation ran
on the device, averaged over the chips, in %."""
from bench.readers import device_idle


def read(run):
    return device_idle(run)
