"""Share of the traced stretch of a serving cell in which no operation
ran on the device, in %."""
from bench.readers import device_idle


def read(run):
    return device_idle(run)
