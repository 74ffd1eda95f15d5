"""Device: 1 - (union of device op intervals / traced window), in %."""
from bench import readers


def read(run):
    return readers.idle_share(run)
