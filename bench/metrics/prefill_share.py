"""Model prefill: device time of the prefill program over device busy
time in the traced group, in %."""
from bench import readers


def read(run):
    s = readers.traced_summary(run)
    t = s.program_s(readers.PREFILL) if s else None
    if t is None or s.busy_s <= 0:
        return None
    return 100.0 * t / s.busy_s
