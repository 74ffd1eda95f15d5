"""Process start to the window's first submission: imports, weights
made on the device, the engine, and one group served to compile (or
load from the persistent cache) every program the window runs."""


def read(run):
    return run.setup_s
