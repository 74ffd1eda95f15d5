"""Model decode: device time of the paged decode-loop program in the
trace over the decode steps ``ServeStats`` counted in the traced group."""
from bench import readers


def read(run):
    return readers.decode_step_ms(run)
