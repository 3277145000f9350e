"""Device time per call of the packed prefill program, from the trace, ms."""
from bench import readers


def read(rec):
    return readers.step_ms(rec, readers.PREFILL)
