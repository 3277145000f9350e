"""Device time per call of the decode program, from the trace, ms."""
from bench import readers


def read(rec):
    return readers.step_ms(rec, readers.DECODE)
