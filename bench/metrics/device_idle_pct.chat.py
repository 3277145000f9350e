"""1 - busy over the traced window, %."""
from bench import readers


def read(rec):
    return readers.idle_pct(rec)
