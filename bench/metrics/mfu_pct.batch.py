"""Model operations of every token processed in the window over the window at the cell's peak, %."""
from bench import readers


def read(rec):
    return readers.window_mfu_pct(rec)
