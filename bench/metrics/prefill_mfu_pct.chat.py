"""Model operations of the traced packed prefill calls over their device time at the cell's peak, %."""
from bench import readers


def read(rec):
    return readers.prefill_mfu_pct(rec)
