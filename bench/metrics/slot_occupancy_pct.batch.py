"""Mean active slots over the window's engine steps that had any, as a share of max_batch."""
from bench import readers


def read(rec):
    return readers.slot_occupancy_pct(rec)
