"""Median wait from due time to leaving the queue, over the requests due in the window."""
from bench import readers


def read(rec):
    return readers.queue_wait_ms(rec, 0.50)
