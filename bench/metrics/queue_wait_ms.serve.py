"""Median, over the requests a batch solved, of the time from a request's
due time to the start of its batch: the batcher's window and the lane's queue."""
from bench.generator import nearest_rank
from bench.tracing import queue_waits


def read(run):
    waits = queue_waits(run.requests or [], run.batches or [])
    return 1e3 * nearest_rank(waits, 0.5) if waits else None
