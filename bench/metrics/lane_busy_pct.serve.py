"""Share of the window in which the service's lane solves a batch: the union
of the batches the harness recorded, over the window's length."""
from bench.tracing import lane_busy_pct


def read(run):
    return lane_busy_pct(run.batches, run.elapsed_s)
