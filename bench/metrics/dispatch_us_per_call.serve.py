"""Mean wall time of one kernel-dispatch span: host arrays in to host arrays out."""
from bench.tracing import dispatch_us_per_call


def read(run):
    return dispatch_us_per_call(run.trace)
