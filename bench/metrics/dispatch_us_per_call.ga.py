"""Mean wall time of one kernel-dispatch span: for the GA's fitness call,
from its device arrays in to the result ready on the device."""
from bench.tracing import dispatch_us_per_call


def read(run):
    return dispatch_us_per_call(run.trace)
