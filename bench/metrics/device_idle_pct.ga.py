"""1 minus the union of device-operation intervals over the traced window."""
from bench.tracing import device_idle_pct


def read(run):
    return device_idle_pct(run.trace)
