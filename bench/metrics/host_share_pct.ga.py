"""Share of the traced window outside the kernel-dispatch spans (the host engine)."""
from bench.tracing import host_share_pct


def read(run):
    return host_share_pct(run.trace)
