"""binpack_fitness: bytes of its calls at the HBM peak over its device time."""
from bench.tracing import hbm_roofline_pct


def read(run):
    return hbm_roofline_pct(run.trace, run.calls, "binpack_fitness", run.peaks)
