"""Share of requests answered without a solve: coalesced onto one in flight,
or found in the service's cache or result store."""


def read(run):
    if not run.stats or not run.stats["requests"]:
        return None
    return 100.0 * run.stats["hit_rate"]
