"""Mean number of requests in a batch the service hands to its lane."""


def read(run):
    if not run.stats or not run.stats["batches"]:
        return None
    return run.stats["batch_occupancy"]["mean"]
