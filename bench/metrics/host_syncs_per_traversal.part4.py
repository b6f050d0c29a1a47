"""Device-to-host reads of the partitioned run loop per traversal in the
window (``PartitionedFrontierPipeline.stats()["host_syncs"]``); nothing
where the program does not count them."""


def read(ctx: dict):
    c = ctx["counters"]
    if "host_syncs" not in c or not c.get("traversals"):
        return None
    return c["host_syncs"] / c["traversals"]
