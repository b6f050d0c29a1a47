"""Device time of gather-class ops in the traced window, summed over the
four chips, over the edges of the traversals completed in it (ns per
edge); the class is read from each op's HLO instruction text by
``bench.trace.classify``."""
from bench.trace import per_edge_ns


def read(ctx: dict):
    return per_edge_ns(ctx, "gather")
