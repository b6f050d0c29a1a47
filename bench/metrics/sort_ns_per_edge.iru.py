"""Device time of sort-class ops in the traced window, over the edges of
the traversals completed in it (ns per edge); the class is read from each
op's HLO instruction text by ``bench.trace.classify``.  The reorder's own
scatters and permutation gathers fall in the scatter and gather classes
until the program names its stages."""
from bench.trace import per_edge_ns


def read(ctx: dict):
    return per_edge_ns(ctx, "sort")
