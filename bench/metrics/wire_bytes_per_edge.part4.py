"""Bytes the boundary all-to-all put on the wire between chips in the
window, over the edges of the traversals completed in it; nothing where
the program does not count them."""


def read(ctx: dict):
    c = ctx["counters"]
    if "wire_bytes" not in c or not c.get("edges"):
        return None
    return c["wire_bytes"] / c["edges"]
