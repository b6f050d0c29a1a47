"""Share of the boundary all-to-all's lanes that carried a value other
than the merge identity (%): ``boundary_live_lanes`` over
``boundary_lanes`` of the window; nothing where the program does not
count them or nothing was shipped."""


def read(ctx: dict):
    c = ctx["counters"]
    if not c.get("boundary_lanes") or "boundary_live_lanes" not in c:
        return None
    return c["boundary_live_lanes"] / c["boundary_lanes"] * 100.0
