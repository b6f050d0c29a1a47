"""Host dispatches of a capacity rung per traversal in the window
(``FrontierPipeline.n_hops`` over the traversals completed)."""
from bench.trace import hops_per_traversal as read  # noqa: F401
