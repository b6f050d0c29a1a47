"""Share of the traced window in which no op ran on the device (%),
averaged over the four chips: one minus the union of each chip's op
intervals over the window."""
from bench.trace import idle_percent as read  # noqa: F401
