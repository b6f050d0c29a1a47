"""Share of the traced window in which no op ran on the device (%): one
minus the union of the device's op intervals over the window."""
from bench.trace import idle_percent as read  # noqa: F401
