"""Share of the traced stretch in which no operation ran on the device."""
from trace_reduce import idle_percent as read  # noqa: F401
