"""collective_us.mesh4: device microseconds a four-chip sharded step
spends per chip in its all-reduces (the border-row assembly's ``pmin``
and the answers' ``pmin``), from the trace."""
from chipbench.mesh import collective_us as read  # noqa: F401
