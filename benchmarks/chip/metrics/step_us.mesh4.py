"""step_us.mesh4: device microseconds per execution of the four-chip
sharded serving step, from the trace's module line, per chip."""
from chipbench.mesh import step_us as read  # noqa: F401
