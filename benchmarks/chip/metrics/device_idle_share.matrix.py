"""device_idle_share.matrix: 1 - (union of device-op intervals) /
traced stretch, from the profiler trace."""
from chipbench.readers import idle_share as read  # noqa: F401
