"""device_idle_share.mesh4: 1 - (union of device-op intervals) / traced
stretch, averaged over the chips that ran, from the profiler trace."""
from chipbench.readers import idle_share as read  # noqa: F401
