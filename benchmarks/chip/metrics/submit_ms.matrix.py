"""submit_ms.matrix: median host wall time of one 65,536-pair matrix
``submit`` (front door, host routing, device step, answers back)."""
from chipbench.readers import submit_ms as read  # noqa: F401
