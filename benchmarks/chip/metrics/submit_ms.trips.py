"""submit_ms.trips: median host wall time of one padded 256-pair
``submit`` (front door, host routing, device step, answers back)."""
from chipbench.readers import submit_ms as read  # noqa: F401
