"""submit_ms.mesh4: median host wall time of one 1024-trip ``submit``
on the four-chip mesh (front door, owner routing, the sharded step,
answers back)."""
from chipbench.readers import submit_ms as read  # noqa: F401
