"""dispatch_ms.matrix: mean self time of ``repro.dispatch`` (the jitted
engine call until it returns: row-id transfer and launch) per
65,536-pair matrix ``submit``, in ms, over the submits
``submit_ms.matrix`` reads, less those over 20 ms."""
from chipbench.spans import dispatch_ms as read  # noqa: F401
