"""route_ms.matrix: mean self time of ``repro.route`` (row ids and
padding to a bucket, on the host) per 65,536-pair matrix ``submit``, in
ms, over the submits ``submit_ms.matrix`` reads, less those over
20 ms."""
from chipbench.spans import route_ms as read  # noqa: F401
