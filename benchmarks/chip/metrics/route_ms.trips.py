"""route_ms.trips: mean self time of ``repro.route`` (row ids and
padding to a bucket, on the host) per 256-pair ``submit``, in ms, over
the submits ``submit_ms.trips`` reads, less those over 20 ms."""
from chipbench.spans import route_ms as read  # noqa: F401
