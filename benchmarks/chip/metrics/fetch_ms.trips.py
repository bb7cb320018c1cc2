"""fetch_ms.trips: mean self time of ``repro.fetch`` (waiting for the device
step and copying the answers to the host) per 256-pair ``submit``, in ms,
over the submits ``submit_ms.trips`` reads, less those over 20 ms."""
from chipbench.spans import fetch_ms as read  # noqa: F401
