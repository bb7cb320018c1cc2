"""fetch_ms.matrix: mean self time of ``repro.fetch`` (waiting for the
device step and copying the answers to the host) per 65,536-pair matrix
``submit``, in ms, over the submits ``submit_ms.matrix`` reads, less
those over 20 ms."""
from chipbench.spans import fetch_ms as read  # noqa: F401
