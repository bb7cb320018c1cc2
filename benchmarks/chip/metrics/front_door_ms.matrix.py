"""front_door_ms.matrix: mean self time of ``repro.submit``,
``repro.plan``, ``repro.wrap`` and ``repro.fold`` (the service's own
code around the engine) per 65,536-pair matrix ``submit``, in ms, over
the submits ``submit_ms.matrix`` reads, less those over 20 ms."""
from chipbench.spans import front_door_ms as read  # noqa: F401
