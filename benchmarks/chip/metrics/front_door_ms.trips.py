"""front_door_ms.trips: mean self time of ``repro.submit``, ``repro.plan``,
``repro.wrap`` and ``repro.fold`` (the service's own code around the
engine) per 256-pair ``submit``, in ms, over the submits ``submit_ms.trips``
reads, less those over 20 ms."""
from chipbench.spans import front_door_ms as read  # noqa: F401
