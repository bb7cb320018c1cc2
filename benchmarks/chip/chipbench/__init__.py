"""The chip benchmark harness: generator, traffic, drivers, reference,
check, trace reduction and peaks, shared by ``run.py`` and
``control.py``."""
