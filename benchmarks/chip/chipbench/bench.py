"""One run of one cell: set-up, the measured window, the check, and the
result line.

Set-up generates the network from the seed, deploys it through
``EdgeSystem.deploy(builder="jax")``, builds the service with the
configuration's policy and warms the one shape the cell's traffic
uses. The window drives ``DistanceService.submit``. After it, the
device's peak memory is read, the program's state is dropped, and the
answers are compared with the plain reference.
"""
from __future__ import annotations

import contextlib
import os
import tempfile
import time
from dataclasses import dataclass

import numpy as np

from . import check, drivers, network, reference, trace, traffic
from .spec import Cell

# a submit that takes longer than this is logged as a stall
STALL_S = 0.02
# the traced stretch closes the window: tracing slows the host, and
# writing the trace stalls it, so the host-clock metrics of a traced run
# are read from the part of the window before the stretch
STRETCH_S = 2.0


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed])


def make_network(config: dict, seed: int) -> network.Network:
    return network.continent(tuple(config["grid"]),
                             tuple(config["district"]),
                             config["border_links"], config["weight_high"],
                             _rng(seed, 0))


def to_program(net: network.Network):
    """The network as the program takes it: a ``Graph`` and its
    ``Partition``."""
    from repro.core.partition import Partition
    from repro.ingest.csr import CSRBuilder

    builder = CSRBuilder(net.num_vertices)
    builder.add_arcs(net.u, net.v, net.w.astype(np.float64))
    return (builder.finalize().to_graph(),
            Partition(net.district.copy(), net.num_districts))


class Stretch:
    """Starts the profiler at ``start`` seconds into the window and
    stops it ``length`` seconds later (or when the window ends)."""

    def __init__(self, start: float, length: float, logdir: str):
        self.start, self.stop, self.logdir = start, start + length, logdir
        self.state = "before"
        self._mark = None

    def warm(self) -> None:
        """Start and stop the profiler once, so that starting it inside
        the window does not pay its first-use cost there."""
        import jax
        with tempfile.TemporaryDirectory(prefix="chipbench-warm-") as d:
            jax.profiler.start_trace(d)
            jax.profiler.stop_trace()

    def poll(self, now: float) -> None:
        if self.state == "before" and now >= self.start:
            import jax
            jax.profiler.start_trace(self.logdir)
            self._mark = jax.profiler.TraceAnnotation(trace.STRETCH)
            self._mark.__enter__()
            self.state = "on"
        elif self.state == "on" and now >= self.stop:
            self.close(now)

    def close(self, now: float) -> None:
        if self.state == "on":
            import jax
            self._mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"


class CompileCounter:
    """Counts XLA compilations from when it is armed."""

    def __init__(self):
        from jax import monitoring
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.count = 0
        self.armed = False

        def listen(event, duration, **kw):
            if self.armed and event == BACKEND_COMPILE_EVENT:
                self.count += 1
        monitoring.register_event_duration_secs_listener(listen)


class GcWatch:
    """Records Python's full (generation 2) garbage collections while
    armed: (start on ``time.perf_counter``, seconds)."""

    def __init__(self):
        import gc
        self.full: list[tuple[float, float]] = []
        self.armed = False
        self._start = None

        def listen(phase, info):
            if not self.armed or info["generation"] != 2:
                return
            now = time.perf_counter()
            if phase == "start":
                self._start = now
            elif self._start is not None:
                self.full.append((self._start, now - self._start))
                self._start = None
        gc.callbacks.append(listen)


@dataclass
class RunRecord:
    """What a metric's ``read(run)`` can read."""
    cell: Cell
    seed: int
    seconds: float
    setup_s: float
    net: network.Network
    traffic: object
    record: object
    trace: trace.TraceSummary | None
    device_kind: str
    untraced_s: float       # the window's part before the traced stretch


class Run:
    """One run of ``cell`` with ``seed``; the steps are separate so
    that the control can reuse a deployment."""

    def __init__(self, cell: Cell, seed: int, seconds: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.system = self.service = None

    def deploy(self) -> None:
        from repro.edge import EdgeSystem
        self.net = make_network(self.cell.config, self.seed)
        g, part = to_program(self.net)
        self.system = EdgeSystem.deploy(g, part, builder="jax")
        self.traffic = traffic.generate(self.cell.mix, self.net,
                                        _rng(self.seed, 1))

    def warm(self) -> None:
        """Build the service and its engine, and run the cell's one
        shape until it is compiled (or loaded from the cache)."""
        from repro.serve import ServingPolicy
        policy = ServingPolicy(**self.cell.config["policy"])
        self.service = self.system.service(policy)
        rng = _rng(self.seed, 2)
        n = self.net.num_vertices
        size = self.traffic.rows * self.traffic.width
        for _ in range(3):
            self.service.submit(rng.integers(0, n, size),
                                rng.integers(0, n, size))

    def stretch_start(self, traced: bool) -> float:
        """Where the traced stretch starts (the window's end untraced)."""
        if not traced:
            return self.seconds
        return self.seconds - min(STRETCH_S, 0.5 * self.seconds)

    def window(self, traced: bool, logdir: str | None = None):
        """Drive the window; with ``traced``, profile its last stretch
        into ``logdir``."""
        annotate = drivers.no_annotation
        stretch = None
        if traced:
            import jax
            annotate = jax.profiler.TraceAnnotation
            start = self.stretch_start(True)
            stretch = Stretch(start, self.seconds - start, logdir)
            stretch.warm()
        rows = _rng(self.seed, 3).integers(0, self.traffic.rows, 1 << 16)
        return drivers.closed_loop(self.service.submit, self.traffic,
                                   self.seconds, rows, annotate, stretch)

    def check(self, rec) -> tuple[list[check.Limit], int]:
        return check.check_closed_loop(
            reference.Reference(self.net), self.traffic, rec,
            self.cell.mix["check"]["sources"], _rng(self.seed, 4))

    def release(self) -> None:
        """Drop the program's state (device tables included)."""
        self.service = self.system = None


def device_info(chips: int) -> dict:
    import jax
    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:chips]]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, log) -> dict:
    """One whole run; returns the result line as a dict."""
    run = Run(cell, seed, seconds)
    run.deploy()
    return measure(run, traced, t_start, log)


def measure(run: Run, traced: bool, t_start: float, log) -> dict:
    """Warm, drive the window, check, and make the result line; the
    program's state is released on the way."""
    cell = run.cell
    counter = CompileCounter()
    gc_watch = GcWatch()
    run.warm()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f}")
    counter.armed = gc_watch.armed = True
    with (tempfile.TemporaryDirectory(prefix="chipbench-trace-")
          if traced else contextlib.nullcontext()) as logdir:
        rec = run.window(traced, logdir)
        counter.armed = gc_watch.armed = False
        summary = _read_trace(logdir) if traced else None
    device = device_info(cell.chips)
    run.release()
    log(f"compiles in window {counter.count}")
    _log_stalls(rec, gc_watch, log)
    t0 = time.perf_counter()
    limits, failed = run.check(rec)
    log(f"check_s {time.perf_counter() - t0:.3f}")
    record = RunRecord(cell, run.seed, run.seconds, setup_s, run.net,
                       run.traffic, rec, summary, device["kind"],
                       run.stretch_start(traced))
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.reader(m["name"])(record)
        if value is None:
            raise SystemExit(f"chipbench: metric {m['name']} found nothing "
                             f"to read in cell {cell.name}")
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    result = {"correct": all(x.holds for x in limits),
              "attempted": (len(rec.start) * run.traffic.rows
                            * run.traffic.width),
              "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.device_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    result["checks"] = {x.name: {"value": x.value, "limit": x.limit}
                        for x in limits}
    for x in limits:
        log(("ok   " if x.holds else "FAIL ") + x.text())
    return result


def _log_stalls(rec, gc_watch: GcWatch, log) -> None:
    """The longest ``submit`` calls of the window, how many took over
    ``STALL_S`` (host stalls that a tail would show), and Python's full
    garbage collections in the window, beside them."""
    import gc
    took = rec.end - rec.start
    top = np.argsort(took)[::-1][:5]
    log(f"submits over {STALL_S * 1e3:g} ms: "
        f"{int((took > STALL_S).sum())} of {len(took)}; longest (at s, ms): "
        + ", ".join(f"({rec.start[i]:.3f}, {took[i] * 1e3:.2f})"
                    for i in top))
    log(f"full gc in window: {len(gc_watch.full)} (at s, ms): "
        + ", ".join(f"({t - rec.t0:.3f}, {d * 1e3:.2f})"
                    for t, d in gc_watch.full)
        + f"; objects tracked by gc {len(gc.get_objects())}")


def _read_trace(logdir: str) -> trace.TraceSummary | None:
    paths = [os.path.join(d, f) for d, _, fs in os.walk(logdir)
             for f in fs if f.endswith(".xplane.pb")]
    if not paths:
        return None
    events = trace.read_xspace(paths[0])
    if not any(trace.DEVICE_PLANE.match(e.plane) for e in events):
        return None
    return trace.reduce(events)
