"""step_hbm_roofline.matrix: the serving device step's share of the HBM
roofline, in percent.

    (least bytes a request needs / peak HBM bytes per second)
        / device time of one step, from the trace

The step is the jitted gather plus ``label_join`` of the replicated
engine; ``STEP_JITS`` are the jit names the trace is searched for. If
the step is renamed, only this list needs repointing.

The bytes are counted from the request and the deployment's sizes, so
that the count stays the same whatever implements the request: each
distinct label row the answers read, at its natural width (q entries
for a border row, k_i for a row of district i), at 2 bytes an entry
(the narrowest lossless code for these integral weights), plus a
4-byte answer and a 4-byte pair id for every pair. The share is a
bandwidth bound only: no VPU peak is published for the chip.
"""
import numpy as np

from chipbench.traffic import MatrixPool
from chipbench.peaks import peak

STEP_JITS = ("jit__engine_fn_quantized", "jit__engine_fn")
ENTRY_BYTES = 2
ANSWER_BYTES = 4
PAIR_ID_BYTES = 4


def border_count(net) -> int:
    """q: vertices with an edge to another district."""
    cross = net.district[net.u] != net.district[net.v]
    return len(np.unique(np.concatenate([net.u[cross], net.v[cross]])))


def request_bytes(sources, targets, district, district_size, q) -> int:
    """Least bytes one one-to-many request (every source against every
    target) reads and writes. A pair inside one district reads the two
    vertices' rows of that district's index; any other pair reads their
    rows of the border table. Each distinct row is read once."""
    ss = np.repeat(np.asarray(sources, dtype=np.int64), len(targets))
    ts = np.tile(np.asarray(targets, dtype=np.int64), len(sources))
    local = district[ss] == district[ts]
    n = len(district)
    # row key: vertex for a district row, n + vertex for a border row
    keys = np.unique(np.concatenate([np.where(local, ss, n + ss),
                                     np.where(local, ts, n + ts)]))
    border = keys >= n
    entries = (q * int(border.sum())
               + int(district_size[district[keys[~border]]].sum()))
    return (entries * ENTRY_BYTES
            + len(ss) * (ANSWER_BYTES + PAIR_ID_BYTES))


# requests whose bytes are averaged: every request's count differs from
# the mean by well under 1 %
SAMPLE = 64


def read(run):
    rec, tr = run.record, run.traffic
    if run.trace is None or not isinstance(tr, MatrixPool):
        return None
    seconds, steps = run.trace.program_time(STEP_JITS)
    if steps == 0 or seconds <= 0:
        return None
    net = run.net
    q = border_count(net)
    size = np.bincount(net.district, minlength=net.num_districts)
    pool = len(tr.sources)
    sample = np.linspace(0, len(rec.start) - 1, SAMPLE).astype(int)
    need = np.mean([request_bytes(tr.sources[i % pool], tr.targets[i % pool],
                                  net.district, size, q)
                    for i in np.unique(sample)])
    bandwidth = peak(run.device_kind, "hbm_bytes_per_s")
    return 100.0 * steps * need / bandwidth / seconds
