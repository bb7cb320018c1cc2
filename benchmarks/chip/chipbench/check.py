"""The comparison that decides ``correct``.

Every answer the timed path gave and the window kept, whose source is
one of a sample of sources drawn from the seed, is compared with the
plain reference. The guarantee is exact shortest distances, so the
comparison is exact: a served distance either equals the reference's
or it is wrong.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .drivers import ClosedLoopRecord
from .reference import UNREACHED, Reference


@dataclass(frozen=True)
class Limit:
    """One number compared with its limit: ``value`` must be at most
    ``limit`` (``at_least``: at least)."""
    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def holds(self) -> bool:
        return (self.value >= self.limit if self.at_least
                else self.value <= self.limit)

    def text(self) -> str:
        op = ">=" if self.at_least else "<="
        return f"{self.name} {self.value:g} (limit {op} {self.limit:g})"


def _as_float(ref: np.ndarray) -> np.ndarray:
    out = ref.astype(np.float32)
    out[ref == UNREACHED] = np.inf
    return out


def compare(ref: Reference, ss: np.ndarray, ts: np.ndarray,
            answers: np.ndarray, num_sources: int,
            rng: np.random.Generator) -> tuple[int, int]:
    """(wrong, compared) over every answer whose source is one of
    ``num_sources`` distinct sources drawn from ``ss``."""
    order = rng.permutation(len(ss))
    _, first = np.unique(ss[order], return_index=True)
    sources = ss[order[np.sort(first)[:num_sources]]]
    idx = np.nonzero(np.isin(ss, sources))[0]
    wrong = 0
    for s in sources:
        sel = idx[ss[idx] == s]
        want = _as_float(ref.distances_from(int(s)))[ts[sel]]
        wrong += int((answers[sel] != want).sum())
    return wrong, len(idx)


def check_closed_loop(ref: Reference, traffic, rec: ClosedLoopRecord,
                      num_sources: int,
                      rng: np.random.Generator) -> tuple[list[Limit], int]:
    """Compare the kept rows' answers of ``num_sources`` sources."""
    rows = [traffic.row_pairs(i, int(r)) for i, r in enumerate(rec.kept_row)]
    none = [np.zeros(0, dtype=np.int64)]
    wrong, compared = compare(ref, np.concatenate(none + [s for s, _ in rows]),
                              np.concatenate(none + [t for _, t in rows]),
                              np.concatenate(none + rec.kept_answers),
                              num_sources, rng)
    return [Limit("wrong", wrong, 0),
            Limit("compared", compared, 1, at_least=True)], wrong
