"""A truncated Histogram's answer. deequ's Histogram reports
``number_of_bins`` and the ``max_detail_bins`` (1,000) most frequent bins,
ties at the cut broken either way: over more values than that, a right
answer is NOT equal to the reference's full histogram. ``TopBins`` is what
the program reported; compared with the full histogram ``w`` (``{label:
count}``, ``chipbench/reference.py``) it is equal where ALL of these hold:

- ``number_of_bins == len(w)``;
- as many bins are reported as are due: ``min(DETAIL_BINS, len(w))``;
- every reported bin has the count ``w`` gives that label;
- the reported counts, sorted, are the that many LARGEST values of ``w``
  (so either side of a tie at the cut is right, and nothing else is).

``chipbench/compare.py`` holds exact answers by ``g != w``: with ``g`` a
``TopBins`` that calls this rule, and the file stays as it is."""

from __future__ import annotations

import numpy as np

DETAIL_BINS = 1000  # deequ's Histogram.MaximumAllowedDetailBins, its default

# id(w) -> (w, its counts in descending order): the reference hands every
# operation of a window the same dict, and a dictionary's worth of counts
# is sorted once
_ORDERED: dict = {}


def _descending(w: dict) -> np.ndarray:
    hit = _ORDERED.get(id(w))
    if hit is None or hit[0] is not w:
        counts = np.fromiter(w.values(), dtype=np.int64, count=len(w))
        counts.sort()
        hit = _ORDERED[id(w)] = (w, counts[::-1])
        while len(_ORDERED) > 64:
            _ORDERED.pop(next(iter(_ORDERED)))
    return hit[1]


class TopBins:
    __slots__ = ("number_of_bins", "bins")

    def __init__(self, number_of_bins: int, bins: dict):
        self.number_of_bins = int(number_of_bins)
        self.bins = bins

    def __eq__(self, w):  # ``!=`` is Python's inverse of this
        if not isinstance(w, dict):
            return NotImplemented
        due = min(DETAIL_BINS, len(w))
        if self.number_of_bins != len(w) or len(self.bins) != due:
            return False
        if any(w.get(label) != count for label, count in self.bins.items()):
            return False
        reported = np.sort(np.fromiter(
            self.bins.values(), dtype=np.int64, count=due))[::-1]
        return bool(np.array_equal(reported, _descending(w)[:due]))

    __hash__ = None

    def __repr__(self) -> str:
        head = sorted(self.bins.items(), key=lambda kv: -kv[1])[:3]
        return (f"TopBins(number_of_bins={self.number_of_bins}, "
                f"{len(self.bins)} bins, largest {head})")
