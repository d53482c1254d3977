"""Per sparse LR fit: the share, in percent, of the window entries its rounds
held that the dictionary form took (``ops/sparse_window.py``: the narrow
positions, compared with their dictionaries instead of gathered and
scattered), read from the ``dict_entries`` and ``entries`` attributes of the
fit's ``sgd.optimize`` span (``ml.sgd dictEntries`` and ``sparseEntries``'
parts). The median over the whole traced sparse fits; None where the program
has no such attributes (an older one) or the ring holds fewer than
``sparse_spans.MIN_FITS`` whole sparse fits."""
from __future__ import annotations

import statistics

from benchmarks.harness import program_spans, sparse_spans


def share(fit):
    """``100 * dict_entries / entries`` of the fit's ``sgd.optimize``, or
    None."""
    for s in fit:
        attrs = s.get("attrs", {})
        if s["name"] == sparse_spans.OPTIMIZE and attrs.get("entries"):
            taken = attrs.get("dict_entries")
            return None if taken is None else 100.0 * taken / attrs["entries"]
    return None


def read(ctx, records=None):
    fits = [fit for fit in program_spans.whole_fits(
        program_spans.ring() if records is None else records)
        if sparse_spans.is_sparse(fit)]
    if len(fits) < sparse_spans.MIN_FITS:
        return None
    shares = [v for v in map(share, fits) if v is not None]
    return statistics.median(shares) if shares else None
