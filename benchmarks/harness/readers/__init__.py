"""One reader a file: ``read(ctx) -> float | None``. A reader that finds
nothing to read returns None and the metric is left out of the line; none
returns 0 for a share of a peak. ``ctx`` holds ``trace`` (the reduction of
``trace_reduce.reduce`` or None), ``window``, ``count`` (one fit's rows,
bytes and FLOPs), ``least`` (one fit's least seconds on this cell's
chips), ``peaks``, ``chips``, ``compiles`` and ``setup`` (the set-up's phases in
seconds)."""

from __future__ import annotations

import importlib


def load(name: str):
    return importlib.import_module(f"{__name__}.{name}").read
