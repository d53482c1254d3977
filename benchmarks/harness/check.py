"""What decides ``correct``: every answer the window produced, against the
plain reference run once on the same table after the window has closed.
Each number compared has its own limit, in the configuration's file."""

from __future__ import annotations

import numpy as np


def distinct_answers(answers):
    """``[(answer, how many fits returned exactly it)]``."""
    seen = {}
    for answer in answers:
        key = tuple((name, np.asarray(answer[name]).tobytes())
                    for name in sorted(answer))
        if key in seen:
            seen[key][1] += 1
        else:
            seen[key] = [answer, 1]
    return [tuple(v) for v in seen.values()]


def decide(answers, reference_module, reference: dict, limits: dict,
           extra: dict = None):
    """``(correct, {number: {"value", "limit"}})``. A number is the worst
    over all the window's answers. ``extra`` are numbers that the harness
    counted itself, each ``(value, limit)``. A number without a limit in
    the configuration's file makes the run not correct."""
    worst = {}
    for answer, _ in distinct_answers(answers):
        for name, value in reference_module.compare(answer,
                                                    reference).items():
            worst[name] = max(worst.get(name, 0.0), float(value))
    compared = {}
    for name, value in worst.items():
        compared[name] = {"value": value, "limit": limits.get(name)}
    for name, (value, limit) in (extra or {}).items():
        compared[name] = {"value": value, "limit": limit}
    correct = bool(worst) and all(
        c["limit"] is not None and np.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in compared.values())
    return correct, compared
