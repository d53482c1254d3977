"""One caller, fits back to back on the resident table — ``fit`` to a model,
the model's data to the host — until the window's seconds have passed; the
fit in flight at the end is finished and counted, and the window is as long
as it took to finish it."""

from __future__ import annotations

import time

import numpy as np

from . import TraceControl, span


def run(fit, to_host, seconds: float, rows_per_fit: int,
        tracer: TraceControl = None) -> dict:
    """``fit() -> model`` and ``to_host(model) -> (answer, path)`` are the
    system's; this loop only calls and times them."""
    walls, answers, paths, traced = [], [], [], []
    start = time.perf_counter()
    end = start
    while end - start < seconds:
        traced.append(tracer is not None and tracer.capturing)
        t_call = time.perf_counter()
        with span("bench.fit"):
            model = fit()
        with span("bench.model_data"):
            answer, path = to_host(model)
        end = time.perf_counter()
        with span("bench.gap"):
            walls.append(end - t_call)
            answers.append(answer)
            paths.append(path)
            del model
            if tracer is not None:
                tracer.between_fits(end - start)
                end = time.perf_counter()
    if tracer is not None:
        tracer.finish()
    window_s = end - start
    fits = len(walls)
    return {
        "window_s": window_s,
        "work_s": window_s - (tracer.overhead_s if tracer else 0.0),
        "attempted": fits,
        "failed": 0,
        "walls_s": walls,
        "traced": traced,
        "answers": answers,
        "paths": paths,
        "metrics": {
            "fit_rows_per_s": fits * rows_per_fit / window_s,
            "fit_wall_p95_ms": float(np.percentile(walls, 95)) * 1e3,
        },
    }
