"""The measured window. A traffic file's ``kind`` names the loop:
``windows/<kind>.py`` holds ``run(fit, to_host, seconds, rows_per_fit,
tracer)``, which returns the end-to-end metrics it can compute, by name, and
what the check and the readers need. Here: what every loop shares.
"""

from __future__ import annotations

import importlib
import shutil
import tempfile
import time


def load(kind: str):
    try:
        return importlib.import_module(f"{__name__}.{kind}").run
    except ModuleNotFoundError:
        raise KeyError(f"no window loop {kind!r} under harness/windows/"
                       ) from None


class TraceControl:
    """Captures the profiler's trace for ``capture_s`` seconds from
    ``start_after_s`` into the window, starting and stopping between fits.
    The seconds spent inside the profiler's own start and stop calls are
    kept in ``overhead_s``."""

    def __init__(self, start_after_s: float, capture_s: float):
        self.start_after_s = start_after_s
        self.capture_s = capture_s
        self.overhead_s = 0.0
        self.dir = None
        self._started_at = None
        self.done = False

    @property
    def capturing(self) -> bool:
        return self._started_at is not None and not self.done

    def between_fits(self, elapsed_s: float) -> None:
        import jax

        if self.done:
            return
        t = time.perf_counter()
        if self._started_at is None:
            if elapsed_s < self.start_after_s:
                return
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)
            self._started_at = elapsed_s
        elif elapsed_s - self._started_at >= self.capture_s:
            jax.profiler.stop_trace()
            self.done = True
        else:
            return
        self.overhead_s += time.perf_counter() - t

    def finish(self) -> None:
        """Stop a capture the window ended inside of."""
        import jax

        if self._started_at is not None and not self.done:
            jax.profiler.stop_trace()
            self.done = True

    def cleanup(self) -> None:
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)
