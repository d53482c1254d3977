"""Per fit: the harness's ``bench.fit`` span minus the device-busy time
inside it (busiest device); the median over the traced fits."""
import statistics


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    return statistics.median(
        f - b for f, b in zip(tr["fit_s"], tr["fit_busy_s"])) * 1e3
