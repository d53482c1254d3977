"""1 - the union of device-operation intervals over the traced cycles, on
the busiest device."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s_busiest"] / tr["window_s"])
