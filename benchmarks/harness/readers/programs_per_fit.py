"""Device program executions in the traced cycles over the fits in them."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["programs"]:
        return None
    return tr["programs"] / tr["cycles"]
