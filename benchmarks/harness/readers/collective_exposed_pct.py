"""Share of the busiest device's busy time in which a collective runs and
no compute operation does."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["collective_s"] or not tr["busy_s_busiest"]:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["busy_s_busiest"]
