"""The FLOPs every fit of the window needs over what the cell's chips could
do in the window's seconds (host time included; the profiler's own start
and stop calls left out)."""


def read(ctx):
    win = ctx["window"]
    peak = ctx["peaks"]["peak_flops_per_s"] * ctx["chips"]
    if not win["attempted"] or win["work_s"] <= 0:
        return None
    return 100.0 * ctx["count"]["flops"] * win["attempted"] / (
        win["work_s"] * peak)
