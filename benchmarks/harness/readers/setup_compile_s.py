"""Seconds inside compile requests during set-up (loads from the
persistent cache included)."""


def read(ctx):
    return float(ctx["compiles"]["setup"]["seconds"])
