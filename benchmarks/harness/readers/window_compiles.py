"""Compile requests inside the window: each is a trace, a lowering and a
look in the cache, whether or not the backend then compiles."""


def read(ctx):
    return float(ctx["compiles"]["window"]["requests"])
