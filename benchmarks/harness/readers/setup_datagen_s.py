"""Host clock around the generator's one jitted call and the wait for it."""


def read(ctx):
    return float(ctx["setup"]["datagen_s"])
