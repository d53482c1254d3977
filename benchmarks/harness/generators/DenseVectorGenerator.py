"""One column of dense vectors (upstream ``DenseVectorGenerator.java``):
``numValues`` rows of ``vectorDim`` uniform [0, 1) doubles, here float32."""

from . import values


def build(params: dict):
    (column,), = params["colNames"]
    n, d = int(params["numValues"]), int(params["vectorDim"])

    def gen(key):
        return {column: values(key, (n, d), 0)}

    return gen, {column: 2}
