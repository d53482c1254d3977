"""Features, label and weight (upstream
``LabeledPointWithWeightGenerator.java``): arity 0 is a uniform [0, 1)
double, a positive arity ``k`` an integer in [0, k)."""

from . import values


def build(params: dict):
    (features, label, weight), = params["colNames"]
    n, d = int(params["numValues"]), int(params["vectorDim"])
    f_arity = int(params.get("featureArity", 2))
    l_arity = int(params.get("labelArity", 2))

    def gen(key):
        import jax

        k = [jax.random.fold_in(key, i) for i in range(3)]
        return {features: values(k[0], (n, d), f_arity),
                label: values(k[1], (n,), l_arity),
                weight: values(k[2], (n,), 0)}

    return gen, {features: 2, label: 1, weight: 1}
