"""Hashed click logs: rows of the Criteo Display Advertising Challenge
(Kaggle, 2014) — a click label, ``numericFields`` integer fields (I1-I13)
and ``categoricalFields`` categorical ones (C1-C26) — as upstream's
``FeatureHasher`` leaves them in ``numFeatures`` buckets: one sparse vector
column of ``k = numericFields + categoricalFields`` entries a row, and the
label.

- numeric field ``j``: one entry at the bucket ``mix32(j) mod numFeatures``
  (a numeric column hashes by its name, so every row shares it), its value
  uniform in [0, 1) (the raw counts as a MinMaxScaler leaves them);
- categorical field ``f``: a value of rank ``r`` in ``[1, C_f]`` (``C_f`` =
  ``cardinalities[f]``) drawn from a power law of exponent ``zipfExponent``
  by its closed-form inverse CDF, ``r = floor(((C_f + 1)^(1 - s) - 1) u +
  1)^(1 / (1 - s)))`` (the continuous law on ``[1, C_f + 1)``, so that
  every one of the ``C_f`` ranks is drawn); one entry at
  ``mix32(numericFields + f, r) mod numFeatures``, value 1.0;
- two fields of a row in one bucket keep two entries (sums over them add);
- the label uniform over {0, 1}, independent of the features.

The column is ``{"ids": (n, k) int32, "values": (n, k) float32, "size":
numFeatures}`` (``size`` a replicated int32 scalar), made as ``(k, n)`` and
handed out transposed: every array of the program is
lane-dense on the TPU, and the outputs lie as the dense tables do, ``k``
padded to 40 sublanes, never to 128 lanes.
"""

from __future__ import annotations

from . import values


def fmix32(h):
    """MurmurHash3's 32-bit finaliser, on uint32 (NumPy or jax.numpy)."""
    u32 = h.dtype.type
    h = h ^ (h >> 16)
    h = h * u32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * u32(0xC2B2AE35)
    return h ^ (h >> 16)


def mix32(a, b=0):
    """The bucket hash of a field ``a`` and a value ``b``, on uint32: for one
    field, distinct values give distinct hashes before the modulus."""
    return fmix32(fmix32(a) ^ b)


def build(params: dict):
    (features, label), = params["colNames"]
    n, m = int(params["numValues"]), int(params["numFeatures"])
    numeric = int(params["numericFields"])
    categorical = int(params["categoricalFields"])
    cards = [float(c) for c in params["cardinalities"]]
    s = float(params["zipfExponent"])
    if len(cards) != categorical or s == 1.0:
        raise ValueError("one cardinality a categorical field, and a "
                         "zipfExponent other than 1")

    def gen(key):
        import jax
        import jax.numpy as jnp

        k_num, k_cat, k_label = (jax.random.fold_in(key, i)
                                 for i in range(3))
        u32 = jnp.uint32
        field = jnp.arange(numeric + categorical, dtype=u32)[:, None]
        c = jnp.asarray(cards, jnp.float32)[:, None]
        a = 1.0 - s
        u = jax.random.uniform(k_cat, (categorical, n), jnp.float32)
        x = (((c + 1.0) ** a - 1.0) * u + 1.0) ** (1.0 / a)
        rank = jnp.clip(jnp.floor(x), 1.0, c).astype(u32)
        ids = jnp.concatenate([
            jnp.broadcast_to(mix32(field[:numeric]), (numeric, n)),
            mix32(field[numeric:], rank)]) % u32(m)
        vals = jnp.concatenate([
            jax.random.uniform(k_num, (numeric, n), jnp.float32),
            jnp.ones((categorical, n), jnp.float32)])
        return {features: {"ids": ids.astype(jnp.int32).T,
                           "values": vals.T, "size": jnp.int32(m)},
                label: values(k_label, (n,), 2)}

    return gen, {features: {"ids": 2, "values": 2, "size": 0}, label: 1}
