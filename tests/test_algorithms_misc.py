"""KNN / NaiveBayes / AgglomerativeClustering / evaluator / stats / Swing
tests vs sklearn/scipy oracles (ref test model: per-algorithm *Test.java)."""

import numpy as np
import pytest

from flink_ml_tpu.common.table import Table
from flink_ml_tpu.models.classification import (
    Knn,
    KnnModel,
    NaiveBayes,
    NaiveBayesModel,
)
from flink_ml_tpu.models.clustering import AgglomerativeClustering
from flink_ml_tpu.models.evaluation import BinaryClassificationEvaluator
from flink_ml_tpu.models.recommendation import Swing
from flink_ml_tpu.models.stats import ANOVATest, ChiSqTest, FValueTest


# ---------------------------------------------------------------------------
# KNN
# ---------------------------------------------------------------------------

def test_knn_matches_sklearn(rng, tmp_path):
    from sklearn.neighbors import KNeighborsClassifier
    x = rng.normal(size=(200, 4)).astype(np.float64)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float64)
    x_test = rng.normal(size=(50, 4))
    train = Table.from_columns(features=x, label=y)
    test = Table.from_columns(features=x_test)

    model = Knn(k=5).fit(train)
    pred = model.transform(test)[0]["prediction"]
    sk = KNeighborsClassifier(n_neighbors=5).fit(x, y).predict(x_test)
    assert np.mean(pred == sk) > 0.95  # ties may break differently

    model.save(str(tmp_path / "knn"))
    reloaded = KnnModel.load(str(tmp_path / "knn"))
    np.testing.assert_array_equal(
        reloaded.transform(test)[0]["prediction"], pred)

    (md,) = model.get_model_data()
    fresh = KnnModel(k=5).set_model_data(md)
    np.testing.assert_array_equal(
        fresh.transform(test)[0]["prediction"], pred)


def test_knn_k_exceeds_train_size():
    train = Table.from_columns(
        features=np.array([[0.0, 0.0], [1.0, 1.0]]),
        label=np.array([0.0, 1.0]))
    model = Knn(k=10).fit(train)
    pred = model.transform(train)[0]["prediction"]
    assert pred.shape == (2,)


# ---------------------------------------------------------------------------
# NaiveBayes
# ---------------------------------------------------------------------------

def test_naive_bayes_categorical(tmp_path):
    # deterministic categorical data: feature 0 perfectly predicts the label
    x = np.array([[0.0, 1.0], [0.0, 0.0], [1.0, 1.0], [1.0, 0.0],
                  [0.0, 1.0], [1.0, 0.0]])
    y = np.array([0.0, 0.0, 1.0, 1.0, 0.0, 1.0])
    t = Table.from_columns(features=x, label=y)
    model = NaiveBayes(smoothing=1.0).fit(t)
    pred = model.transform(t)[0]["prediction"]
    np.testing.assert_array_equal(pred, y)

    model.save(str(tmp_path / "nb"))
    reloaded = NaiveBayesModel.load(str(tmp_path / "nb"))
    np.testing.assert_array_equal(
        reloaded.transform(t)[0]["prediction"], pred)

    # unseen feature value gets the smoothed floor, no crash
    t2 = Table.from_columns(features=np.array([[7.0, 1.0]]))
    assert model.transform(t2)[0]["prediction"].shape == (1,)

    # the model data is numeric arrays and round-trips
    (md,) = model.get_model_data()
    assert all(np.asarray(md.column(c)).dtype == np.float64
               for c in md.column_names)
    fresh = NaiveBayesModel().set_model_data(md)
    np.testing.assert_array_equal(fresh.transform(t)[0]["prediction"], pred)


def test_naive_bayes_model_saved_as_dicts_still_loads(tmp_path):
    """Before the model data was arrays a saved model held ``theta`` as
    ``L x d`` dicts in ``data/model.json``: such a directory loads to the
    same model."""
    import json
    import os

    x = np.array([[0.0, 1.0], [0.0, 0.0], [1.0, 1.0], [1.0, 0.0],
                  [0.0, 1.0], [1.0, 3.0]])
    y = np.array([0.0, 0.0, 1.0, 1.0, 0.0, 1.0])
    t = Table.from_columns(features=x, label=y)
    model = NaiveBayes(smoothing=1.0).fit(t)
    path = str(tmp_path / "nb")
    model.save(path)
    os.remove(os.path.join(path, "data", "model.npz"))
    as_dicts = [[{str(v): float(model.theta[li, j, k])
                  for k, v in enumerate(model.values[j]) if v == v}
                 for j in range(2)] for li in range(2)]
    with open(os.path.join(path, "data", "model.json"), "w") as f:
        json.dump({"theta": as_dicts, "pi": model.pi.tolist(),
                   "labels": model.labels.tolist(),
                   "floors": model.floors.tolist()}, f)
    old = NaiveBayesModel.load(path)
    for name in ("theta", "values", "pi", "labels", "floors"):
        np.testing.assert_array_equal(getattr(old, name),
                                      getattr(model, name), err_msg=name)
    t2 = Table.from_columns(features=np.array([[7.0, 1.0], [1.0, 3.0]]))
    np.testing.assert_array_equal(old.transform(t2)[0]["prediction"],
                                  model.transform(t2)[0]["prediction"])


def test_naive_bayes_matches_sklearn_categorical(rng):
    from sklearn.naive_bayes import CategoricalNB
    x = rng.integers(0, 3, size=(300, 4)).astype(np.float64)
    y = ((x[:, 0] + x[:, 1]) % 2).astype(np.float64)
    t = Table.from_columns(features=x, label=y)
    ours = NaiveBayes(smoothing=1.0).fit(t).transform(t)[0]["prediction"]
    sk = CategoricalNB(alpha=1.0).fit(x.astype(int), y).predict(x.astype(int))
    assert np.mean(ours == sk) > 0.98


# ---------------------------------------------------------------------------
# AgglomerativeClustering
# ---------------------------------------------------------------------------

def test_agglomerative_clustering(rng):
    a = rng.normal(scale=0.2, size=(20, 2))
    b = rng.normal(scale=0.2, size=(20, 2)) + 10
    x = np.concatenate([a, b])
    t = Table.from_columns(features=x)
    out, merges = AgglomerativeClustering(num_clusters=2).transform(t)
    pred = out["prediction"]
    assert len(np.unique(pred[:20])) == 1
    assert pred[0] != pred[-1]
    assert merges.num_rows == 39  # n-1 merges

    # distance threshold variant
    op = AgglomerativeClustering(num_clusters=None, distance_threshold=5.0,
                                 linkage="single")
    out2, _ = op.transform(t)
    assert len(np.unique(out2["prediction"])) == 2

    with pytest.raises(ValueError):
        AgglomerativeClustering(num_clusters=None).transform(t)
    with pytest.raises(ValueError):
        AgglomerativeClustering(linkage="ward",
                                distance_measure="cosine").transform(t)


# ---------------------------------------------------------------------------
# BinaryClassificationEvaluator
# ---------------------------------------------------------------------------

def test_evaluator_matches_sklearn(rng):
    from sklearn.metrics import average_precision_score, roc_auc_score
    n = 500
    labels = rng.integers(0, 2, n).astype(np.float64)
    scores = np.clip(labels * 0.6 + rng.normal(scale=0.35, size=n), 0, 1)
    t = Table.from_columns(label=labels, rawPrediction=scores)
    ev = BinaryClassificationEvaluator(
        metrics_names=["areaUnderROC", "areaUnderPR", "ks",
                       "areaUnderLorenz"])
    out = ev.transform(t)[0]
    assert out.column_names == ["areaUnderROC", "areaUnderPR", "ks",
                                "areaUnderLorenz"]
    auc = out["areaUnderROC"][0]
    np.testing.assert_allclose(auc, roc_auc_score(labels, scores), atol=1e-9)
    np.testing.assert_allclose(out["areaUnderPR"][0],
                               average_precision_score(labels, scores),
                               atol=0.02)  # trapezoid vs step interpolation
    assert 0 < out["ks"][0] <= 1
    assert 0.5 < out["areaUnderLorenz"][0] < 1.0


def test_evaluator_tie_heavy_and_weighted(rng):
    """Tie groups are collapsed vectorized (np.add.reduceat) — exercise
    heavy ties plus sample weights against sklearn's weighted AUC."""
    from sklearn.metrics import roc_auc_score
    n = 5000
    scores = np.round(rng.random(n), 2)  # ~100 distinct values: dense ties
    labels = (rng.random(n) < scores).astype(np.float64)
    weights = rng.random(n) + 0.5
    t = Table.from_columns(label=labels, rawPrediction=scores,
                           weight=weights)
    ev = BinaryClassificationEvaluator(weight_col="weight")
    out = ev.transform(t)[0]
    np.testing.assert_allclose(
        out["areaUnderROC"][0],
        roc_auc_score(labels, scores, sample_weight=weights), atol=1e-9)
    # all-tied degenerate input: AUC must be exactly 0.5
    t2 = Table.from_columns(label=labels, rawPrediction=np.full(n, 0.7))
    out2 = BinaryClassificationEvaluator().transform(t2)[0]
    np.testing.assert_allclose(out2["areaUnderROC"][0], 0.5, atol=1e-12)


def test_evaluator_vector_raw_prediction(rng):
    from flink_ml_tpu.common.table import as_dense_vector_column
    labels = np.array([1.0, 0.0, 1.0, 0.0])
    probs = np.array([[0.2, 0.8], [0.7, 0.3], [0.4, 0.6], [0.6, 0.4]])
    t = Table.from_columns(label=labels,
                           rawPrediction=as_dense_vector_column(probs))
    out = BinaryClassificationEvaluator().transform(t)[0]
    assert out["areaUnderROC"][0] == 1.0


# ---------------------------------------------------------------------------
# Stats tests
# ---------------------------------------------------------------------------

def test_chisq_test_operator(rng):
    from scipy.stats import chi2_contingency
    x = rng.integers(0, 3, size=(200, 2)).astype(np.float64)
    y = rng.integers(0, 2, 200).astype(np.float64)
    t = Table.from_columns(features=x, label=y)
    flat = ChiSqTest(flatten=True).transform(t)[0]
    assert flat.column_names == ["featureIndex", "pValue",
                                 "degreeOfFreedom", "statistic"]
    assert flat.num_rows == 2
    # single-row variant
    wide = ChiSqTest().transform(t)[0]
    assert wide.num_rows == 1
    np.testing.assert_allclose(wide["pValues"][0].to_array(), flat["pValue"])


def test_anova_and_fvalue_operators(rng):
    from sklearn.feature_selection import f_classif
    y = rng.integers(0, 3, 150).astype(np.float64)
    x = rng.normal(size=(150, 3))
    x[:, 1] += y
    t = Table.from_columns(features=x, label=y)
    out = ANOVATest(flatten=True).transform(t)[0]
    f_sk, p_sk = f_classif(x, y)
    np.testing.assert_allclose(out["statistic"], f_sk, rtol=1e-8)
    np.testing.assert_allclose(out["pValue"], p_sk, rtol=1e-8)

    y2 = rng.normal(size=150)
    t2 = Table.from_columns(features=x, label=y2)
    out2 = FValueTest(flatten=True).transform(t2)[0]
    assert out2.num_rows == 3


# ---------------------------------------------------------------------------
# Swing
# ---------------------------------------------------------------------------

def test_swing_basic():
    # two users each bought items {1, 2, 3}: all pairs similar
    users = np.repeat([1, 2], 3).astype(np.int64)
    items = np.tile([1, 2, 3], 2).astype(np.int64)
    t = Table.from_columns(user=users, item=items)
    out = Swing(min_user_behavior=1, alpha1=0, alpha2=0, beta=0.0,
                k=2).transform(t)[0]
    assert set(out["item"].tolist()) == {1, 2, 3}
    recs = dict(zip(out["item"], out["output"]))
    # for item 1: users {1,2} intersect on {1,2,3}; w_u=w_v=1/3^0=1,
    # sim = 1/3; items 2,3 each get score 1/3
    first = recs[1].split(";")[0]
    item_id, score = first.split(",")
    assert float(score) == pytest.approx(1 / 3)


def test_swing_filters_and_validation():
    t = Table.from_columns(user=np.array([1, 1, 2], np.int64),
                           item=np.array([1, 2, 1], np.int64))
    # user 2 has 1 purchase < minUserBehavior=2 → filtered, no pairs
    out = Swing(min_user_behavior=2, k=5).transform(t)[0]
    assert out.num_rows == 0
    with pytest.raises(ValueError):
        Swing(min_user_behavior=10, max_user_behavior=5).transform(t)


def test_stats_tests_device_parity(rng):
    """Device-resident inputs run the on-device reduction branches of the
    ANOVA/F-value tests; results must match the host float64 paths."""
    from flink_ml_tpu.ops import columnar
    from flink_ml_tpu.ops.stats import anova_f_test, f_value_test

    x = (rng.normal(size=(600, 5)) * [1, 2, 3, 4, 5] + 3).astype(np.float64)
    y_cat = rng.integers(0, 3, 600).astype(np.float64)
    x[y_cat == 1, 0] += 2.0  # give feature 0 real signal
    y_cont = x[:, 1] * 0.5 + rng.normal(size=600)

    xd = columnar.to_device(x.astype(np.float32))
    for host, dev in [(anova_f_test(x, y_cat), anova_f_test(xd, y_cat)),
                      (f_value_test(x, y_cont), f_value_test(xd, y_cont))]:
        f_h, p_h, dof_h = host
        f_d, p_d, dof_d = dev
        np.testing.assert_allclose(f_d, f_h, rtol=2e-3)
        np.testing.assert_allclose(p_d, p_h, rtol=5e-3, atol=1e-9)
        np.testing.assert_array_equal(dof_d, dof_h)


def test_univariate_selector_device_parity(rng):
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.feature import UnivariateFeatureSelector
    from flink_ml_tpu.ops import columnar

    x = rng.normal(size=(400, 8))
    y = rng.integers(0, 2, 400).astype(np.float64)
    x[y == 1, 2] += 3.0
    sel = dict(features_col="f", label_col="l", output_col="o",
               feature_type="continuous", label_type="categorical",
               selection_mode="numTopFeatures", selection_threshold=2)
    m_h = UnivariateFeatureSelector(**sel).fit(
        Table.from_columns(f=x, l=y))
    m_d = UnivariateFeatureSelector(**sel).fit(
        Table.from_columns(f=columnar.to_device(x.astype(np.float32)), l=y))
    np.testing.assert_array_equal(sorted(m_h.indices), sorted(m_d.indices))
    assert 2 in m_d.indices


def test_kbins_device_subsample_slice(rng):
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.feature import KBinsDiscretizer
    from flink_ml_tpu.ops import columnar

    x = rng.normal(size=(1000, 3))
    kb = dict(input_col="f", output_col="o", num_bins=4, sub_samples=200)
    m_h = KBinsDiscretizer(**kb).fit(Table.from_columns(f=x))
    m_d = KBinsDiscretizer(**kb).fit(
        Table.from_columns(f=columnar.to_device(x.astype(np.float32))))
    for a, b in zip(m_h.bin_edges, m_d.bin_edges):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)


def test_naive_bayes_device_fit_parity(rng):
    """Integral categorical data on device must learn the same model as
    the host path (theta/pi/floors/labels) and fall back for data that
    does not qualify."""
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.classification.naivebayes import NaiveBayes
    from flink_ml_tpu.ops import columnar

    x = np.floor(rng.random((400, 6)) * 5)
    y = np.floor(rng.random(400) * 3)
    nb = dict(features_col="f", label_col="l")
    m_h = NaiveBayes(**nb).fit(Table.from_columns(f=x, l=y))
    m_d = NaiveBayes(**nb).fit(Table.from_columns(
        f=columnar.to_device(x.astype(np.float32)),
        l=columnar.to_device(y.astype(np.float32))))
    np.testing.assert_array_equal(m_d.labels, m_h.labels)
    np.testing.assert_allclose(m_d.pi, m_h.pi, rtol=1e-12)
    np.testing.assert_allclose(m_d.floors, m_h.floors, rtol=1e-12)
    np.testing.assert_array_equal(m_d.values, m_h.values)
    assert m_d.theta.shape == m_h.theta.shape == (3, 6, 5)
    np.testing.assert_allclose(m_d.theta, m_h.theta, rtol=1e-12)
    # identical predictions end to end
    t = Table.from_columns(f=x, l=y)
    np.testing.assert_array_equal(
        np.asarray(m_d.transform(t)[0]["prediction"]),
        np.asarray(m_h.transform(t)[0]["prediction"]))

    # non-integral features: device path declines, host fallback used
    x_frac = x + 0.5
    m_f = NaiveBayes(**nb).fit(Table.from_columns(
        f=columnar.to_device(x_frac.astype(np.float32)),
        l=columnar.to_device(y.astype(np.float32))))
    m_f_host = NaiveBayes(**nb).fit(Table.from_columns(
        f=x_frac.astype(np.float32).astype(np.float64), l=y))
    np.testing.assert_array_equal(m_f.labels, m_f_host.labels)
