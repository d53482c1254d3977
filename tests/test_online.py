"""Online algorithm tests (ref: OnlineLogisticRegressionTest.java,
OnlineKMeansTest.java, OnlineStandardScalerTest.java — unbounded streams
with model-version checks)."""

import numpy as np
import pytest

from flink_ml_tpu.common.table import Table, as_dense_vector_column
from flink_ml_tpu.iteration.streaming import StreamTable
from flink_ml_tpu.models.classification import (
    OnlineLogisticRegression,
    OnlineLogisticRegressionModel,
)
from flink_ml_tpu.models.clustering import KMeansModel, OnlineKMeans
from flink_ml_tpu.models.feature import (
    OnlineStandardScaler,
    OnlineStandardScalerModel,
)


def make_lr_stream(rng, n=2000, d=4):
    w_true = rng.normal(size=d)
    x = rng.normal(size=(n, d))
    y = (x @ w_true > 0).astype(np.float64)
    return Table.from_columns(features=x, label=y), w_true


def init_model_table(d):
    return Table.from_columns(
        coefficient=as_dense_vector_column(np.zeros((1, d))),
        modelVersion=np.asarray([0], np.int64))


def test_online_lr_requires_initial_model(rng):
    t, _ = make_lr_stream(rng, n=64)
    with pytest.raises(ValueError):
        OnlineLogisticRegression().fit(t)


def test_online_lr_learns_and_versions(rng):
    t, w_true = make_lr_stream(rng, n=4000)
    est = (OnlineLogisticRegression(global_batch_size=500, alpha=0.5,
                                    beta=1.0)
           .set_initial_model_data(init_model_table(4)))
    model = est.fit(t)
    # versions increment once per global batch
    assert model.model_version == 4000 // 500
    assert [v for v, _ in model.history] == list(range(1, 9))
    out = model.transform(t)[0]
    acc = np.mean(out["prediction"] == t["label"])
    assert acc > 0.9, f"accuracy {acc}"
    # version column stamped on predictions
    assert (out["version"] == model.model_version).all()


def test_online_lr_regularization_sparsifies(rng):
    t, _ = make_lr_stream(rng, n=2000)
    est = (OnlineLogisticRegression(global_batch_size=200, reg=2.0,
                                    elastic_net=1.0)
           .set_initial_model_data(init_model_table(4)))
    model = est.fit(t)
    assert np.count_nonzero(model.coefficients) < 4  # l1 zeroes weak dims


def test_online_lr_transform_stream_uses_versions(rng):
    t, _ = make_lr_stream(rng, n=900)
    est = (OnlineLogisticRegression(global_batch_size=300)
           .set_initial_model_data(init_model_table(4)))
    model = est.fit(t)
    outs = list(model.transform_stream(StreamTable.from_table(t, 300)))
    assert [o["version"][0] for o in outs] == [1, 2, 3]


def test_online_lr_save_load(rng, tmp_path):
    t, _ = make_lr_stream(rng, n=500)
    model = (OnlineLogisticRegression(global_batch_size=100)
             .set_initial_model_data(init_model_table(4))).fit(t)
    model.save(str(tmp_path / "olr"))
    reloaded = OnlineLogisticRegressionModel.load(str(tmp_path / "olr"))
    np.testing.assert_array_equal(reloaded.coefficients, model.coefficients)
    assert reloaded.model_version == model.model_version


def test_online_kmeans_tracks_drift(rng):
    # initial centroids near origin; stream shifted by +10 → centroids move
    init = KMeansModel(centroids=np.array([[0.0, 0.0], [1.0, 1.0]]),
                       weights=np.array([1.0, 1.0]))
    x = rng.normal(size=(1000, 2)) + np.array([10.0, 10.0])
    est = (OnlineKMeans(global_batch_size=100, decay_factor=0.5, k=2)
           .set_initial_model_data(init.get_model_data()[0]))
    model = est.fit(Table.from_columns(features=x))
    # the capturing centroid converges to the stream's mean; the empty one
    # keeps its position with decayed weight (reference semantics)
    closest = np.linalg.norm(model.centroids - np.array([10, 10]),
                             axis=1).min()
    assert closest < 0.5
    assert model.weights.max() > 100 and model.weights.min() < 1
    pred = model.transform(Table.from_columns(features=x))[0]["prediction"]
    assert pred.shape == (1000,)


def test_online_kmeans_decay_zero_forgets_history():
    init = KMeansModel(centroids=np.array([[100.0], [-100.0]]),
                       weights=np.array([1e9, 1e9]))
    x = np.concatenate([np.full((50, 1), 5.0), np.full((50, 1), -5.0)])
    est = (OnlineKMeans(global_batch_size=100, decay_factor=0.0, k=2)
           .set_initial_model_data(init.get_model_data()[0]))
    model = est.fit(Table.from_columns(features=x))
    # decay 0: old weights vanish; centroids jump to batch means
    np.testing.assert_allclose(sorted(model.centroids.ravel()), [-5.0, 5.0])


def test_online_standard_scaler(rng):
    from flink_ml_tpu.common.window import CountTumblingWindows
    x = rng.normal(size=(1000, 3)) * [1, 5, 10] + [0, 2, -4]
    t = Table.from_columns(input=x)
    est = OnlineStandardScaler(
        windows=CountTumblingWindows.of(250), with_mean=True)
    model = est.fit(t)
    assert model.model_version == 3  # 4 windows → versions 0..3
    assert len(model.history) == 4
    # cumulative stats equal full-batch stats at the end
    np.testing.assert_allclose(model.mean, x.mean(axis=0), rtol=1e-9)
    np.testing.assert_allclose(model.std, x.std(axis=0, ddof=1), rtol=1e-9)
    out = model.transform(t)[0]
    assert (out["version"] == 3).all()
    np.testing.assert_allclose(out["output"].std(axis=0, ddof=1), 1.0,
                               rtol=1e-6)


def test_online_standard_scaler_save_load(rng, tmp_path):
    x = rng.normal(size=(100, 2))
    model = OnlineStandardScaler().fit(Table.from_columns(input=x))
    model.save(str(tmp_path / "oss"))
    reloaded = OnlineStandardScalerModel.load(str(tmp_path / "oss"))
    np.testing.assert_array_equal(reloaded.mean, model.mean)
    assert reloaded.model_version == model.model_version


def test_online_lr_model_delay_join(rng):
    """maxAllowedModelDelayMs semantics: a chunk with event time t must be
    scored by a model of timestamp >= t - maxDelay, so raising the allowed
    delay lets data run ahead on an older model version."""
    from flink_ml_tpu.models.online import OnlineLogisticRegressionModel

    x = rng.normal(size=(40, 2))
    ts = np.arange(40, dtype=np.int64) * 100  # event times 0..3900
    t = Table.from_columns(features=x, ts=ts)
    chunks = StreamTable.from_table(t, 10)  # chunk max ts: 900/1900/2900/3900

    w_old, w_new = np.array([1.0, 0.0]), np.array([0.0, 1.0])
    # models arrive at t=0 (v1, old) and t=2900 (v2, new)
    model_stream = [(0, 1, w_old), (2900, 2, w_new)]

    model = OnlineLogisticRegressionModel(coefficients=w_old,
                                          model_version=1)
    model.set_max_allowed_model_delay_ms(0)
    outs = list(model.transform_stream(chunks, model_stream, "ts"))
    # delay 0: chunks ending at 900/1900 need model_ts>=900 → must advance
    # all the way to v2 (next available with ts>=900 is 2900)
    assert [int(o["version"][0]) for o in outs] == [2, 2, 2, 2]

    model2 = OnlineLogisticRegressionModel(coefficients=w_old,
                                           model_version=1)
    model2.set_max_allowed_model_delay_ms(2000)
    outs2 = list(model2.transform_stream(
        StreamTable.from_table(t, 10), iter(model_stream), "ts"))
    # delay 2000: chunk@900,1900 satisfied by model@0 (v1); chunk@2900
    # needs >=900 → still v1? 2900-2000=900 > 0 → advance to v2
    assert [int(o["version"][0]) for o in outs2] == [1, 1, 2, 2]


def test_online_lr_delay_join_always_uses_latest_arrived(rng):
    """A generous delay must not pin scoring to a stale model: models whose
    timestamps are in the data's past are always applied."""
    from flink_ml_tpu.models.online import OnlineLogisticRegressionModel

    x = rng.normal(size=(20, 2))
    ts = 2900 + np.arange(20, dtype=np.int64) * 100
    t = Table.from_columns(features=x, ts=ts)

    w1, w2 = np.array([1.0, 0.0]), np.array([0.0, 1.0])
    model = OnlineLogisticRegressionModel(coefficients=w1, model_version=1)
    model.set_max_allowed_model_delay_ms(5000)
    outs = list(model.transform_stream(
        StreamTable.from_table(t, 10), [(0, 1, w1), (100, 2, w2)], "ts"))
    assert [int(o["version"][0]) for o in outs] == [2, 2]


def test_online_lr_delay_join_requires_both_args(rng):
    from flink_ml_tpu.models.online import OnlineLogisticRegressionModel

    model = OnlineLogisticRegressionModel(coefficients=np.ones(2))
    with pytest.raises(ValueError, match="together"):
        list(model.transform_stream(StreamTable([]), model_stream=[]))


class _DieAfter:
    """Crash injection for unbounded fits: raises after N batches."""

    def __init__(self, at):
        self.at = at

    def on_epoch_watermark_incremented(self, batch_idx, state):
        if batch_idx + 1 == self.at:
            raise RuntimeError("injected crash")

    def on_iteration_terminated(self, state):
        pass


def test_online_lr_checkpoint_resume(rng, tmp_path):
    """Crash mid-stream, rerun the tail of the stream: the resumed fit
    continues from the checkpointed FTRL state (version keeps counting)."""
    from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
    from flink_ml_tpu.models.online import OnlineLogisticRegression

    x = rng.normal(size=(800, 4))
    y = (x @ [1, -1, 2, 0.5] > 0).astype(float)
    t = Table.from_columns(features=x, label=y)
    init = Table.from_columns(
        coefficient=np.zeros((1, 4)), modelVersion=np.asarray([0]))

    def est():
        e = OnlineLogisticRegression(global_batch_size=100, reg=0.0)
        e.set_initial_model_data(init)
        return e

    expected = est().fit(StreamTable.from_table(t, 100))

    mgr = CheckpointManager(str(tmp_path / "ck"))
    cfg = IterationConfig(mode="host", checkpoint_interval=2,
                          checkpoint_manager=mgr)
    with pytest.raises(RuntimeError):
        (est().set_iteration_config(cfg, listeners=[_DieAfter(3)])
         .fit(StreamTable.from_table(t, 100)))
    assert mgr.list_checkpoints()

    # crash fired in batch 3's listener, before that batch's checkpoint:
    # last snapshot = after batch 2, so re-feed batches 3..8
    tail = t.take(np.arange(200, 800))
    resumed = (est().set_iteration_config(cfg)
               .fit(StreamTable.from_table(tail, 100)))
    assert resumed.model_version == expected.model_version
    np.testing.assert_allclose(resumed.coefficients, expected.coefficients,
                               rtol=1e-8)
    assert not mgr.list_checkpoints()  # success cleared them


def test_online_kmeans_checkpoint_resume(rng, tmp_path):
    from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
    from flink_ml_tpu.models.online import OnlineKMeans

    x = np.concatenate([rng.normal(size=(200, 3)),
                        rng.normal(size=(200, 3)) + 5])
    rng.shuffle(x)
    t = Table.from_columns(features=x)
    init = KMeansModel(centroids=x[:2].copy(),
                       weights=np.zeros(2)).get_model_data()[0]

    def est():
        e = OnlineKMeans(global_batch_size=100, decay_factor=1.0, seed=0)
        e.set_initial_model_data(init)
        return e

    expected = est().fit(StreamTable.from_table(t, 100)).centroids

    mgr = CheckpointManager(str(tmp_path / "ck"))
    cfg = IterationConfig(mode="host", checkpoint_interval=1,
                          checkpoint_manager=mgr)
    with pytest.raises(RuntimeError):
        (est().set_iteration_config(cfg, listeners=[_DieAfter(2)])
         .fit(StreamTable.from_table(t, 100)))
    resumed = (est().set_iteration_config(cfg)
               .fit(StreamTable.from_table(t.take(np.arange(100, 400)), 100)))
    np.testing.assert_allclose(resumed.centroids, expected, rtol=1e-8)


def test_online_scaler_checkpoint_resume(rng, tmp_path):
    from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
    from flink_ml_tpu.models.online import OnlineStandardScaler

    x = rng.normal(size=(400, 3)) * 2 + 1
    t = Table.from_columns(input=x)
    expected = OnlineStandardScaler(input_col="input", output_col="o").fit(
        StreamTable.from_table(t, 100))

    mgr = CheckpointManager(str(tmp_path / "ck"))
    cfg = IterationConfig(mode="host", checkpoint_interval=1,
                          checkpoint_manager=mgr)
    est = OnlineStandardScaler(input_col="input", output_col="o")
    with pytest.raises(RuntimeError):
        (est.set_iteration_config(cfg, listeners=[_DieAfter(2)])
         .fit(StreamTable.from_table(t, 100)))
    est2 = OnlineStandardScaler(input_col="input", output_col="o")
    resumed = (est2.set_iteration_config(cfg)
               .fit(StreamTable.from_table(t.take(np.arange(100, 400)), 100)))
    np.testing.assert_allclose(resumed.mean, expected.mean, rtol=1e-8)
    np.testing.assert_allclose(resumed.std, expected.std, rtol=1e-8)
    assert resumed.model_version == expected.model_version


def test_iterate_unbounded_checkpointer(tmp_path):
    """The generalized iterate_unbounded checkpoint path: resume restores
    (model, version) with native Python types."""
    from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
    from flink_ml_tpu.iteration.streaming import (StreamCheckpointer,
                                                  iterate_unbounded)

    mgr = CheckpointManager(str(tmp_path / "ck"))
    cfg = IterationConfig(mode="host", checkpoint_interval=1,
                          checkpoint_manager=mgr)
    step = lambda model, batch: model + batch  # noqa: E731

    out = list(iterate_unbounded(0.0, [1.0, 2.0], step,
                                 checkpointer=StreamCheckpointer(cfg)))
    assert out[-1] == (3.0, 2)
    assert not mgr.list_checkpoints()  # completion cleared

    # crash after two batches: simulate by not completing (partial iteration)
    gen = iterate_unbounded(0.0, [1.0, 2.0, 4.0], step,
                            checkpointer=StreamCheckpointer(cfg))
    assert next(gen) == (1.0, 1) and next(gen) == (3.0, 2)
    del gen  # abandoned mid-stream: checkpoints survive
    assert mgr.list_checkpoints()

    resumed = list(iterate_unbounded(0.0, [4.0], step,
                                     checkpointer=StreamCheckpointer(cfg)))
    (model, ver), = resumed
    assert (model, ver) == (7.0, 3)
    assert type(ver) is int


def test_window_stream_event_time(rng):
    from flink_ml_tpu.common.window import EventTimeTumblingWindows
    from flink_ml_tpu.iteration.streaming import window_stream

    ts = np.array([0, 100, 900, 1000, 1500, 2100, 2200], np.int64)
    t = Table.from_columns(v=np.arange(7.0), ts=ts)
    wins = list(window_stream(StreamTable.from_table(t, 3),
                              EventTimeTumblingWindows.of(1000), "ts"))
    assert [list(w["v"]) for w in wins] == [[0, 1, 2], [3, 4], [5, 6]]


def test_online_scaler_event_time_windows(rng):
    """One versioned model per event-time tumbling window; cumulative
    moments across windows (reference OnlineStandardScaler semantics)."""
    from flink_ml_tpu.common.window import EventTimeTumblingWindows
    from flink_ml_tpu.models.online import OnlineStandardScaler

    x = rng.normal(size=(60, 2)) * 3 + 2
    ts = np.arange(60, dtype=np.int64) * 100  # 0..5900 → 6 windows of 1000ms
    t = Table.from_columns(input=x, ts=ts)

    est = OnlineStandardScaler(input_col="input", output_col="o")
    est.set_windows(EventTimeTumblingWindows.of(1000))
    model = est.fit(StreamTable.from_table(t, 25), timestamp_col="ts")
    assert len(model.history) == 6          # one snapshot per window
    assert model.model_version == 5
    # window-end timestamps: the (timestamp, version, data) stream the
    # model-delay join consumes
    assert model.history_timestamps == [1000, 2000, 3000, 4000, 5000, 6000]
    assert model.timestamp == 6000
    np.testing.assert_allclose(model.mean, x.mean(axis=0), rtol=1e-8)
    np.testing.assert_allclose(model.std, x.std(axis=0, ddof=1), rtol=1e-8)

    with pytest.raises(ValueError, match="timestamp_col"):
        OnlineStandardScaler(input_col="input", output_col="o") \
            .set_windows(EventTimeTumblingWindows.of(1000)) \
            .fit(StreamTable.from_table(t, 25))


def test_window_stream_event_time_sessions(rng):
    """Session windows close on a gap > gap_ms or at end-of-stream; end
    timestamp = last element + gap (SessionWindows.java semantics, close
    rule per docs/deviations.md)."""
    from flink_ml_tpu.common.window import EventTimeSessionWindows
    from flink_ml_tpu.iteration.streaming import window_stream

    #           ├─ session 1 ─┤  gap>500   ├ s2 ┤   gap>500  ├ s3
    ts = np.array([0, 100, 400, 450, 1500, 1600, 3000], np.int64)
    t = Table.from_columns(v=np.arange(7.0), ts=ts)
    # chunking must not affect assignment: try several chunk sizes
    for chunk in (1, 2, 3, 7):
        wins = list(window_stream(StreamTable.from_table(t, chunk),
                                  EventTimeSessionWindows.with_gap(500),
                                  "ts", with_end_ts=True))
        assert [list(w["v"]) for _, w in wins] == \
            [[0, 1, 2, 3], [4, 5], [6]]
        assert [end for end, _ in wins] == [950, 2100, 3500]

    with pytest.raises(ValueError, match="timestamp_col"):
        list(window_stream(StreamTable.from_table(t, 3),
                           EventTimeSessionWindows.with_gap(500)))


def test_window_stream_processing_time_sessions(monkeypatch):
    """Processing-time sessions bucket by chunk arrival gaps."""
    import time as time_mod

    from flink_ml_tpu.common.window import ProcessingTimeSessionWindows
    from flink_ml_tpu.iteration.streaming import window_stream

    arrivals = iter([0.0, 0.1, 5.0, 5.2, 20.0])  # seconds
    monkeypatch.setattr(time_mod, "time", lambda: next(arrivals))
    t = Table.from_columns(v=np.arange(10.0))
    wins = list(window_stream(StreamTable.from_table(t, 2),
                              ProcessingTimeSessionWindows.with_gap(1000)))
    assert [list(w["v"]) for w in wins] == \
        [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9]]


def test_online_scaler_session_windows(rng):
    """One versioned model per session window (VERDICT r2 ask #4): three
    activity bursts separated by >gap silence → three snapshots stamped
    last-event + gap."""
    from flink_ml_tpu.common.window import EventTimeSessionWindows
    from flink_ml_tpu.models.online import OnlineStandardScaler

    x = rng.normal(size=(60, 2)) * 3 + 2
    ts = np.concatenate([
        np.arange(20, dtype=np.int64) * 10,          # burst 1: 0..190
        5000 + np.arange(20, dtype=np.int64) * 10,   # burst 2: 5000..5190
        9000 + np.arange(20, dtype=np.int64) * 10,   # burst 3: 9000..9190
    ])
    t = Table.from_columns(input=x, ts=ts)

    est = OnlineStandardScaler(input_col="input", output_col="o")
    est.set_windows(EventTimeSessionWindows.with_gap(1000))
    model = est.fit(StreamTable.from_table(t, 7), timestamp_col="ts")
    assert len(model.history) == 3
    assert model.history_timestamps == [1190, 6190, 10190]
    np.testing.assert_allclose(model.mean, x.mean(axis=0), rtol=1e-8)
    np.testing.assert_allclose(model.std, x.std(axis=0, ddof=1), rtol=1e-8)


def test_online_scaler_count_windows_rechunk_stream(rng):
    """CountTumblingWindows must re-group a pre-chunked stream to the
    window size, not inherit the stream's chunking."""
    from flink_ml_tpu.common.window import CountTumblingWindows
    from flink_ml_tpu.models.online import OnlineStandardScaler

    x = rng.normal(size=(200, 2))
    t = Table.from_columns(input=x)
    est = OnlineStandardScaler(input_col="input", output_col="o")
    est.set_windows(CountTumblingWindows.of(100))
    model = est.fit(StreamTable.from_table(t, 25))  # 25-row chunks
    assert len(model.history) == 2  # 200 rows / 100-row windows


def test_processing_time_windows_no_timestamp_col(rng):
    """Processing-time windows bucket by arrival; no timestamp column."""
    from flink_ml_tpu.common.window import ProcessingTimeTumblingWindows
    from flink_ml_tpu.models.online import OnlineStandardScaler

    x = rng.normal(size=(50, 2))
    t = Table.from_columns(input=x)
    est = OnlineStandardScaler(input_col="input", output_col="o")
    est.set_windows(ProcessingTimeTumblingWindows.of(3_600_000))
    model = est.fit(StreamTable.from_table(t, 10))
    # all chunks arrive within one wall-clock hour window
    assert len(model.history) == 1
    np.testing.assert_allclose(model.mean, x.mean(axis=0), rtol=1e-8)


def test_online_models_publish_model_gauges(rng):
    """Ref: consuming model data publishes ml.model version/timestamp
    gauges (OnlineStandardScalerModel.java:202-210)."""
    from flink_ml_tpu.common.metrics import metrics
    from flink_ml_tpu.models.online import OnlineStandardScalerModel

    md = Table.from_columns(
        mean=np.zeros((1, 2)), std=np.ones((1, 2)),
        modelVersion=np.asarray([7], np.int64),
        timestamp=np.asarray([123456], np.int64))
    OnlineStandardScalerModel(with_std=True).set_model_data(md)
    g = metrics.group("ml", "model")
    assert g.get_gauge("version") == 7
    assert g.get_gauge("timestamp") == 123456


def test_online_lr_mixed_dense_sparse_stream(rng):
    """A stream interleaving dense and sparse (CSR) batches crosses the
    device/host residency boundary both ways (dense batches keep FTRL state
    on device; a sparse batch pulls it back to host). With full-pattern
    sparse vectors the two branches compute the same math, so the mixed
    stream must match an all-dense fit — and the public model contract
    stays host numpy float64 regardless of where state last lived."""
    from flink_ml_tpu.linalg.vectors import SparseVector
    from flink_ml_tpu.models.online import OnlineLogisticRegression

    n, d, b = 600, 4, 200
    x = rng.normal(size=(n, d))
    y = (x @ [1.0, -2.0, 0.5, 1.5] > 0).astype(np.float64)

    def sparse_col(block):
        col = np.empty(block.shape[0], dtype=object)
        for i, row in enumerate(block):
            col[i] = SparseVector(d, np.arange(d), row)
        return col

    chunks = [
        Table.from_columns(features=x[0:b], label=y[0:b]),          # dense
        Table.from_columns(features=sparse_col(x[b:2 * b]),         # CSR
                           label=y[b:2 * b]),
        Table.from_columns(features=x[2 * b:], label=y[2 * b:]),    # dense
    ]

    def fit(stream):
        est = OnlineLogisticRegression(global_batch_size=b)
        est.set_initial_model_data(init_model_table(d))
        return est.fit(stream)

    mixed = fit(StreamTable(iter(chunks)))
    all_dense = fit(Table.from_columns(features=x, label=y))

    np.testing.assert_allclose(mixed.coefficients, all_dense.coefficients,
                               rtol=1e-5, atol=1e-7)
    assert mixed.model_version == n // b
    for v, c in mixed.history:
        assert isinstance(c, np.ndarray) and c.dtype == np.float64
    assert isinstance(mixed.coefficients, np.ndarray)
    assert mixed.coefficients.dtype == np.float64


def test_generate_batches_preserves_device_residency():
    """Chunks whose device columns align with the global batch size must
    flow through generate_batches without a host off-ramp (an earlier
    version concatenated each chunk with an empty buffer, silently pulling
    every batch to host — 40 MB per batch over the host link)."""
    import jax.numpy as jnp

    from flink_ml_tpu.iteration.streaming import generate_batches

    x = jnp.ones((40, 4), jnp.float32)
    y = jnp.zeros((40,), jnp.float32)
    chunks = [Table.from_columns(features=x[i:i + 10], label=y[i:i + 10])
              for i in range(0, 40, 10)]
    for batch in generate_batches(StreamTable(iter(chunks)), 10):
        col = batch.column("features")
        assert not isinstance(col, np.ndarray) and hasattr(col, "devices"), \
            "device column was off-ramped to host"
