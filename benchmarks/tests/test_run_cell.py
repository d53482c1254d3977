"""The rehearsal: one run of every cell end to end at a size the CPU holds,
through ``run_cell.run``'s internal entry; the runs that must print no
result; and the timed path broken underneath, once for each fault a cell
can have, with ``correct`` coming out false."""

import io
import json
import types

import numpy as np
import pytest

from benchmarks import run_cell
from benchmarks.harness import device, spec
from benchmarks.harness import system as real_system

PEAKS = {"peak_flops_per_s": 1e12, "peak_hbm_bytes_per_s": 1e11}
CUT = {"inputData": {"numValues": 48000}, "stage": {"globalBatchSize": 400}}
CUTS = {"lr_fit_ref20": CUT, "lr_fit_ref20_dp4": CUT}
SEED = 2**31 + 4321


@pytest.fixture(autouse=True)
def compile_cache(tmp_path_factory):
    """The while-loop program asks for one small compile in every fit (a
    finding of PR 25); as on the chip, the persistent cache answers it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_compilation_cache_dir
    cc.set_cache_dir(str(tmp_path_factory.getbasetemp() / "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield
    cc.reset_cache()
    if before:
        cc.set_cache_dir(before)


def drive(cell, system=None, trace=False, seconds=0.3):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell.run(cell, SEED, seconds, trace, system=system,
                      require_tpu=False, overrides=CUTS[cell], peaks=PEAKS,
                      out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()


@pytest.mark.parametrize("cell", sorted(CUTS))
def test_rehearsal_end_to_end(cell):
    rc, result, err = drive(cell)
    assert rc == 0
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, err
    assert result["attempted"] >= 1 and result["failed"] == 0
    wanted = {m["name"] for m in spec.load_cell(cell).end_to_end}
    assert set(result["metrics"]) == wanted
    assert all(m["value"] > 0 for m in result["metrics"].values())
    for name, c in result["compared"].items():
        assert c["value"] <= c["limit"], name
        assert f"compared {name}: value" in err
    assert err.strip().splitlines()[-1] == "correct: True"


def test_traced_run_leaves_out_what_it_cannot_read():
    """No TPU plane on the CPU: the readers of the device trace return
    nothing and their metrics are left out, never 0."""
    rc, result, _ = drive("lr_fit_ref20", trace=True, seconds=0.6)
    assert rc == 0 and result["correct"] is True
    assert "fit_device_roofline" not in result["metrics"]
    assert "device_idle_pct" not in result["metrics"]
    # compile requests, not backend compiles: the while-loop form asks for
    # one small program anew in every fit (PERF.md section 5)
    assert result["metrics"]["window_compiles"]["value"] <= result["attempted"]
    assert result["compared"]["window_backend_compiles"]["value"] == 0
    assert result["metrics"]["fit_mfu"]["value"] > 0
    assert result["metrics"]["setup_datagen_s"]["value"] > 0


def test_same_seed_same_answer_other_seed_other_table():
    from benchmarks.harness import generators

    mesh = real_system.configure_mesh(__import__("jax").devices()[:1])
    params = {"colNames": [["features", "label", "weight"]], "numValues": 64,
              "vectorDim": 3, "featureArity": 0, "labelArity": 2}
    make = lambda seed: np.asarray(generators.make_columns(  # noqa: E731
        "org.x.LabeledPointWithWeightGenerator", params, seed,
        real_system.row_sharding(mesh))["features"])
    np.testing.assert_array_equal(make(SEED), make(SEED))
    assert not np.array_equal(make(SEED), make(SEED + 1))
    assert make(SEED).min() >= 0.0 and make(SEED).max() < 1.0


# -- runs that must print no result -------------------------------------------

def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
    rc = run_cell.main(["--workload", "lr_fit_ref20", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0 and captured.out.strip() == ""
    assert "not 'tpu'" in captured.err


@pytest.mark.parametrize("name", ["nope", "lr_fit_r120"])
def test_unknown_workload_exits_nonzero(capsys, name):
    rc = run_cell.main(["--workload", name, "--seed", "1", "--seconds", "1"])
    assert rc != 0 and capsys.readouterr().out.strip() == ""


def _fake(kind="TPU v5 lite", platform="tpu"):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_device_checks():
    assert device.require_chips([_fake()], 1)["count"] == 1
    with pytest.raises(device.DeviceError, match="peaks.json"):
        device.require_chips([_fake(kind="TPU v9")], 1)
    with pytest.raises(device.DeviceError, match="needs 4 chips"):
        device.require_chips([_fake()], 4)
    with pytest.raises(device.DeviceError, match="not 'tpu'"):
        device.require_chips([_fake(platform="gpu")], 1)
    with pytest.raises(device.DeviceError):
        device.require_chips([], 1)


# -- the timed path broken underneath ----------------------------------------

class Broken:
    """The real system with one fault planted where the window drives it."""

    def __init__(self, fault):
        self.fault = fault
        self.init_rows = None

    def __getattr__(self, name):
        return getattr(real_system, name)

    def configure_mesh(self, devices):
        if self.fault == "no_exchange":
            devices = list(devices)[:1]     # chip 0 alone, no psum
        return real_system.configure_mesh(devices)

    def make_table(self, columns):
        if self.fault == "no_exchange":     # chip 0's rows alone
            columns = {k: v[:v.shape[0] // 4] for k, v in columns.items()}
        return real_system.make_table(columns)

    def build_stage(self, class_name, params):
        params = dict(params)
        if self.fault == "half_batch" and "globalBatchSize" in params:
            params["globalBatchSize"] //= 2
        if self.fault == "no_exchange":
            params["globalBatchSize"] //= 4
        return real_system.build_stage(class_name, params)

    def model_to_host(self, stage, model):
        answer, path = real_system.model_to_host(stage, model)
        first = sorted(answer)[0]
        if self.fault == "state_unchanged":
            answer = {k: np.zeros_like(v) for k, v in answer.items()}
        if self.fault == "answer_altered":
            altered = np.array(answer[first], np.float64)
            altered.flat[0] += 0.02 * np.max(np.abs(altered))
            answer = dict(answer, **{first: altered})
        return answer, path


LR_FAULTS = ["state_unchanged", "half_batch", "answer_altered"]


@pytest.mark.parametrize("cell,fault", [
    *[("lr_fit_ref20", f) for f in LR_FAULTS],
    *[("lr_fit_ref20_dp4", f) for f in LR_FAULTS + ["no_exchange"]],
])
def test_a_broken_timed_path_is_not_correct(cell, fault):
    rc, result, err = drive(cell, system=Broken(fault))
    assert rc == 0
    assert result["correct"] is False, (fault, result["compared"])
    assert err.strip().splitlines()[-1] == "correct: False"
    assert any(c["value"] > c["limit"] for c in result["compared"].values())


def test_a_compile_inside_the_window_is_not_correct():
    class Recompiling(Broken):
        def fit(self, stage, table):
            import jax
            import jax.numpy as jnp

            self.n = getattr(self, "n", 0) + 1
            if self.n > 3:      # after the warm fits: a new shape each fit
                jax.jit(lambda a: a + 1)(jnp.zeros(self.n))
            return real_system.fit(stage, table)

    rc, result, _ = drive("lr_fit_ref20", system=Recompiling(None))
    assert rc == 0 and result["correct"] is False
    assert result["compared"]["window_backend_compiles"]["value"] > 0


def test_tail_reader_leaves_out_traced_fits_and_short_windows():
    from benchmarks.harness import readers

    read = readers.load("fit_wall_tail_ms")
    walls = [0.010] * 95 + [0.020] * 5 + [0.5] * 10
    traced = [False] * 100 + [True] * 10
    tail = read({"window": {"walls_s": walls, "traced": traced}})
    assert 10.0 <= tail <= 20.0
    assert read({"window": {"walls_s": walls[:10],
                            "traced": traced[:10]}}) is None


def test_tail_stands_end_to_end_only_where_it_is_steady():
    one, four = spec.load_cell("lr_fit_ref20"), spec.load_cell(
        "lr_fit_ref20_dp4")
    assert "fit_wall_p95_ms" not in {m["name"] for m in one.end_to_end}
    assert "fit_wall_tail_ms" in {m["name"] for m in one.per_layer}
    assert "fit_wall_p95_ms" in {m["name"] for m in four.end_to_end}
    assert "fit_wall_tail_ms" not in {m["name"] for m in four.per_layer}
