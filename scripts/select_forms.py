"""The forms of an exact per-column order statistic, timed on the chip at the
RobustScaler cell's shape (12M x 100 float32, lower / median / upper): what
a process's FIRST fit costs in each, phase by phase, and what a steady fit
does. PERF.md section 6 (PR 36, PR 37) holds what it read; this file is the
record of what was timed.

    python scripts/select_forms.py [--rows 12000000] [--dim 100] [--heads 3,4] [--blocks 8192] [--slices 8]

The forms are one program text with another number of straight-line counting
passes in its head (``ops/quantile.HEAD_PASSES``), after which the head
finishes its brackets where they are few-element brackets (the finishing
pass, behind a branch) and the driver goes pass by pass for what is left.
This process never touches JAX (a chip belongs to one process at a time):
every measurement is a child, each a fresh process, each printing one JSON
line.

- ``first[K]`` (``--runs`` children a form; the first of them fills the
  compile cache where it was cold and says so by its ``backend_compiles``):
  process start to ``import jax``, to ``import flink_ml_tpu`` and the
  selection's module, ``jax.devices()``, the table, then the FIRST
  ``select_on_device`` of the process (its wall is what a stage's first
  fit pays beside the stage's own wrapper: trace, lowering, the cache's
  load, the first execution of every program the fit runs), its compile
  requests, the programs it made, whether ``jax.experimental.pallas`` was
  imported; then ``--repeats`` warm calls: the steady fit, its passes;
- ``phases[K]``: the same set-up, then the head, the step and the finishing
  program through the staged API, each step timed alone: ``trace``,
  ``lower`` (and the lowered text's length), ``compile`` (with the cache
  warm: its load), the first execution, a second;
- ``pallas_import``: ``import jax.experimental.pallas`` and ``.tpu`` in a
  fresh process that has imported jax: the floor of any form that keeps a
  kernel (form (c) of ISSUE 36), before one line of it is traced;
- ``steady``: one child that holds the table: ``bisect32`` (what
  ``rank_select_device`` was until PR 36: a kept ``(n, d)`` key image and 32
  rounds), ``xla_pass[9]`` (one counting pass at nine pivots a column,
  alone) and ``xla_pass[9 | 3 | 0 + ends]`` (the ends of three brackets
  beside nine counts, three, none: what the pass that pulls a bracket in to
  its elements costs, and what of it is the ends'), ``finish[blocks x
  slices]`` (the finishing pass's read of the table alone, ``block_sums``
  and ``block_elements`` on brackets as the cell's third pass leaves them,
  for every ``--blocks`` a shard and ``--slices`` a turn of its loop),
  ``select[K]`` for every head on the cell's uniform table, answers held to
  ``bisect32``'s bit for bit, and ``tables[<distribution>][K]`` on tables of
  OTHER distributions (``table_makers``), ``--tables`` seeds each: passes,
  the brackets the finishing pass closed and declined, and milliseconds (a
  pass count is the table's, not the shape's). What PR 37 also timed and
  did not keep (PERF.md section 6): count and sum in words of their own
  (37.6 ms where the packed word takes 21.4), blocks of neighbouring rows
  (29.3; a sorted table's bracket is then one block), the finishing
  program launched by the driver after a report (52.8 ms a fit for 51.3).

- ``seeds[<rule>]`` (``--seeds N``): the cell's uniform table on ``N`` more
  seeds under each of ``RULES`` (the program as kept; the pass that pulls a
  bracket's ends in asked for sooner, and later; a sample twice as large): passes, ends
  passes and milliseconds a seed. The cell's spread over seeds is the share
  of seeds whose table takes a pass more, or a dearer one, times what that
  costs.

Needs a TPU: off the chip a child exits 2 (``--allow-cpu`` for a rehearsal
at a small ``--rows``).
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

PROBS = [0.25, 0.5, 0.75]


def bisect32(x, ranks):
    """The form PR 36 replaced, as it stood (``ops/quantile.py`` at
    1679eaa)."""
    import jax
    import jax.numpy as jnp

    m = ranks.shape[0]
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    keys = jnp.where(u >= jnp.uint32(0x80000000),
                     jnp.uint32(0xFFFFFFFF) - u,
                     u + jnp.uint32(0x80000000))
    target = (ranks + 1)[:, None]
    d = x.shape[1]
    lo = jnp.zeros((m, d), jnp.uint32)
    hi = jnp.full((m, d), jnp.uint32(0xFFFFFFFF))

    def step(_, state):
        lo, hi = state
        mid = lo + (hi - lo) // jnp.uint32(2)
        cnt = jnp.sum(
            (keys[:, :, None] <= mid.T[None, :, :]).astype(jnp.int32),
            axis=0)
        ok = cnt.T >= target
        return jnp.where(ok, lo, mid + jnp.uint32(1)), jnp.where(ok, mid, hi)

    _, hi = jax.lax.fori_loop(0, 32, step, (lo, hi))
    back = jnp.where(hi >= jnp.uint32(0x80000000),
                     hi - jnp.uint32(0x80000000),
                     jnp.uint32(0xFFFFFFFF) - hi)
    return jax.lax.bitcast_convert_type(back, jnp.float32)


def table_makers(n: int, d: int):
    """``{distribution: key -> (n, d) float32}``, each jitted: the tables
    the program is timed on."""
    import jax
    import jax.numpy as jnp

    def uniform(key):
        return jax.random.uniform(key, (n, d), jnp.float32)

    def normal(key):
        return jax.random.normal(key, (n, d), jnp.float32)

    def zero_inflated(key):
        u = jax.random.uniform(key, (n, d), jnp.float32)
        return jnp.where(u < 0.6, 0.0, -jnp.log1p(-(u - 0.6) / 0.4))

    def integer_coded(key):
        return jax.random.randint(key, (n, d), 0, 1000).astype(jnp.float32)

    def sorted_rows(key):
        slope, shift = jax.random.split(key)
        return (jnp.arange(n, dtype=jnp.float32)[:, None] / n
                * jax.random.uniform(slope, (1, d), jnp.float32, 0.5, 2.0)
                + jax.random.normal(shift, (1, d), jnp.float32))

    return {name: jax.jit(make) for name, make in (
        ("uniform", uniform), ("normal", normal),
        ("zero_inflated", zero_inflated), ("integer_coded", integer_coded),
        ("sorted", sorted_rows))}


def select_counts():
    """``ml.select``'s counters, as they stand."""
    from flink_ml_tpu.common.metrics import ML_GROUP, metrics

    return dict(metrics.group(ML_GROUP, "select").snapshot()["counters"])


def counted(call):
    """``(call's result, the finishing pass's finished and declined
    brackets inside it)``."""
    before = select_counts()
    out = call()
    after = select_counts()
    return out, [after.get(k, 0) - before.get(k, 0)
                 for k in ("finished", "declined")]


def timed(fn, repeats: int):
    """``(median seconds, last result)`` of ``repeats`` warm calls."""
    import jax

    out = jax.block_until_ready(fn())           # compile + warm
    walls = []
    for _ in range(repeats):
        t = time.perf_counter()
        out = jax.block_until_ready(fn())
        walls.append(time.perf_counter() - t)
    return statistics.median(walls), out


# -- the children --------------------------------------------------------------

def _set_up(args, phases: dict):
    """What every child does before it measures: the imports, the compile
    cache as ``benchmarks/run_cell.py`` configures it, the chip, the mesh.
    Fills ``phases`` and returns ``(jax, quantile, mesh)``."""
    import jax

    phases["import_jax_s"] = time.perf_counter() - _PROCESS_START
    from benchmarks import run_cell
    from flink_ml_tpu.ops import quantile
    from flink_ml_tpu.parallel.mesh import create_mesh, set_default_mesh

    phases["imports_s"] = time.perf_counter() - _PROCESS_START
    run_cell.configure_compile_cache()
    t = time.perf_counter()
    devices = jax.devices()
    phases["devices_s"] = time.perf_counter() - t
    if devices[0].platform != "tpu" and not args.allow_cpu:
        print("select_forms needs a TPU", file=sys.stderr)
        sys.exit(2)
    mesh = create_mesh(devices=devices[:1])
    set_default_mesh(mesh)
    return jax, quantile, mesh


def child_first(args, head: int) -> dict:
    phases = {}
    jax, quantile, mesh = _set_up(args, phases)
    from benchmarks.harness import compiles

    listener = compiles.CompileListener().install()
    t = time.perf_counter()
    x = jax.block_until_ready(table_makers(args.rows, args.dim)["uniform"](
        jax.random.key(args.seed)))
    phases["datagen_s"] = time.perf_counter() - t
    quantile.HEAD_PASSES = head
    before = listener.snapshot()
    t = time.perf_counter()
    _, passes = quantile.select_on_device(x, PROBS)
    first = time.perf_counter() - t
    built = compiles.delta(listener.snapshot(), before)
    walls = []
    for _ in range(args.repeats):
        t = time.perf_counter()
        quantile.select_on_device(x, PROBS)
        walls.append((time.perf_counter() - t) * 1e3)
    return {"form": f"first[{head}]", **phases, "first_fit_s": first,
            "compiles": built, "passes": passes,
            "steady_fit_ms": statistics.median(walls),
            "pallas_imported": "jax.experimental.pallas" in sys.modules}


def child_phases(args, head: int) -> dict:
    phases = {}
    jax, quantile, mesh = _set_up(args, phases)
    x = jax.block_until_ready(table_makers(args.rows, args.dim)["uniform"](
        jax.random.key(args.seed)))
    quantile.HEAD_PASSES = head
    spec = quantile._spec_on_mesh(mesh, args.rows, tuple(PROBS))
    head_program, step_program, _ = quantile.select_programs(mesh, len(PROBS))
    finish_program = quantile.finish_program(mesh, len(PROBS))
    out = {"form": f"phases[{head}]"}

    def staged(name, program, operands):
        t0 = time.perf_counter()
        traced = program.trace(*operands)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        compiled = lowered.compile()
        t3 = time.perf_counter()
        result = jax.block_until_ready(compiled(*operands))
        t4 = time.perf_counter()
        jax.block_until_ready(compiled(*operands))
        t5 = time.perf_counter()
        out[name] = {"trace_s": t1 - t0, "lower_s": t2 - t1,
                     "lowered_chars": len(lowered.as_text()),
                     "compile_or_load_s": t3 - t2, "first_run_ms":
                     (t4 - t3) * 1e3, "second_run_ms": (t5 - t4) * 1e3}
        return result

    state, _ = staged("head", head_program, (x, spec))
    staged("step", step_program, (x, spec, state))
    staged("finish", finish_program, (x, spec, state))
    return out


def child_pallas_import(args) -> dict:
    import jax  # noqa: F401

    t = time.perf_counter()
    import jax.experimental.pallas  # noqa: F401
    import jax.experimental.pallas.tpu  # noqa: F401

    return {"form": "pallas_import",
            "import_jax_s": t - _PROCESS_START,
            "import_pallas_s": time.perf_counter() - t}


def child_steady(args) -> list:
    import numpy as np

    phases = {}
    jax, quantile, mesh = _set_up(args, phases)
    import jax.numpy as jnp

    n, d = args.rows, args.dim
    makers = table_makers(n, d)
    ranks = quantile.select_ranks(PROBS, n)
    table_bytes = n * d * 4
    lines = [{"form": "shape", "device": jax.devices()[0].device_kind,
              "rows": n, "dim": d,
              "table_read_ms_at_819GBs": table_bytes / 819e9 * 1e3}]

    def say(form, **fields):
        lines.append({"form": form, **fields})
        print(json.dumps(lines[-1]), flush=True)

    def select_with(head):
        quantile.HEAD_PASSES = head
        quantile.select_programs.cache_clear()
        quantile.finish_program.cache_clear()

    def over_seeds(make):
        passes, finished, declined, walls = [], [], [], []
        for seed in range(args.seed + 1, args.seed + 1 + args.tables):
            x = jax.block_until_ready(make(jax.random.key(seed)))
            if not passes:
                quantile.select_on_device(x, PROBS)        # builds
            t = time.perf_counter()
            (_, made), closed = counted(
                lambda: quantile.select_on_device(x, PROBS))
            walls.append((time.perf_counter() - t) * 1e3)
            passes.append(made)
            finished.append(closed[0])
            declined.append(closed[1])
            del x
        return passes, finished, declined, walls

    x = jax.block_until_ready(makers["uniform"](jax.random.key(args.seed)))
    shipped_slices = quantile.FINISH_SLICES
    want = None
    if not args.skip_bisect32:
        old_form = jax.jit(bisect32)
        s, want = timed(lambda: old_form(x, ranks), 3)
        want = np.asarray(want)
        say("bisect32", ms=s * 1e3, passes=33)
    rng = np.random.default_rng(args.seed)
    piv = jnp.asarray(np.sort(rng.integers(
        0x3E000000, 0x3F800000, (9, d)), axis=0), jnp.int32)
    one_pass = jax.jit(quantile.count_le)
    s, _ = timed(lambda: one_pass(x, piv), args.repeats)
    say("xla_pass[9]", ms=s * 1e3, GBps=table_bytes / s / 1e9)

    def with_ends(pivots):
        """A pass at the first ``pivots`` of ``piv`` with the ends of three
        brackets beside them."""
        @jax.jit
        def run(x, piv):
            lo, hi = quantile._unsigned(piv[:3]), quantile._unsigned(piv[6:])
            found = quantile._ends_within(x, lo, hi, n)
            return (quantile.count_le(x, piv[:pivots]), found) if pivots \
                else found
        return run

    for pivots in (9, 3, 0):
        run = with_ends(pivots)
        s, _ = timed(lambda: run(x, piv), args.repeats)
        say(f"xla_pass[{pivots} + ends]", ms=s * 1e3,
            GBps=table_bytes / s / 1e9)
    # brackets as the cell's third pass leaves them: some 70-140 elements
    # in 200 keys around each quartile
    lo = jnp.asarray(np.repeat(np.asarray(quantile._float_to_key(
        jnp.asarray(PROBS, jnp.float32)))[:, None], d, axis=1) - 100)
    width = jnp.full((len(PROBS), d), 200, jnp.uint32)
    finish = jax.jit(lambda x, lo, width, blocks: quantile.block_elements(
        quantile.block_sums(x, lo, width, blocks), width), static_argnums=3)
    for blocks in args.blocks:
        for slices in args.slices:
            quantile.FINISH_SLICES = slices
            finish.clear_cache()
            s, out = timed(lambda: finish(x, lo, width, blocks),
                           args.repeats)
            say(f"finish[{blocks} x {slices}]", ms=s * 1e3,
                GBps=table_bytes / s / 1e9,
                crowded=int(np.asarray(out[1]).sum()))
    quantile.FINISH_SLICES = shipped_slices
    for head in args.heads:
        select_with(head)
        quantile.select_on_device(x, PROBS)                # builds
        walls = []
        for _ in range(args.repeats):
            t = time.perf_counter()
            (got, passes), closed = counted(
                lambda: quantile.select_on_device(x, PROBS))
            walls.append((time.perf_counter() - t) * 1e3)
        say(f"select[{head}]", ms=statistics.median(walls), passes=passes,
            finished=closed[0], declined=closed[1],
            equals_bisect32=None if want is None else bool(
                np.array_equal(got.view(np.uint32), want.view(np.uint32))))
    del x
    for head in args.heads:
        select_with(head)
        for name, make in makers.items():
            passes, finished, declined, walls = over_seeds(make)
            say(f"tables[{name}][{head}]", passes=passes, finished=finished,
                declined=declined, ms_median=statistics.median(walls),
                ms_max=max(walls))
    return lines


#: rule -> what takes its place in ``ops/quantile.py`` for ``seeds``:
#: the keys a bracket must span for each of its elements before a pass
#: pulls its ends in (``ENDS_KEYS``: 16 until the first reading of this
#: child, 64 since), the sample
RULES = {"as_kept": {}, "ends_16": {"ends_keys": 16},
         "ends_256": {"ends_keys": 256}, "sample_2x": {"sample": 1 << 18}}


def child_seeds(args) -> list:
    """The cell's uniform table on ``--seeds`` seeds under each of
    ``RULES``: passes, the passes among them that pulled the brackets'
    ends in, milliseconds: how often a seed's table is a slow one, and what
    it then costs (the cell's spread over seeds is this)."""
    phases = {}
    jax, quantile, mesh = _set_up(args, phases)
    import jax.numpy as jnp

    from flink_ml_tpu.observability.tracing import tracer

    make = table_makers(args.rows, args.dim)["uniform"]
    tracer.keep_recent = True
    kept = (quantile._wants_ends, quantile.SAMPLE_ROWS)
    lines = []
    for rule in args.rules:
        change = RULES[rule]
        keys = change.get("ends_keys", quantile.ENDS_KEYS)

        def wants(held, width, stuck, keys=keys):
            u32 = jnp.uint32
            return jnp.where(held <= quantile.PIVOTS + 1,
                             width // u32(keys) > held,
                             stuck & (width > u32(keys)))

        quantile._wants_ends = wants
        quantile.SAMPLE_ROWS = change.get("sample", kept[1])
        quantile.select_programs.cache_clear()
        quantile.finish_program.cache_clear()
        passes, ends, declined, walls = [], [], [], []
        for seed in range(args.seed + 100, args.seed + 100 + args.seeds):
            x = jax.block_until_ready(make(jax.random.key(seed)))
            quantile.select_on_device(x, PROBS)            # builds
            tracer.recent.clear()
            t = time.perf_counter()
            (_, made), closed = counted(
                lambda: quantile.select_on_device(x, PROBS))
            walls.append(round((time.perf_counter() - t) * 1e3, 2))
            passes.append(made)
            declined.append(closed[1])
            ends.append(sum(1 for r in tracer.recent
                            if r["name"] == "select.launch"
                            and r["attrs"].get("ends")))
            del x
        lines.append({"form": f"seeds[{rule}]", "passes": passes,
                      "ends_passes": ends, "declined": declined, "ms": walls,
                      "ms_median": statistics.median(walls),
                      "slow_seeds": sum(w > 1.05 * statistics.median(walls)
                                        for w in walls)})
        print(json.dumps(lines[-1]), flush=True)
    quantile._wants_ends, quantile.SAMPLE_ROWS = kept
    return lines


# -- the parent: never touches JAX ---------------------------------------------

def spawn(argv, child: str, head: int = 0):
    """One child, a fresh process; its JSON lines (the last one is its
    result) or an error record."""
    done = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), *argv,
         "--child", child, "--head", str(head)],
        capture_output=True, text=True, cwd=ROOT)
    lines = [json.loads(line) for line in done.stdout.splitlines()
             if line.startswith("{")]
    if done.returncode or not lines:
        lines.append({"form": f"{child}[{head}]", "rc": done.returncode,
                      "stderr": done.stderr[-2000:]})
    for line in lines:
        print(json.dumps(line), flush=True)
    return lines


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=12_000_000)
    parser.add_argument("--dim", type=int, default=100)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--repeats", type=int, default=7)
    parser.add_argument("--runs", type=int, default=3,
                        help="first-fit children a form")
    parser.add_argument("--tables", type=int, default=3)
    parser.add_argument("--blocks", default=[8192],
                        type=lambda v: [int(k) for k in v.split(",")],
                        help="blocks a shard for the finish[...] forms")
    parser.add_argument("--slices", default=[8],
                        type=lambda v: [int(k) for k in v.split(",")],
                        help="slices a turn of the finishing pass's loop")
    parser.add_argument("--heads", default=[3, 4],
                        type=lambda v: [int(k) for k in v.split(",")])
    parser.add_argument("--seeds", type=int, default=0,
                        help="uniform tables a rule of RULES (0: skip)")
    parser.add_argument("--rules", default=list(RULES),
                        type=lambda v: v.split(","))
    parser.add_argument("--skip-bisect32", action="store_true")
    parser.add_argument("--skip-steady", action="store_true")
    parser.add_argument("--skip-first", action="store_true")
    parser.add_argument("--allow-cpu", action="store_true")
    parser.add_argument("--child", default=None)
    parser.add_argument("--head", type=int, default=0)
    args = parser.parse_args(argv)

    if args.child is not None:
        result = {"first": lambda: child_first(args, args.head),
                  "phases": lambda: child_phases(args, args.head),
                  "pallas_import": lambda: child_pallas_import(args),
                  "steady": lambda: child_steady(args),
                  "seeds": lambda: child_seeds(args)}[args.child]()
        if isinstance(result, dict):
            print(json.dumps(result), flush=True)
        return 0

    passed = list(sys.argv[1:] if argv is None else argv)
    report = []
    if not args.skip_first:
        report += spawn(passed, "pallas_import")
        for head in args.heads:
            for _ in range(args.runs):
                report += spawn(passed, "first", head)
            report += spawn(passed, "phases", head)
    if not args.skip_steady:
        report += spawn(passed, "steady")
    if args.seeds:
        report += spawn(passed, "seeds")
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"select_forms_{args.rows}.json").write_text(
        json.dumps(report, indent=1))
    return 1 if any("rc" in line for line in report) else 0


if __name__ == "__main__":
    sys.exit(main())
