"""A process's cold start from inside: the program's cold spans, read after
the run as ``program_spans`` reads the ring.

The program records a span wherever code runs once a process, whether or not
anybody is looking (``flink_ml_tpu/observability/tracing.py``:
``Tracer.cold_span``, kept in the bounded list ``tracer.cold``, oldest
first): ``import:<module>`` around the package's imports and its heavy third
parties, the root ``first_fit`` around the first fit of a stage class,
``build:<program>`` around a program builder's body. Cold spans name only
cold spans as parents, so the list resolves every parent it names. While a
cold span is open the program's one ``jax.monitoring`` listener adds what jax
did to the innermost one's attributes: ``trace_s``, ``lower_s``,
``compile_s`` (each second counted once, a trace inside a trace not twice)
and ``cache_load_s``, which lies inside ``compile_s`` and is never added to
it.

Every reader here reads set-up's records only: those that closed no later
than the first ``first_fit`` root did, the cell's stage's first fit. The
records themselves decide that; the harness's clock is not asked. Five
numbers, in seconds:

- ``import``: the ``import:*`` spans with no ``import:*`` above them and
  inside no ``first_fit``, summed: every import the program made before
  its first fit;
- ``import_deps``: of those trees, the spans not named
  ``import:flink_ml_tpu*`` (the topmost such: jax, scipy, ...): the third
  parties' share of ``import``;
- ``first_fit``: the first ``first_fit`` root, call to return;
- ``first_fit_import``: the topmost ``import:*`` spans inside that root, so
  ``import`` and this one add and nothing is counted twice;
- ``first_fit_build``: ``trace_s + lower_s + compile_s`` over that root's
  tree.

A program whose tracer has no ``cold`` list (the parent of the PR that added
it): every reader returns None and the metric is left out. A program that
has the list and no span of a kind: 0.0, which is a reading.
"""

from __future__ import annotations

IMPORT = "import:"
OWN = "import:flink_ml_tpu"
ROOT = "first_fit"
BUILD_ATTRS = ("trace_s", "lower_s", "compile_s")


def cold():
    """The program's cold records, oldest first; None where the program
    keeps none."""
    try:
        from flink_ml_tpu.observability.tracing import tracer
    except ImportError:
        return None
    return getattr(tracer, "cold", None)


def _end_us(record) -> int:
    return record["ts_us"] + record["dur_us"]


def _ancestors(record, by_id):
    """The records above ``record`` in the list, nearest first."""
    seen = set()
    while record["parent"] in by_id and record["parent"] not in seen:
        seen.add(record["parent"])
        record = by_id[record["parent"]]
        yield record


def setup_records(records):
    """``(set-up's records, the first first_fit root or None)``."""
    records = list(records)
    by_id = {r["id"]: r for r in records}
    roots = [r for r in records if r["name"] == ROOT
             and not any(a["name"] == ROOT for a in _ancestors(r, by_id))]
    if not roots:
        return records, None
    first = min(roots, key=lambda r: r["ts_us"])
    return [r for r in records if _end_us(r) <= _end_us(first)], first


def seconds(records) -> dict:
    """The five numbers of one list of cold records."""
    kept, first = setup_records(records)
    by_id = {r["id"]: r for r in kept}
    above = {r["id"]: list(_ancestors(r, by_id)) for r in kept}

    def is_import(r):
        return r["name"].startswith(IMPORT)

    def is_dep(r):
        return is_import(r) and not r["name"].startswith(OWN)

    def under_first(r):
        return first is not None and any(a is first for a in above[r["id"]])

    before = [r for r in kept if is_import(r)
              and not any(a["name"] == ROOT for a in above[r["id"]])]
    out = {
        "import": sum(r["dur_us"] for r in before
                      if not any(is_import(a) for a in above[r["id"]])),
        "import_deps": sum(r["dur_us"] for r in before if is_dep(r)
                           and not any(is_dep(a) for a in above[r["id"]])),
        "first_fit": 0 if first is None else first["dur_us"],
        "first_fit_import": sum(
            r["dur_us"] for r in kept if is_import(r) and under_first(r)
            and not any(is_import(a) for a in above[r["id"]])),
    }
    out = {name: us / 1e6 for name, us in out.items()}
    out["first_fit_build"] = float(sum(
        r.get("attrs", {}).get(attr, 0.0) for r in kept
        if r is first or under_first(r) for attr in BUILD_ATTRS))
    return out


def read(name: str):
    """What a reader returns: one of the five, or None where the program
    keeps no cold records."""
    records = cold()
    return None if records is None else seconds(records)[name]
