"""Regenerate docs/operators.md from the live stage registry.

Run: python docs/gen_operator_docs.py
The table is generated, not hand-written, so it cannot drift from the code
(the docs analog of test_ml_lib_completeness.py).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

HEADER = """# Operator reference

All stages discoverable by the benchmark runner / save-load registry.
Generated by `docs/gen_operator_docs.py` — do not edit by hand.

Params are shown as `name (default)`; `*` marks a param that must be set
before use. Snake-case setters exist for every param
(`stage.set_max_iter(5)`, `stage.max_iter`).

"""
#: what the generated tables cannot say: model data whose columns are not
#: upstream's (docs/deviations.md has the reasons)
FOOTER = """## Model data that departs from upstream's

| Model | `get_model_data()` columns (one row) | Upstream |
|---|---|---|
| `NaiveBayesModel` | `theta` (L, d, V) float64 log-probabilities; `values` (d, V) each feature's values in ascending order, NaN where a feature has fewer than V (and `theta` holds the floor there); `piArray` (L,); `labels` (L,); `floors` (L, d) what a value unseen at fit time scores | `theta` as `Map<Double, Double>[][]`, `piArray`, `labels` |
"""


def main():
    from flink_ml_tpu.api.stage import AlgoOperator, Estimator, Model, Transformer
    from flink_ml_tpu.benchmark.runner import _stage_registry

    def kind(cls):
        if issubclass(cls, Estimator):
            return "Estimator"
        if issubclass(cls, Model):
            return "Model"
        if issubclass(cls, Transformer):
            return "Transformer"
        if issubclass(cls, AlgoOperator):
            return "AlgoOperator"
        return "Stage"

    by_module = {}
    for name, cls in sorted(_stage_registry().items()):
        area = cls.__module__.split(".models.")[-1].split(".")[0]
        by_module.setdefault(area, []).append((name, cls))

    lines = [HEADER]
    for area in sorted(by_module):
        lines.append(f"## {area}\n")
        lines.append("| Stage | Kind | Params |")
        lines.append("|---|---|---|")
        for name, cls in by_module[area]:
            params = []
            for p in cls().params():
                if p.default_value is not None:
                    mark = f"({p.default_value!r})"
                else:
                    # required iff None is not a legal value (same rule as
                    # WithParams._set_decoded)
                    try:
                        p.validate(None)
                        mark = "(unset)"
                    except ValueError:
                        mark = "*"
                params.append(f"`{p.name}` {mark}")
            lines.append(f"| `{name}` | {kind(cls)} | {', '.join(params)} |")
        lines.append("")
    lines.append(FOOTER)
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "operators.md")
    with open(out_path, "w") as f:
        f.write("\n".join(lines))
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
