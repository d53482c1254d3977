"""Per sparse LR fit: the device time of the gradient, in ms: the self time
over the traced cycles of the operations the program names as its
gradient's, the ``gradient_ops`` attribute of its sparse fits' ``sgd.launch``
spans (``flink_ml_tpu/ops/sparse_window.py::gradient_ops``: the compiled
program's operations made under the ``sgd.sparse_gradient`` scope, the
scatter-add of the wide entries first among them). Keyed on the scope, not
on the compiler's numbering, which ``sparse_grad_device_ms`` reads and which
moves with the program. Operations past the trace's ten longest are not
counted. None where the ring names no such operation (a program without the
attribute, an older one) or the trace holds none of them."""
from benchmarks.harness import program_spans, sparse_spans


def names(records) -> set:
    """Every ``gradient_ops`` name the whole sparse fits' spans give."""
    found = set()
    for fit in program_spans.whole_fits(records):
        if sparse_spans.is_sparse(fit):
            for s in fit:
                found.update(s.get("attrs", {}).get("gradient_ops", ()))
    return found


def read(ctx, records=None):
    tr = ctx["trace"]
    if not tr or not tr["cycles"]:
        return None
    ops = names(program_spans.ring() if records is None else records)
    found = [s for name, s in tr["device_ops"] if name in ops]
    if not found:
        return None
    return 1e3 * sum(found) / tr["cycles"]
