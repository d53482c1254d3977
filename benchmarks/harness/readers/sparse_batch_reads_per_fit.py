"""Per sparse LR fit: the ``batch_reads`` attribute of the program's
``sgd.optimize`` span, the HBM reads of a round's batch window the fit made
(``ml.sgd batchReads``' part): one a round while both products read the
window made once on chip, 20 at the published 20 rounds; two a round past
the on-chip gate. The median over the whole traced sparse fits."""
from benchmarks.harness import sparse_spans


def read(ctx):
    return sparse_spans.read("batch_reads")
