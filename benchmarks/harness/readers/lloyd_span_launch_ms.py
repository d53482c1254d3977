"""Per Lloyd fit: the program's ``lloyd.launch`` span (the rounds enqueued, no
wait; on the checkpointed and host-round paths the loop that drives them);
the median over the whole traced fits."""
from benchmarks.harness import lloyd_spans


def read(ctx):
    return lloyd_spans.read("launch")
