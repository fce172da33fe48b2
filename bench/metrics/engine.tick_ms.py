"""Mean host milliseconds of one gossip tick of all replicas
(``on_periodic`` and ``gc_deltas`` each, then the simulator's delivery),
from the benchmark's span around it."""


def read(rec, ctx):
    xs = rec.span_seconds("tick")
    return sum(xs) / len(xs) * 1e3 if xs else None
