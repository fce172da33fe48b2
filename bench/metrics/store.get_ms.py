"""Mean host milliseconds of one read: ``StoreReplica.get`` and the
record's row brought to the host."""


def read(rec, ctx):
    xs = rec.span_seconds("get")
    return sum(xs) / len(xs) * 1e3 if xs else None
