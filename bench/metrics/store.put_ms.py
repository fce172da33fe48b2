"""Mean host milliseconds of one ``StoreReplica.put``, ending when the
replica's result columns are ready on the device."""


def read(rec, ctx):
    xs = rec.span_seconds("put")
    return sum(xs) / len(xs) * 1e3 if xs else None
