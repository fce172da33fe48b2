"""How unevenly the held experts are loaded: for each local step in the
window and each MoE layer, the rows of the busiest held expert over the
mean held expert's rows (``moe_rows``, the step's program counter),
averaged over steps and layers. 1 is an even load; the busiest expert
sets the grouped matmuls' pace."""

import numpy as np


def read(rec, ctx):
    rows = rec.info.get("moe_rows")
    if rows is None or not len(rows):
        return None
    rows = np.asarray(rows, np.float64)
    mean = rows.mean(axis=-1)
    busy = mean > 0
    if not busy.any():
        return None
    return float((rows.max(axis=-1)[busy] / mean[busy]).mean())
