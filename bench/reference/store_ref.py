"""Plain reference of the versioned keyed store: numpy columns, one row
per record, where the highest version written to a row wins (last
writer wins by version, the store's join). Independent of the program.
"""

from __future__ import annotations

import numpy as np


def apply_updates(base_vals: np.ndarray, base_vers: np.ndarray,
                  rows: np.ndarray, vers: np.ndarray,
                  vals: np.ndarray):
    """The converged store: ``base`` with every update ``(rows[i],
    vers[i], vals[i])`` joined in. Returns new ``(vals, vers)``."""
    out_vals, out_vers = base_vals.copy(), base_vers.copy()
    if rows.size:
        order = np.lexsort((vers, rows))          # by row, version ascending
        r, v = rows[order], vers[order]
        last = np.r_[r[1:] != r[:-1], True]       # highest version per row
        r, v, idx = r[last], v[last], order[last]
        win = v > out_vers[r]
        out_vals[r[win]] = vals[idx[win]]
        out_vers[r[win]] = v[win]
    return out_vals, out_vers


def value_of(base_vals: np.ndarray, base_vers: np.ndarray,
             written: dict, row: int, version: int):
    """The value the store holds for ``row`` at ``version``: the base row
    at the base version, else the update written with that version
    (``written[(row, version)]``); None when no such write exists."""
    if version == base_vers[row]:
        return base_vals[row]
    return written.get((row, version))
