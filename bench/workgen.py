"""The general traffic generator: turns a traffic file's parameters and a
seed into the operations a run sends.

Every seed gets the same work in another order: the gaps between
arrivals are one set of Poisson gaps drawn from the traffic file's
``arrival_seed`` and scaled so each run's window holds exactly ``rate x
seconds`` operations; the read/update split is exact; the order of the
gaps, which operation arrives when, which replica serves it, and its
record and values are drawn from the run's seed.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

READ, UPDATE = 0, 1


def scrambled_zipf(rng, n_items: int, draws: int,
                   theta: float) -> np.ndarray:
    """``draws`` item indices from a bounded zipfian(``theta``) over
    ``n_items``, the hot ranks scattered over the item space by a fixed
    permutation (YCSB's scrambled zipfian)."""
    w = 1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(w) / w.sum()
    ranks = np.minimum(np.searchsorted(cdf, rng.random(draws)), n_items - 1)
    return np.random.default_rng(0).permutation(n_items)[ranks]


@dataclass
class OpenLoopOps:
    due: np.ndarray        # [n] due time in seconds from the window start
    kind: np.ndarray       # [n] READ or UPDATE
    record: np.ndarray     # [n] record index
    client: np.ndarray     # [n] which replica the client talks to
    values: np.ndarray     # [n, width] float32 (rows of reads are unused)

    def __len__(self) -> int:
        return int(self.due.size)


def open_loop(traffic: dict, n_records: int, n_clients: int, width: int,
              seed: int, seconds: float, stream: int = 0) -> OpenLoopOps:
    """Open-loop operations for ``seconds`` at the traffic file's rate.
    ``stream`` separates independent op streams of one seed (warm-up and
    window)."""
    n = int(round(float(traffic["rate_ops_s"]) * seconds))
    fixed = np.random.default_rng(int(traffic["arrival_seed"]))
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    rng = np.random.default_rng([seed, stream])
    gaps = rng.permutation(fixed.exponential(1.0, n))
    due = np.cumsum(gaps) * (seconds / gaps.sum()) if n else gaps
    due = np.concatenate([[0.0], due[:-1]]) if n else due
    n_upd = int(round(n * float(traffic["update_proportion"])))
    kind = rng.permutation(np.r_[np.full(n_upd, UPDATE, np.int8),
                                 np.full(n - n_upd, READ, np.int8)])
    client = rng.permutation(np.arange(n) % n_clients).astype(np.int32)
    if traffic["request_distribution"] != "zipfian":
        raise ValueError(f"unknown request distribution "
                         f"{traffic['request_distribution']!r}")
    record = scrambled_zipf(rng, n_records, n,
                            float(traffic["zipfian_constant"]))
    values = rng.standard_normal((n, width), dtype=np.float32)
    return OpenLoopOps(due, kind, record.astype(np.int64), client, values)


def token_batch(seed: int, job: int, pod: int, step: int, batch: int,
                seq: int, vocab: int) -> dict:
    """One training batch: ``batch`` rows of ``seq + 1`` uniform tokens,
    split into inputs and next-token labels. Every (job, pod, step) has
    rows of its own."""
    rng = np.random.default_rng([seed, job, pod, step])
    toks = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int64)
    toks = toks.astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
