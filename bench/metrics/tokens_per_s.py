"""Tokens of every local step completed in the window over the window's
seconds; gossip, convergence and materializing the outer parameters
take their share of the window."""


def read(rec, ctx):
    if "tokens_in_window" not in rec.counts:
        return None
    return rec.counts["tokens_in_window"] / rec.window_s
