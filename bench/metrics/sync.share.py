"""Share of the window outside the local-step spans: materializing the
outer parameters, optimizer set-up and copies per round, gossip and
convergence."""


def read(rec, ctx):
    steps = rec.span_seconds("local_step")
    if not steps:
        return None
    return 100.0 * (1.0 - min(sum(steps), rec.window_s) / rec.window_s)
