"""Median, over every update due in the window, of the time from its due
time to the first moment every replica holds a version at least as new
for its record."""

from bench.harness import percentile


def read(rec, ctx):
    return percentile(rec.samples.get("update_visible_ms", []), 50)
