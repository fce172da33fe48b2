"""Seconds from process start to the first timed operation: loading,
making data and weights, compiling and warming up."""


def read(rec, ctx):
    return rec.setup_s
