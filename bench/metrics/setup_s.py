"""Seconds from the start of the harness process to the start of the window:
imports, device start, weights, HFL state, batches, compilation (or the
persistent cache's hits) and the warm-up periods."""


def read(ctx):
    return ctx["setup_s"]
