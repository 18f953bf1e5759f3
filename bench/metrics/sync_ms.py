"""Device time of one execution of the consensus sync program, in ms,
averaged over executions in the traced window and over chips."""
from bench.metrics import module_ms


def read(ctx):
    return module_ms(ctx, "sync")
