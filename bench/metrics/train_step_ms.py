"""Device time of one execution of the cluster train step program, in ms,
averaged over executions in the traced window and over chips."""
from bench.metrics import module_ms


def read(ctx):
    return module_ms(ctx, "train")
