"""Device self time of the backward pass per execution of the cluster train
step, in ms: the ops under ``transpose(...)`` inside the scope
``train.grad``, forward work that ``jax.checkpoint`` recomputes there
included (``bench/scopes.py``), averaged over chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "train", "train.backward")
