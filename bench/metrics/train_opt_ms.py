"""Device self time of the optimizer update per execution of the cluster
train step, in ms: the ops under the scope ``train.optimizer``
(``bench/scopes.py``), averaged over chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "train", "train.optimizer")
