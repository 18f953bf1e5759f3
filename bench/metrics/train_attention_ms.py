"""Device self time of attention per execution of the cluster train step,
in ms: the ops under the scope ``attention`` (``flash_attention``), forward
and backward together; a part of ``train_fwd_ms`` and ``train_bwd_ms``,
not an addition to them (``bench/scopes.py``), averaged over chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "train", "attention", sublayer=True)
