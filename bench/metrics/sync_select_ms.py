"""Device self time of the scope ``sync.select`` per execution of the consensus
sync program, in ms: building the drift vectors and choosing Omega (the
sorts of ``lax.top_k``, or a threshold and its mask) (``bench/scopes.py``),
averaged over chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "sync", "sync.select")
