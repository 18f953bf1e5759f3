"""Device self time of the scope ``sync.merge`` per execution of the consensus
sync program, in ms: scattering the payloads back to dense, the
error-feedback updates, the new reference and its adoption
(``bench/scopes.py``), averaged over chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "sync", "sync.merge")
