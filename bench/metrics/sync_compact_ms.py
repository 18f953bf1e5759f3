"""Device self time of the scope ``sync.compact`` per execution of the
consensus sync program, in ms: gathering the kept values and indices into
the payloads, wire rounding included (``bench/scopes.py``), averaged over
chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "sync", "sync.compact")
