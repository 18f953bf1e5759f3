"""Device self time of the scope ``sync.exchange`` per execution of the
consensus sync program, in ms: combining the clusters' payloads (a local or
dense mean, or the pod all-gather, whose collectives XLA leaves without
their scope and ``bench/scopes.py`` charges here), averaged over chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "sync", "sync.exchange")
