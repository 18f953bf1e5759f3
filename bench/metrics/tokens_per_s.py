"""Training tokens of every step completed in the window over the window's
seconds, syncs included (host clock; the window ends on the device)."""


def read(ctx):
    w = ctx["window"]
    return w["steps"] * ctx["work"]["tokens_per_step"] / w["seconds"]
