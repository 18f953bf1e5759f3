"""Share of the traced window in which no operation ran on the device, in %,
averaged over the cell's chips."""


def read(ctx):
    t = ctx["trace"]
    busy = sum(d["busy_ns"] for d in t["devices"]) / len(t["devices"])
    return 100.0 * (1.0 - busy / t["window_ns"])
