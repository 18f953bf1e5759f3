"""One reader per metric, found by the metric's name in BENCHMARK.json:
``bench/metrics/<name>.py`` defines ``read(ctx)``, which returns the value
or ``None`` where it finds nothing to read. ``ctx`` is built by
``bench/run.py``."""


def module_ms(ctx, role):
    """Device ms per execution of the program playing ``role`` ("train" or
    "sync"), averaged over chips; None where the trace holds none."""
    name = ctx["modules"][role]
    per = []
    for d in ctx["trace"]["devices"]:
        rec = d["modules"].get(name)
        if not rec or not rec["count"]:
            return None
        per.append(rec["ns"] / rec["count"])
    return sum(per) / len(per) / 1e6
