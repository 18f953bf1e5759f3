"""Device idle time per HFL period under the program's own host spans, in
ms: the idle pieces the innermost open ``hfl.*`` span takes (``hfl.run``,
``hfl.step``, ``hfl.round``, ``hfl.batch``, ``hfl.train``, ``hfl.sync``),
the caller's callback (``hfl.on_step``) left out, over the ``hfl.run`` spans
(one per period) in the window, the spans first moved onto the device's
clock (``bench/scopes.py``), averaged over chips: the chip waiting on the
program's own host path."""
from bench.scopes import engine_idle_ms


def read(ctx):
    return engine_idle_ms(ctx)
