"""H-period orchestration: run intra-cluster steps, sync every H (Alg. 5).

The branch lives at the host level (two separately-jitted programs) rather
than a ``lax.cond`` inside one program: the sync program has a different
collective pattern (pod all-gathers) and keeping it separate lets the
dry-run lower/compile and roofline each phase independently — exactly how
the paper accounts latency (Γ^period = H intra-cluster iterations + one
Θ^U + Θ^D consensus).

``run_hfl`` is now a thin adapter over the event-driven simulation engine
(``repro.sim.engine.SimEngine``) in null-wireless mode: the same lockstep
schedule, with virtual time attached. Callers that want the wall-clock /
scenario machinery (stragglers, mobility, dropout, async) build a
``SimEngine`` via ``repro.sim.scenarios`` and call ``engine.run`` directly,
which also returns the trace.

The whole call is the profiler span ``hfl.run``; the engine opens the
per-step spans inside it (``hfl.step``, ``hfl.round``, ``hfl.batch``,
``hfl.train``, ``hfl.sync``, ``hfl.on_step``), so a running
``jax.profiler`` trace shows where the host spent each step.
"""
from __future__ import annotations

from typing import Callable, Iterable, Optional


def run_hfl(
    state,
    train_step: Callable,
    sync_step: Callable,
    batches: Iterable,
    period: int,
    num_steps: int,
    on_step: Optional[Callable] = None,
):
    """Drive ``num_steps`` iterations, syncing every ``period``.

    Call order per step is unchanged from the historical loop: train, then
    (at period boundaries) sync, then ``on_step(t, state, loss)``.

    ``period`` is the TIER-1 period (``hfl_cfg.tiers[1].period``). A
    depth > 2 ``sync_step`` (``core.hfl.HierSyncStep``) is detected by the
    engine, which threads its tier buffers and fires the higher boundaries
    on their own per-tier periods (``hier_fire_top``); with an async root
    tier the run switches to the mixed-discipline event loop.
    """
    from repro.obs.spans import profiler_span
    from repro.sim.engine import SimEngine

    with profiler_span("hfl.run"):
        engine = SimEngine(period=period, record=False)
        state, _trace = engine.run(
            state, train_step, sync_step, batches, num_steps, on_step=on_step
        )
    return state
