"""JAX profiling hooks: compile vs steady timing, HLO costs, live memory.

Three small tools, all host-side and backend-agnostic:

  * ``StepClock`` — splits wall time into first-step (trace + jit
    compile) and steady-state. The historical ``s/step`` figure divided
    total elapsed by step count, silently folding the compile stall into
    every step; ``compile_s`` and ``steady_s_per_step`` report the two
    separately.
  * ``program_costs`` — lowers/compiles a jitted callable and runs the
    trip-count-aware ``launch/hlo_cost`` analysis over the HLO text:
    flops, HBM bytes, collective bytes, a top-level launch count (entry
    instructions that actually dispatch work) and the program's device
    memory. Opt-in via ``ObsConfig.hlo_cost``.
  * ``live_bytes`` — current live device-array footprint (the heartbeat's
    peak-memory proxy; works on CPU where ``memory_stats`` is absent).
"""
from __future__ import annotations

import time

# entry-computation ops that dispatch no device work
_NO_LAUNCH_OPS = frozenset((
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id",
))


class StepClock:
    """Wall-clock accountant for a jitted step loop.

    Call ``step()`` after each completed step; the first completion marks
    the end of trace+compile. ``steady_s_per_step`` averages strictly
    post-compile steps (None until a second step lands).
    """

    def __init__(self):
        self.t0 = time.perf_counter()
        self._t_first = None
        self._steps = 0

    def step(self) -> None:
        self._steps += 1
        if self._t_first is None:
            self._t_first = time.perf_counter()

    @property
    def steps(self) -> int:
        return self._steps

    @property
    def compile_s(self):
        """First-step wall time (trace + compile + one execution)."""
        return (None if self._t_first is None
                else self._t_first - self.t0)

    @property
    def steady_s_per_step(self):
        if self._t_first is None or self._steps < 2:
            return None
        return (time.perf_counter() - self._t_first) / (self._steps - 1)

    def summary(self) -> dict:
        return {"steps": self._steps, "compile_s": self.compile_s,
                "steady_s_per_step": self.steady_s_per_step}


def program_costs(fn, *args, **kwargs) -> dict:
    """Lower + compile ``fn(*args)`` and analyze the HLO: trip-count-aware
    flops/bytes/collective bytes (``launch/hlo_cost``), the top-level
    launch count, and the compiled program's device memory
    (``memory_analysis()``: argument, output, aliased and temporary
    bytes). A jitted ``fn`` already called with these arguments is not
    compiled again."""
    from repro.launch.hlo_cost import HloCost

    compiled = fn.lower(*args, **kwargs).compile()
    hc = HloCost(compiled.as_text())
    cost = hc.entry_cost()
    launches = sum(1 for ins in hc.comps[hc.entry]
                   if ins.op not in _NO_LAUNCH_OPS)
    mem = compiled.memory_analysis()
    return {"flops": cost["flops"], "hbm_bytes": cost["bytes"],
            "collective_bytes": float(sum(cost["coll"].values())),
            "launches": launches,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes}


def live_bytes() -> float:
    """Bytes of live device arrays (CPU-safe peak-memory proxy)."""
    import jax

    return float(sum(a.nbytes for a in jax.live_arrays()))


def device_memory_stats() -> dict:
    """``memory_stats()`` of the default device; empty on a backend that
    keeps none (the CPU returns ``None``)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return dict(stats) if stats else {}
