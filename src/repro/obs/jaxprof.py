"""JAX profiling hooks: compile vs steady timing, live and device memory.

Small tools, all host-side and backend-agnostic:

  * ``StepClock`` — splits wall time into first-step (trace + jit
    compile) and steady-state. The historical ``s/step`` figure divided
    total elapsed by step count, silently folding the compile stall into
    every step; ``compile_s`` and ``steady_s_per_step`` report the two
    separately.
  * ``live_bytes`` — current live device-array footprint (the heartbeat's
    peak-memory proxy; works on CPU where ``memory_stats`` is absent).
  * ``device_memory_stats`` — the default device's runtime counters, with
    the peak as buffers plus the memory reserved for the programs'
    temporaries.
"""
from __future__ import annotations

import time

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


def live_bytes() -> float:
    """Bytes of live device arrays (CPU-safe peak-memory proxy)."""
    import jax

    return float(sum(a.nbytes for a in jax.live_arrays()))


def device_memory_stats() -> dict:
    """``memory_stats()`` of the default device, plus ``peak_bytes``: the
    peak of buffers in use (``peak_bytes_in_use``) plus the memory the
    runtime reserves apart for the programs' temporaries
    (``peak_bytes_reserved``; the TPU runtime keeps them there, so the
    buffer peak alone leaves them out). Empty on a backend that keeps no
    counters (the CPU returns ``None``)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    if not stats:
        return {}
    out = dict(stats)
    if "peak_bytes_in_use" in out:
        out["peak_bytes"] = (out["peak_bytes_in_use"]
                             + out.get("peak_bytes_reserved", 0))
    return out
