"""The four-chip pod cell at a CPU size: one cluster per device on its (4,1,1)
pod mesh, on four virtual devices in a child process (the device count is
fixed when JAX starts): a sound run is correct, and with the timed path
broken underneath (the cross-cluster all-gather left out, or a fault of
``bench/tests/faults.py`` planted) it is not."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests.faults import PLANTED

ROOT = Path(__file__).resolve().parents[2]

CHILD = """
import sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from bench import run
from bench.tests.faults import PLANTED
from bench.tests.tiny import SEED, TINY
from repro.core import hfl
fault = {fault!r}
if fault == "exchange_left_out":
    def own_only(x, axis_name, **kw):
        return jnp.broadcast_to(x[None], (4,) + x.shape)
    jax.lax.all_gather = own_only
elif fault in PLANTED:
    target, plant = PLANTED[fault]
    setattr(hfl, target, plant(getattr(hfl, target)))
run.run(["--workload", "olmo1b-c4pod.sparse-h2", "--seed", str(SEED),
         "--seconds", "0.2", "--trace", "0"], require_tpu=False,
        overrides=TINY)
"""


@pytest.mark.parametrize("fault", ["sound", "exchange_left_out",
                                   *PLANTED])
def test_pod_cell(fault):
    flags = os.environ.get("XLA_FLAGS", "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"{flags} --xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    code = CHILD.format(root=str(ROOT), fault=fault)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"] is (fault == "sound"), r["checks"]
