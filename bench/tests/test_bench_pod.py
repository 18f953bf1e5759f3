"""The harness's mesh path at a CPU size: the sparse cell's traffic with one
cluster per device on a (4,1,1) pod mesh, the deployment a four-chip cell
states, on four virtual devices in a child process (the device count is
fixed when JAX starts): a sound run is correct, and with the cross-cluster
all-gather left out it is not."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

CHILD = """
import sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from bench import run
from bench.tests.tiny import POD, SEED, TINY
if {drop_exchange}:
    def own_only(x, axis_name, **kw):
        return jnp.broadcast_to(x[None], (4,) + x.shape)
    jax.lax.all_gather = own_only
run.run(["--workload", "olmo1b-c2.sparse-h2", "--seed", str(SEED),
         "--seconds", "0.2", "--trace", "0"], require_tpu=False,
        overrides={{**TINY, **POD}})
"""


@pytest.mark.parametrize("drop_exchange", [False, True],
                         ids=["sound", "exchange_left_out"])
def test_pod_cell(drop_exchange):
    flags = os.environ.get("XLA_FLAGS", "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"{flags} --xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    code = CHILD.format(root=str(ROOT), drop_exchange=drop_exchange)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"] is (not drop_exchange), r["checks"]
