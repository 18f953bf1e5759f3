"""The harness end to end at a CPU size: it refuses a CPU device, a sound
program comes out correct, and the timed path broken underneath comes out
not correct."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench import run
from repro.core import hfl
from bench.tests.tiny import SEED, TINY

ROOT = Path(__file__).resolve().parents[2]
ONE_CHIP = ["olmo1b-c2.sparse-h2", "olmo1b-c2.dense-h2"]


def argv(cell, seconds=0.5):
    return ["--workload", cell, "--seed", str(SEED), "--seconds",
            str(seconds), "--trace", "0"]


def test_refuses_a_cpu_device(capsys):
    with pytest.raises(SystemExit) as e:
        run.run(argv(ONE_CHIP[0]))
    assert "not 'tpu'" in str(e.value)
    assert capsys.readouterr().out == ""


def test_command_exits_nonzero_without_a_result_off_the_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "bench/run.py", *argv(ONE_CHIP[1])],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_run_is_correct(cell, capsys):
    r = run.run(argv(cell), require_tpu=False, overrides=TINY)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "period_s_p95", "setup_s"}
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert list(json.loads(line))[-1] == "checks"


def _unchanged(make):
    def build(loss_fn, opt, sched):
        step = make(loss_fn, opt, sched)

        def broken(state, batch):
            _, losses = step(state, batch)
            return state._replace(step=state.step + 1), losses
        return broken
    return build


def _half_batch(make):
    def build(loss_fn, opt, sched):
        step = make(loss_fn, opt, sched)

        def broken(state, batch):
            half = jax.tree.map(lambda x: x[:, :x.shape[1] // 2], batch)
            return step(state, half)
        return broken
    return build


def _answer_altered(make):
    def build(plan):
        sync = make(plan)

        def broken(state):
            new = sync(state)
            w = dict(new.w_ref)
            w["embed"] = w["embed"] * 1.01
            return new._replace(w_ref=w)
        return broken
    return build


@pytest.mark.parametrize("cell", ONE_CHIP)
@pytest.mark.parametrize("target,fault", [
    ("make_cluster_train_step", _unchanged),
    ("make_cluster_train_step", _half_batch),
    ("make_sync", _answer_altered),
], ids=["state_unchanged", "half_batch", "answer_altered"])
def test_broken_path_is_not_correct(cell, target, fault, monkeypatch):
    monkeypatch.setattr(hfl, target, fault(getattr(hfl, target)))
    r = run.run(argv(cell, 0.2), require_tpu=False, overrides=TINY)
    assert not r["correct"], r["checks"]
