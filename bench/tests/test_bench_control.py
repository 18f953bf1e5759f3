"""The control and the planted faults against the sound program, at a size a
CPU holds: each has to read well above what sound runs read on some number
(the rule the limits are set by, checked at this size)."""

import pytest

from bench import check, control
from bench.tests.tiny import SEED, TINY


@pytest.fixture(scope="module")
def readings():
    got = list(control.readings("olmo1b-c2.sparse-h2", [SEED, 7, 11], 1,
                                require_tpu=False, overrides=TINY))
    lower = {k: max(r["numbers"][k] for r in got if r["kind"] == "program")
             for k in check.NUMBERS}
    return lower, {r["kind"]: r["numbers"] for r in got
                   if r["kind"] != "program"}


def test_sound_runs_read_small(readings):
    lower, _ = readings
    assert lower["loss_gap"] < 1e-3
    assert max(lower.values()) < 0.1


@pytest.mark.parametrize("kind,factor", [
    ("control", 3.0), ("half_batch", 10.0), ("no_exchange", 10.0),
    ("answer_altered", 10.0)])
def test_control_and_faults_stand_out(readings, kind, factor):
    lower, other = readings
    assert any(other[kind][k] >= factor * lower[k] for k in check.NUMBERS), (
        lower, other[kind])
