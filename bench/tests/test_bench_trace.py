"""The trace reduction on a recorded chip trace: two HFL periods of
``olmo1b-c2.sparse-h2`` on one v5e (``--trace 1``), gzipped."""
import importlib
import json
from pathlib import Path

import pytest

from bench import trace_reduce, work

ROOT = Path(__file__).resolve().parents[2]
TRACE = ROOT / "bench/testdata/sparse-h2.xplane.pb.gz"
Q = 238_026_757
CONF = json.loads((ROOT / "bench/configs/olmo1b-l2-c2.json").read_text())
PEAKS = json.loads((ROOT / "bench/peaks.json").read_text())["TPU v5 lite"]


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce_trace(TRACE)


@pytest.fixture(scope="module")
def ctx(red):
    return {
        "trace": red, "chips": 1, "traced_steps": 4, "peaks": PEAKS,
        "modules": {"train": "jit_train_step", "sync": "jit_flat_sync"},
        "work": {
            "tokens_per_step": 16384,
            "flops_per_token": work.train_flops_per_token(CONF, 1024),
            "sync_bytes": work.sync_bytes("sparse", q=Q, clusters=2,
                                          clusters_here=2, phi_up=0.9,
                                          exchange=False),
        },
    }


def test_busy_union_and_programs(red):
    assert red["window_ns"] == 11_231_015_577
    (dev,) = red["devices"]
    assert dev["name"] == "/device:TPU:0"
    assert dev["busy_ns"] == 11_191_092_745
    assert dev["modules"]["jit_train_step"] == {"ns": 1_182_632_347.0,
                                                "count": 4}
    assert dev["modules"]["jit_flat_sync"] == {"ns": 10_008_472_657.0,
                                               "count": 2}
    # the programs cover the busy time, with only the host's gaps between
    total = sum(m["ns"] for m in dev["modules"].values())
    assert dev["busy_ns"] <= total <= red["window_ns"]
    assert dev["collective_ns"] == 0.0  # one chip: no exchange


def test_gaps_are_attributed_to_host_spans(red):
    (dev,) = red["devices"]
    idle = red["window_ns"] - dev["busy_ns"]
    assert sum(ns for _, ns in dev["gaps"]) == pytest.approx(idle)
    by = {}
    for lbl, ns in dev["gaps"]:
        by[lbl] = by.get(lbl, 0.0) + ns
    # the host reads each step's loss before it dispatches the next step
    assert max(by, key=by.get) == "bench.loss_read"
    assert by["bench.loss_read"] == pytest.approx(35_814_819.0)
    top = trace_reduce.breakdown(red)
    assert top["idle_gaps"][0][0] == "bench.loss_read"
    assert top["device_ops"][0][0].startswith("jit_flat_sync/sort")
    assert len(top["device_ops"]) == len(top["idle_gaps"]) == 10


@pytest.mark.parametrize("name,lo,hi", [
    ("idle_share", 0.3, 0.4),
    ("train_step_ms", 295.0, 296.5),
    ("sync_ms", 5000.0, 5010.0),
    ("mfu", 4.0, 4.6),
    ("sync_roofline", 0.2, 0.25),
])
def test_readers_on_the_recorded_trace(ctx, name, lo, hi):
    v = importlib.import_module(f"bench.metrics.{name}").read(ctx)
    assert lo <= v <= hi
    if name in ("mfu", "sync_roofline"):
        assert v <= 100.0


def test_union_and_labels():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        [0, 3], [5, 8]]
    spans = [(0, 100, "bench.window"), (10, 50, "bench.sync"),
             (20, 30, "bench.loss_read")]
    assert trace_reduce._label(spans, 25) == "bench.loss_read"
    assert trace_reduce._label(spans, 40) == "bench.sync"
    assert trace_reduce._label(spans, 60) == "-"
    assert trace_reduce.is_collective("%all-gather-start.3 = (f32[4]) x")
    assert trace_reduce.is_collective("%all-reduce = f32[] all-reduce(x)")
    assert not trace_reduce.is_collective("%fusion.2 = f32[8] fusion(x)")
