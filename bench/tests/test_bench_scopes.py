"""The scope reduction (``bench/scopes.py``) on recorded chip traces, of a
program built before the scopes and of one built with them, and on small
made-up cases."""
import gzip
import importlib
from pathlib import Path

import pytest

from bench import scopes, trace_reduce

ROOT = Path(__file__).resolve().parents[2]
TRACE = ROOT / "bench/testdata/sparse-h2.xplane.pb.gz"
NEW = ["train_fwd_ms", "train_bwd_ms", "train_opt_ms", "train_attention_ms",
       "sync_select_ms", "sync_compact_ms", "sync_exchange_ms",
       "sync_merge_ms", "engine_idle_ms"]
MODULES = {"train": "jit_train_step", "sync": "jit_flat_sync"}


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce_trace(TRACE)


@pytest.fixture(scope="module")
def sco():
    return scopes.reduce_scopes(TRACE)


def test_self_times_add_up_to_the_busy_time(red, sco):
    (dev,), (old,) = sco["devices"], red["devices"]
    assert sco["window_ns"] == red["window_ns"]
    total = dev["tagged_ns"] + dev["untagged_ns"]
    assert total == pytest.approx(old["busy_ns"], rel=1e-4)
    # the op path is known for all but XLA's own copies
    assert dev["tagged_ns"] >= 0.98 * old["busy_ns"]
    for name, prog in dev["programs"].items():
        assert prog["count"] == old["modules"][name]["count"]
        assert sum(prog["layers"].values()) == pytest.approx(
            old["modules"][name]["ns"], rel=1e-3)


def test_idle_time_is_split_not_labelled(red, sco):
    (dev,), (old,) = sco["devices"], red["devices"]
    # a program without host spans leaves all of the idle time unclaimed;
    # trace_reduce rounds each op's times down to whole ns, this reader
    # keeps the trace's ps
    assert dev["idle"] == {"-": pytest.approx(red["window_ns"]
                                              - old["busy_ns"], rel=1e-4)}
    assert sco["runs"] == 0


def test_unscoped_train_step_splits_by_transform():
    by_transform = (("bwd", ("transpose",)), ("fwd", ("jvp",)))
    sco = scopes.reduce_scopes(TRACE, layers=by_transform)
    prog = sco["devices"][0]["programs"]["jit_train_step"]
    per = {k: v / prog["count"] / 1e6 for k, v in prog["layers"].items()}
    assert 65.0 <= per["fwd"] <= 67.0
    assert 209.0 <= per["bwd"] <= 211.0
    assert per["-"] < 25.0  # the update and XLA's copies


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_scopes(red, sco, name):
    ctx = {"trace": red, "scopes": sco, "modules": MODULES}
    assert importlib.import_module(f"bench.metrics.{name}").read(ctx) is None


def test_path_names_and_layers():
    bwd = ("jit(train_step)/vmap(train.grad)/transpose(jvp())/while/body/"
           "closed_call/checkpoint/attention/mul:")
    assert scopes.layer_of(bwd) == "train.backward"
    assert "attention" in scopes.path_names(bwd)
    fwd = "jit(train_step)/vmap(train.grad)/jvp()/while/body/dot_general:"
    assert scopes.layer_of(fwd) == "train.forward"
    assert scopes.layer_of(
        "jit(train_step)/vmap(train.optimizer)/mul:") == "train.optimizer"
    assert scopes.layer_of(
        "jit(flat_sync)/sync.select/top_k;jit(flat_sync)/sync.merge/x"
    ) == "sync.select"
    # the primitive never matches, nor does a name inside a longer one
    assert scopes.layer_of("jit(flat_sync)/sync.merge") == "-"
    assert scopes.layer_of("jit(flat_sync)/sync.selected/top_k") == "-"


@pytest.mark.parametrize("op_name,event,layer", [
    # XLA's rewrite of the pod all-gather keeps no op path
    (None, "%all-reduce.1 = f32[68367156]{0} all-reduce(%dus.1)",
     "sync.exchange"),
    (None, "all-gather-start.2", "sync.exchange"),
    (None, "%copy.3 = f32[8]{0} copy(%p)", "-"),
    (None, "%all-reduce-scatter-fusion = f32[8]{0} fusion(%p)", "-"),
    ("jit(sparse_sync)/sync.merge/add:", "all-reduce.2", "sync.merge"),
])
def test_an_untagged_collective_is_the_exchange(op_name, event, layer):
    assert scopes.op_layer(op_name, event) == layer


def test_self_times_and_idle_split():
    ops = [(0, 100, "while"), (10, 30, "a"), (30, 60, "b"), (40, 50, "c"),
           (120, 150, "d")]
    got = {p: ps for ps, p in scopes._self_times(ops, 0, 140)}
    assert got == {"while": 50, "a": 20, "b": 20, "c": 10, "d": 20}
    spans = [(90, 200, "hfl.step"), (95, 130, "hfl.on_step")]
    idle = scopes._split_idle([(0, 100), (120, 150)], spans, 0, 160)
    assert idle == {"hfl.on_step": 20, "hfl.step": 10}


def test_spans_move_onto_the_device_clock():
    ms = 1_000_000_000  # ps
    spans = [(0, 1 * ms, "hfl.train"), (300 * ms, 301 * ms, "hfl.train"),
             (301 * ms, 302 * ms, "hfl.sync")]
    kinds = {"jit_train_step": "train", "jit_sync": "sync"}
    # the second train program starts 0.4 ms before its launch span; the
    # sync, queued behind it, starts long after its own
    mods = [(ms // 2, 290 * ms, "jit_train_step"),
            (299_600_000_000, 590 * ms, "jit_train_step"),
            (590 * ms + 10_000_000, 600 * ms, "jit_sync")]
    assert scopes._clock_offset(spans, mods, kinds) == 400_000_000
    # no program before its launch: the spans stay where they are
    assert scopes._clock_offset(spans, mods[:1], kinds) == 0
    assert scopes._clock_offset(spans, mods, {}) == 0


def test_the_scoped_trace_moves_its_spans(scoped_ctx):
    (dev,) = scoped_ctx["scopes"]["devices"]
    assert dev["clock_offset_ns"] == pytest.approx(528_600, abs=100)


def test_readers_find_the_runs_trace(red, tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACE_ROOT", tmp_path)
    run = tmp_path / "cell" / "plugins" / "profile" / "1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(gzip.decompress(TRACE.read_bytes()))
    ctx = {"trace": red, "modules": MODULES}
    assert scopes.of(ctx)["window_ns"] == red["window_ns"]
    assert ctx["scopes"] is scopes.of(ctx)  # read once per run
    # a trace of another window is not this run's
    other = {"trace": {**red, "window_ns": red["window_ns"] + 5e3}}
    assert scopes.of(other) is None


# a --trace 1 run of olmo1b-c2.sparse-h2 on one v5e with the scopes and
# spans in the program: two HFL periods
SCOPED = ROOT / "bench/testdata/sparse-h2-scoped.xplane.pb.gz"


@pytest.fixture(scope="module")
def scoped_ctx():
    red = trace_reduce.reduce_trace(SCOPED)
    return {"trace": red, "scopes": scopes.reduce_scopes(SCOPED),
            "modules": MODULES}


@pytest.mark.parametrize("name,lo,hi", [
    ("train_fwd_ms", 66.0, 66.2),
    ("train_bwd_ms", 209.8, 210.0),
    ("train_opt_ms", 12.2, 12.4),
    ("train_attention_ms", 88.6, 88.9),
    ("sync_select_ms", 2880.0, 2882.0),
    ("sync_compact_ms", 1045.0, 1047.0),
    # the local mean fuses into the downlink drift, which is sync.select
    ("sync_exchange_ms", 0.0, 0.0),
    ("sync_merge_ms", 830.0, 832.0),
    # the trace's spans sit 0.53 ms late against the device's ops
    ("engine_idle_ms", 0.90, 0.93),
])
def test_readers_on_the_scoped_trace(scoped_ctx, name, lo, hi):
    v = importlib.import_module(f"bench.metrics.{name}").read(scoped_ctx)
    assert lo <= v <= hi


@pytest.mark.parametrize("role,layers", [
    ("train", ["train_fwd_ms", "train_bwd_ms", "train_opt_ms"]),
    ("sync", ["sync_select_ms", "sync_compact_ms", "sync_exchange_ms",
              "sync_merge_ms"]),
])
def test_layers_and_their_rest_add_up_to_the_program(scoped_ctx, role,
                                                     layers):
    read = lambda n: importlib.import_module(f"bench.metrics.{n}").read(
        scoped_ctx)
    (dev,) = scoped_ctx["scopes"]["devices"]
    prog = dev["programs"][MODULES[role]]
    rest = prog["layers"]["-"] / prog["count"] / 1e6
    whole = read(f"{role}_step_ms" if role == "train" else "sync_ms")
    assert sum(read(n) for n in layers) + rest == pytest.approx(whole,
                                                                rel=1e-3)
    assert rest < 0.03 * whole  # XLA's own copies and hoisted constants


def test_idle_splits_into_engine_callback_and_harness(scoped_ctx):
    from bench.metrics import engine_idle_ms

    sco = scoped_ctx["scopes"]
    (dev,) = sco["devices"]
    (old,) = scoped_ctx["trace"]["devices"]
    assert sco["runs"] == 2
    engine = engine_idle_ms.read(scoped_ctx) * 1e6 * sco["runs"]
    parts = engine + dev["idle"]["hfl.on_step"] + dev["idle"]["-"]
    assert parts == pytest.approx(sum(dev["idle"].values()), rel=1e-9)
    assert parts == pytest.approx(scoped_ctx["trace"]["window_ns"]
                                  - old["busy_ns"], rel=1e-4)


def test_the_program_spans_are_in_the_host_plane():
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(
        gzip.decompress(SCOPED.read_bytes()))
    names = {ev.name for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events}
    assert {"hfl.run", "hfl.step", "hfl.round", "hfl.batch", "hfl.train",
            "hfl.sync", "hfl.on_step"} <= names


# a --trace 1 run of olmo1b-c4pod.sparse-h2 on four v5e chips: two HFL
# periods, the pod sync's all-gathers rewritten into untagged all-reduces
POD = ROOT / "bench/testdata/c4pod-sparse-h2.xplane.pb.gz"
POD_MODULES = {"train": "jit_train_step", "sync": "jit_sparse_sync"}


@pytest.fixture(scope="module")
def pod_ctx():
    red = trace_reduce.reduce_trace(POD)
    return {"trace": red, "scopes": scopes.reduce_scopes(POD),
            "modules": POD_MODULES}


def test_pod_exchange_is_the_collectives_time(pod_ctx):
    from bench.metrics import sync_exchange_ms

    devs = pod_ctx["trace"]["devices"]
    assert len(devs) == 4
    per_sync = [d["collective_ns"] / d["modules"]["jit_sparse_sync"]["count"]
                for d in devs]
    coll_ms = sum(per_sync) / len(per_sync) / 1e6
    assert coll_ms > 10.0
    assert sync_exchange_ms.read(pod_ctx) == pytest.approx(coll_ms, rel=0.05)


@pytest.mark.parametrize("name,lo,hi", [
    ("sync_ms", 7270.0, 7274.0),
    ("sync_select_ms", 3499.0, 3502.0),
    ("sync_compact_ms", 910.0, 913.0),
    ("sync_exchange_ms", 20.5, 21.7),
    ("sync_merge_ms", 2773.0, 2776.0),
    ("train_step_ms", 233.0, 233.6),
])
def test_readers_on_the_pod_trace(pod_ctx, name, lo, hi):
    v = importlib.import_module(f"bench.metrics.{name}").read(pod_ctx)
    assert lo <= v <= hi


def test_pod_sync_layers_and_their_rest_add_up(pod_ctx):
    read = lambda n: importlib.import_module(f"bench.metrics.{n}").read(
        pod_ctx)
    rests = [d["programs"]["jit_sparse_sync"]["layers"]["-"]
             / d["programs"]["jit_sparse_sync"]["count"] / 1e6
             for d in pod_ctx["scopes"]["devices"]]
    rest = sum(rests) / len(rests)
    layers = ["sync_select_ms", "sync_compact_ms", "sync_exchange_ms",
              "sync_merge_ms"]
    assert sum(read(n) for n in layers) + rest == pytest.approx(
        read("sync_ms"), rel=1e-3)
