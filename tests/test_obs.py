"""Observability subsystem tests: registry snapshot determinism, span
nesting/monotonicity on both timelines, Chrome trace-event schema, exact
span/ledger bit conservation on real engine runs (lockstep + async,
broadcast included), bit-identical replay with tracing on vs off, the
zero-overhead disabled path, StepClock compile/steady split, the run
logger's JSONL stream, and ``tools/trace_summary.py --check``."""
import json
import subprocess
import sys
import tracemalloc
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import HFLConfig
from repro.core.hfl import hfl_init, make_cluster_train_step, make_sync_step
from repro.obs import (
    NULL_REGISTRY, NULL_TELEMETRY, MetricsRegistry, ObsConfig, RunLogger,
    SpanTracer, StepClock, Telemetry, VIRTUAL_PID, make_telemetry,
    to_jsonable, validate_trace,
)
from repro.obs.metrics import current_registry, set_registry, use_registry
from repro.optim import SGDM
from repro.sim.scenarios import apply_hfl_overrides, build_engine, get_scenario
from repro.wireless.latency import LatencyParams

TOOLS = Path(__file__).resolve().parents[1] / "tools"


@pytest.fixture(autouse=True)
def _ambient_registry_guard():
    """Telemetry() installs itself as the ambient registry; restore the
    module default after every test so tests stay order-independent."""
    prev = current_registry()
    yield
    set_registry(prev)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def _feed(reg, order):
    for link in order:
        reg.counter("bits").inc(100.0, link=link)
    reg.gauge("rate").set(2.5, fn="a")
    reg.histogram("lat").observe(np.array([1e-3, 2e-3, np.inf]))
    return reg


def test_registry_snapshot_deterministic():
    a = _feed(MetricsRegistry(), ["ul", "dl", "ul"]).snapshot()
    b = _feed(MetricsRegistry(), ["ul", "ul", "dl"]).snapshot()
    assert a == b
    assert a["bits"]["series"] == {"link=dl": 100.0, "link=ul": 200.0}
    assert list(a) == sorted(a)
    # non-finite observations are filtered, the rest aggregated
    h = a["lat"]["series"][""]
    assert h["count"] == 2 and h["sum"] == pytest.approx(3e-3)
    json.dumps(to_jsonable(a))  # plain-JSON by construction


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_null_registry_is_inert_shared():
    assert NULL_REGISTRY.enabled is False
    m = NULL_REGISTRY.counter("anything")
    assert m is NULL_REGISTRY.histogram("else")  # one shared no-op metric
    m.inc(5.0, link="ul")
    assert NULL_REGISTRY.snapshot() == {}


def test_ambient_registry_scoping():
    assert current_registry() is NULL_REGISTRY
    reg = MetricsRegistry()
    with use_registry(reg):
        assert current_registry() is reg
        current_registry().counter("c").inc()
    assert current_registry() is NULL_REGISTRY
    assert reg.counter("c").value() == 1.0


# ---------------------------------------------------------------------------
# Span tracer + schema
# ---------------------------------------------------------------------------


def test_span_nesting_and_dual_timeline():
    tr = SpanTracer()
    tr.span("round", track="cluster0", t0=0.0, dur=2.0)
    tr.span("iter", track="cluster0", t0=0.0, dur=1.0)  # nested, same t0
    tr.span("iter", track="cluster0", t0=1.0, dur=1.0)
    tr.instant("reprice", track="fleet", t=1.5)
    with tr.host_span("jit"):
        with tr.host_span("inner"):
            pass
    obj = tr.to_chrome()
    validate_trace(obj)
    pids = {e["pid"] for e in obj["traceEvents"] if e.get("ph") == "X"}
    assert pids == {1, 2}  # both clock domains present
    host = [e for e in obj["traceEvents"]
            if e.get("ph") == "X" and e["pid"] != VIRTUAL_PID]
    # nested host span closed first, and sits inside its parent
    inner, outer = host[0], host[1]
    assert inner["name"] == "inner" and outer["name"] == "jit"
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_validate_trace_rejects_bad_traces():
    with pytest.raises(ValueError):
        validate_trace({"events": []})
    tr = SpanTracer()
    tr.span("b", track="x", t0=5.0, dur=1.0)
    tr.span("a", track="x", t0=1.0, dur=1.0)  # virtual time ran backwards
    with pytest.raises(ValueError, match="backwards"):
        validate_trace(tr.to_chrome())
    with pytest.raises(ValueError, match="missing key"):
        validate_trace({"traceEvents": [{"ph": "X", "name": "n"}]})


def test_event_cap_drops_spans_but_conserves_bits():
    tr = SpanTracer(max_events=1)
    tr.link_span("ul", t0=0.0, dur=1.0, bits=8.0)
    tr.link_span("ul", t0=1.0, dur=1.0, bits=16.0)  # past the cap
    assert len(tr.events) == 1 and tr.dropped == 1
    assert tr.link_bits["ul"] == 24.0  # accumulation never stops
    meta = tr.to_chrome()["metadata"]
    assert meta["dropped_events"] == 1 and meta["link_bits"]["ul"] == 24.0


# ---------------------------------------------------------------------------
# Engine integration: real runs
# ---------------------------------------------------------------------------

D = 12


def _quad_loss(params, batch):
    return jnp.mean((params["w"][None, :] - batch) ** 2), {}


def _run(name, *, obs=None, accounting="analytic", steps=None, lp=None,
         hfl_over=()):
    scn = get_scenario(name)
    hfl = apply_hfl_overrides(scn, HFLConfig(
        num_clusters=3, mus_per_cluster=2, period=2,
        payload_accounting=accounting, **dict(hfl_over)))
    engine = build_engine(scn, hfl, seed=0, obs=obs,
                          lp=lp or LatencyParams(model_params=1e5))
    params = {"w": jnp.zeros((D,), jnp.float32)}
    opt = SGDM(momentum=0.0)
    state = hfl_init(params, opt, hfl)
    train = jax.jit(make_cluster_train_step(_quad_loss, opt, lambda t: 0.2))
    sync = jax.jit(make_sync_step(hfl, mesh=None))
    rng = np.random.default_rng(1)
    N, B = hfl.num_clusters, hfl.mus_per_cluster * 2

    def gen():
        while True:
            yield jnp.asarray(rng.normal(size=(N, B, D)).astype(np.float32))

    steps = steps if steps is not None else 2 * hfl.period
    state, trace = engine.run(state, train, sync, gen(), steps)
    return engine, state, trace


@pytest.mark.parametrize("name", ["stragglers", "async"])
def test_engine_trace_validates(name):
    engine, _, _ = _run(name, obs=ObsConfig())
    obj = engine.obs.tracer.to_chrome()
    validate_trace(obj)
    spans = [e for e in obj["traceEvents"] if e.get("ph") == "X"]
    assert any(e["pid"] == VIRTUAL_PID for e in spans)
    assert any(e["pid"] != VIRTUAL_PID for e in spans)  # host jit spans
    # round-trips through JSON intact
    validate_trace(json.loads(json.dumps(to_jsonable(obj))))


@pytest.mark.parametrize("name", ["stragglers", "async"])
def test_measured_conservation_is_bit_exact(name):
    """Per-link span bits must equal the PayloadLedger totals EXACTLY —
    same floats in the same order, broadcast legs included. The engine
    also self-checks this at teardown; assert it independently here."""
    engine, _, _ = _run(name, obs=ObsConfig(), accounting="measured")
    ledger, tracer = engine.ledger, engine.obs.tracer
    assert ledger is not None
    recorded = {l: b for l, b in ledger.bits.items() if b}
    assert recorded, "measured run recorded no payloads"
    for link, total in ledger.bits.items():
        assert tracer.link_bits.get(link, 0.0) == total  # bit-for-bit
    # the exported metadata carries the same books for trace_summary
    meta = tracer.to_chrome()["metadata"]
    assert meta["link_bits"] == tracer.link_bits
    # and a broadcast actually happened (repriced-broadcast path covered)
    names = {e["name"] for e in tracer.events}
    if name == "stragglers":
        assert "sync_bcast" in names


def test_replay_bit_identical_tracing_on_vs_off():
    """Instrumentation must be a pure observer: rows, meta AND the final
    model are bitwise unchanged by turning tracing on."""
    e1, s1, t1 = _run("stragglers", obs=ObsConfig(), accounting="measured")
    e2, s2, t2 = _run("stragglers", obs=None, accounting="measured")
    assert e1.obs.enabled and not e2.obs.enabled
    assert t1.rows == t2.rows
    assert t1.meta == t2.meta
    np.testing.assert_array_equal(np.asarray(s1.params["w"]),
                                  np.asarray(s2.params["w"]))


def test_engine_emits_registry_metrics():
    engine, _, _ = _run("stragglers", obs=ObsConfig(), accounting="measured")
    snap = engine.obs.registry.snapshot()
    assert snap["sim.train_launches"]["series"][""] > 0
    assert "link=sbs_ul" in snap["comm.bits"]["series"]
    assert "fn=hfl_latency" in snap["wireless.pricings"]["series"]


# ---------------------------------------------------------------------------
# Disabled path: zero overhead
# ---------------------------------------------------------------------------


def test_disabled_path_shares_null_singletons():
    assert make_telemetry(None) is NULL_TELEMETRY
    assert make_telemetry(ObsConfig(enabled=False)) is NULL_TELEMETRY
    # disabled telemetry keeps the profiler span and records nothing else
    assert isinstance(NULL_TELEMETRY.host_span("x"),
                      jax.profiler.TraceAnnotation)
    assert NULL_TELEMETRY.registry is NULL_REGISTRY
    engine, _, _ = _run("stragglers")  # no obs config at all
    assert engine.obs is NULL_TELEMETRY


def test_disabled_path_allocates_nothing():
    tele = NULL_TELEMETRY
    # warm up any lazy interning, then measure
    for _ in range(10):
        tele.tick()
        with tele.host_span("x"):
            pass
        NULL_REGISTRY.counter("c").inc(1.0, link="ul")
    tracemalloc.start()
    before, _ = tracemalloc.get_traced_memory()
    for _ in range(1000):
        tele.tick()
        with tele.host_span("x"):
            pass
        NULL_REGISTRY.counter("c").inc(1.0, link="ul")
    after, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # a handful of bytes of interpreter bookkeeping is fine; what must not
    # happen is per-call growth (1000 iterations -> each byte here is ~1KB)
    assert after - before < 1024


# ---------------------------------------------------------------------------
# StepClock, RunLogger, telemetry facade
# ---------------------------------------------------------------------------


def test_step_clock_splits_compile_from_steady():
    c = StepClock()
    assert c.steps == 0 and c.compile_s is None
    c.step()
    assert c.steps == 1 and c.compile_s >= 0.0
    assert c.steady_s_per_step is None  # one sample can't separate compile
    c.step()
    c.step()
    s = c.summary()
    assert s["steps"] == 3
    assert s["steady_s_per_step"] is not None and s["steady_s_per_step"] >= 0
    assert s["compile_s"] == c.compile_s


def test_run_logger_streams_jsonl(tmp_path, capsys):
    p = tmp_path / "run.jsonl"
    log = RunLogger(str(p))
    log.log("config", "[train] hello", arch="a", n=np.int64(3))
    log.log("metrics", None, metrics={"x": 1.0})  # JSONL-only event
    log.close()
    assert capsys.readouterr().out == "[train] hello\n"
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert [l["event"] for l in lines] == ["config", "metrics"]
    assert lines[0]["arch"] == "a" and lines[0]["n"] == 3  # np -> plain
    assert all("t_host_s" in l for l in lines)


def test_telemetry_conservation_check_raises_on_mismatch():
    tele = Telemetry(ObsConfig())
    tele.tracer.link_span("mu_ul", t0=0.0, dur=1.0, bits=8.0)

    class FakeLedger:
        bits = {"mu_ul": 16.0}

    with pytest.raises(AssertionError, match="conservation"):
        tele.check_conservation(FakeLedger())
    FakeLedger.bits = {"mu_ul": 8.0}
    tele.check_conservation(FakeLedger())  # exact match passes


# ---------------------------------------------------------------------------
# trace_summary tool
# ---------------------------------------------------------------------------


def _export(tmp_path, tamper=None):
    engine, _, trace = _run("stragglers", obs=ObsConfig(),
                            accounting="measured")
    path = tmp_path / "trace.json"
    engine.obs.export_chrome(
        str(path), metadata={"engine_meta": to_jsonable(trace.meta)})
    if tamper:
        obj = json.loads(path.read_text())
        tamper(obj)
        path.write_text(json.dumps(obj))
    return path


def _summary(*args):
    return subprocess.run(
        [sys.executable, str(TOOLS / "trace_summary.py"), *map(str, args)],
        capture_output=True, text=True)


def test_trace_summary_check_passes_on_real_trace(tmp_path):
    path = _export(tmp_path)
    r = _summary(path, "--check")
    assert r.returncode == 0, r.stderr
    assert "conservation holds" in r.stdout
    r = _summary(path)  # summary mode renders the breakdowns
    assert r.returncode == 0
    assert "per-link payloads" in r.stdout and "critical path" in r.stdout


def test_trace_summary_check_catches_bit_leak(tmp_path):
    def leak(obj):
        for ev in obj["traceEvents"]:
            if ev.get("cat") == "comm":
                ev["args"]["bits"] += 1.0  # one lost bit
                break

    r = _summary(_export(tmp_path, tamper=leak), "--check")
    assert r.returncode == 1
    assert "FAIL" in r.stderr
