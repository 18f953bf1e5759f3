"""Codec + measured-accounting tests: bit-exact round trips, stream-length
invariants (closed-form == jax-traced == 8·len(encode)), analytic-vs-
measured agreement, the bitpack Pallas kernel, the sync probe's fidelity to
the real sync payloads, and the engine's measured pricing."""
import warnings

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.comm.accounting import PayloadLedger, access_bits, make_sync_probe
from repro.comm.codecs import CODECS, get_codec
from repro.configs.base import HFLConfig, SimConfig
from repro.core import sparsify as sp
from repro.core.hfl import (
    _wire_round, hfl_init, make_cluster_train_step, make_sync_step,
)
from repro.optim import SGDM
from repro.sim.devices import DeviceFleet
from repro.sim.engine import SimEngine, init_dl_error, make_async_sync_step
from repro.wireless.latency import LatencyParams
from repro.wireless.topology import HCNTopology

CODEC_NAMES = sorted(CODECS)
SPARSE_NAMES = [n for n in CODEC_NAMES
                if n != "best" and not n.startswith("dense")]


def _payload(rng, size, k):
    idx = np.sort(rng.choice(size, k, replace=False)).astype(np.int32)
    vals = rng.normal(size=k).astype(np.float32)
    # exercise exact zeros too (a kept value may be zero after padding)
    if k > 2:
        vals[0] = 0.0
    return vals, idx


# ---------------------------------------------------------------------------
# Stream invariants (example-based: always run)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", CODEC_NAMES)
def test_measure_equals_stream_length(name):
    codec = get_codec(name)
    rng = np.random.default_rng(0)
    for size, k in [(1, 1), (13, 5), (300, 1), (300, 299), (4096, 41)]:
        v, i = _payload(rng, size, k)
        blob = codec.encode(v, i, size)
        assert codec.measure_bits(v, i, size) == 8 * len(blob)
        assert int(codec.measure_bits_jax(jnp.asarray(v), jnp.asarray(i),
                                          size)) == 8 * len(blob)


@pytest.mark.parametrize("name", SPARSE_NAMES)
def test_sparse_roundtrip_bit_exact(name):
    codec = get_codec(name)
    rng = np.random.default_rng(1)
    for size, k in [(7, 3), (256, 17), (2048, 2047)]:
        v, i = _payload(rng, size, k)
        dv, di = codec.decode(codec.encode(v, i, size), size)
        np.testing.assert_array_equal(di, i)
        np.testing.assert_array_equal(dv, codec.wire_values(v))


@pytest.mark.parametrize("name", ["dense-f32", "dense-bf16"])
def test_dense_roundtrip(name):
    codec = get_codec(name)
    rng = np.random.default_rng(2)
    v, i = _payload(rng, 500, 99)
    dense = np.zeros(500, np.float32)
    np.add.at(dense, i, v)
    out = codec.decode_dense(codec.encode(v, i, 500), 500)
    np.testing.assert_array_equal(out, codec.wire_values(dense))


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------


@st.composite
def payloads(draw):
    size = draw(st.integers(1, 300))
    k = draw(st.integers(1, size))
    idx = draw(st.sets(st.integers(0, size - 1), min_size=k, max_size=k))
    # hypothesis wants width-32 bounds that are exact float32 values
    bound = float(np.float32(1e20))
    vals = draw(st.lists(
        st.floats(-bound, bound, allow_nan=False, allow_infinity=False,
                  width=32),
        min_size=k, max_size=k,
    ))
    return (np.asarray(vals, np.float32),
            np.asarray(sorted(idx), np.int32), size)


@settings(max_examples=25, deadline=None)
@given(payloads(), st.sampled_from(CODEC_NAMES))
def test_property_roundtrip_and_measure(payload, name):
    """decode(encode(x)) == x bit-exact (modulo the codec's declared wire
    rounding) and measured bits == len(encoded stream) for EVERY codec."""
    v, i, size = payload
    codec = get_codec(name)
    blob = codec.encode(v, i, size)
    assert codec.measure_bits(v, i, size) == 8 * len(blob)
    assert int(codec.measure_bits_jax(jnp.asarray(v), jnp.asarray(i),
                                      size)) == 8 * len(blob)
    dv, di = codec.decode(blob, size)
    if name in SPARSE_NAMES:
        np.testing.assert_array_equal(di, i)
        np.testing.assert_array_equal(dv, codec.wire_values(v))
    else:
        dense = np.zeros(size, np.float32)
        np.add.at(dense, i, v)
        if name.startswith("dense"):
            np.testing.assert_array_equal(
                codec.decode_dense(blob, size), codec.wire_values(dense))
        else:  # best: the winner's wire semantics; f32 winners are exact
            assert codec.decode_dense(blob, size).shape == (size,)


# ---------------------------------------------------------------------------
# Analytic-vs-measured agreement
# ---------------------------------------------------------------------------


def test_dense_f32_matches_analytic_payload_exactly():
    """The paper's accounting at φ=0 IS dense-f32: bit-for-bit equal."""
    Q = 11_217
    lp = LatencyParams(model_params=float(Q), bits_per_param=32.0)
    codec = get_codec("dense-f32")
    v = np.ones(Q, np.float32)
    i = np.arange(Q, dtype=np.int32)
    assert codec.measure_bits(v, i, Q) == lp.payload(0.0)
    assert access_bits("dense-f32", Q, 0.0) == lp.payload(0.0)


def test_sparse_codec_beats_analytic_at_high_phi():
    """At φ=0.99 the idealized 32·(1-φ) charges no indices at all; a real
    codec must pay them — and the q8 delta streams STILL come in under."""
    size = 1 << 16
    x = jax.random.normal(jax.random.PRNGKey(0), (size,))
    vals, idx = sp.pack_phi(x, 0.99)
    v, i = np.asarray(vals), np.asarray(idx)
    analytic = 32.0 * (1.0 - 0.99)
    assert get_codec("delta-varint-q8").measure_bits(v, i, size) / size < analytic
    assert get_codec("best").measure_bits(v, i, size) / size < analytic


def test_best_codec_picks_the_minimum():
    rng = np.random.default_rng(3)
    best = get_codec("best")
    for size, k in [(64, 60), (4096, 40)]:
        v, i = _payload(rng, size, k)
        concrete = min(
            get_codec(n).measure_bits(v, i, size)
            for n in CODEC_NAMES if n != "best"
        )
        assert best.measure_bits(v, i, size) == 8 + concrete
        winner, bits = best.choose(v, i, size)
        assert bits == concrete
    # dense-ish payload -> a dense/bitmap format; sparse -> a delta stream
    v, i = _payload(rng, 4096, 40)
    assert best.choose(v, i, 4096)[0].name.startswith("delta")


# ---------------------------------------------------------------------------
# Bitpack Pallas kernel (interpret mode)
# ---------------------------------------------------------------------------


def test_bitpack_kernel_matches_packbits():
    from repro.kernels.bitpack import ops as bp
    from repro.kernels.bitpack.ref import bitpack_ref

    rng = np.random.default_rng(4)
    for n in (5, 300, 4096):
        mask = (rng.random(n) < 0.3).astype(np.float32)
        assert bp.bitpack_bytes(mask) == bitpack_ref(mask).tobytes()


def test_bitmap_codec_pallas_path_identical():
    rng = np.random.default_rng(5)
    codec = get_codec("bitmap")
    v, i = _payload(rng, 3000, 123)
    np.testing.assert_array_equal(
        codec.encode(v, i, 3000), codec.encode(v, i, 3000, impl="pallas"))


def test_bitmap_payload_compaction():
    from repro.kernels.bitpack import ops as bp

    rng = np.random.default_rng(6)
    x = rng.normal(size=1000).astype(np.float32)
    x[rng.random(1000) < 0.9] = 0.0
    packed, vals = bp.bitmap_payload(x)
    np.testing.assert_array_equal(vals, x[x != 0.0])
    assert packed == np.packbits(x != 0.0, bitorder="little").tobytes()


# ---------------------------------------------------------------------------
# q8 wire format through the sync's error feedback
# ---------------------------------------------------------------------------


def test_wire_round_q8_matches_codec():
    rng = np.random.default_rng(7)
    x = rng.normal(size=257).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(_wire_round(jnp.asarray(x), "q8")),
        get_codec("bitmap-q8").wire_values(x),
    )


def test_q8_sync_feeds_error_back():
    """quantized_sparse + wire_format=q8: the eps buffer must hold the
    EXACT selection+quantization residual (drift conservation)."""
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1, period=1,
                    sync_mode="quantized_sparse", wire_format="q8",
                    phi_sbs_ul=0.5, phi_mbs_dl=0.0, beta_s=1.0, beta_m=0.0)
    params = {"w": jnp.zeros((16,), jnp.float32)}
    state = hfl_init(params, SGDM(momentum=0.0), hfl)
    drift = jnp.arange(1.0, 17.0)
    state = state._replace(
        params={"w": state.params["w"] + drift[None, :]})
    out = make_sync_step(hfl, mesh=None)(state)
    # per cluster: s = drift; sent = q8(top-half of s); eps = s - sent
    vals, idx = sp.pack_phi(drift, 0.5)
    sent = np.zeros(16, np.float32)
    sent[np.asarray(idx)] = get_codec("bitmap-q8").wire_values(
        np.asarray(vals))
    np.testing.assert_allclose(
        np.asarray(out.eps["w"][0]), np.asarray(drift) - sent, rtol=1e-6)


# ---------------------------------------------------------------------------
# Probe fidelity + ledger + engine measured pricing
# ---------------------------------------------------------------------------

D = 48


def _quad_loss(params, batch):
    return jnp.mean((params["w"][None, :] - batch) ** 2), {}


def _tiny_state(hfl, drift_seed=0):
    params = {"w": jnp.zeros((D,), jnp.float32)}
    state = hfl_init(params, SGDM(momentum=0.0), hfl)
    rng = np.random.default_rng(drift_seed)
    drift = jnp.asarray(rng.normal(size=(hfl.num_clusters, D)).astype(np.float32))
    return state._replace(params={"w": state.params["w"] + drift})


def test_sync_probe_measures_the_real_payloads():
    hfl = HFLConfig(num_clusters=3, mus_per_cluster=1, period=1,
                    sync_mode="sparse", phi_sbs_ul=0.75, phi_mbs_dl=0.5,
                    beta_s=0.4, beta_m=0.3)
    codec = get_codec("delta-varint")
    state = _tiny_state(hfl)
    ul_bits, dl_bits = make_sync_probe(hfl, codec)(state)
    # recompute the payloads the flat sync sends, on the host
    wn = np.asarray(state.params["w"], np.float32)
    wref = np.zeros(D, np.float32)
    sents = []
    for n in range(3):
        s = wn[n] - wref
        vals, idx = sp.pack_phi(jnp.asarray(s), 0.75)
        assert int(ul_bits[n]) == codec.measure_bits(
            np.asarray(vals), np.asarray(idx), D)
        sents.append(np.asarray(sp.unpack_topk(vals, idx, D)))
    delta = np.sum(sents, axis=0) / 3
    dvals, didx = sp.pack_phi(jnp.asarray(delta), 0.5)
    assert int(dl_bits) == codec.measure_bits(
        np.asarray(dvals), np.asarray(didx), D)


def test_ledger_links_and_totals():
    led = PayloadLedger(codec="bitmap", size=100)
    led.record("mu_ul", 800, events=4)
    led.record("sbs_ul", 300)
    led.record("mbs_dl", 200)
    with pytest.raises(KeyError):
        led.record("nope", 1)
    assert led.bits_access_total == 800
    assert led.bits_fronthaul_total == 500
    s = led.summary()
    assert s["events_mu_ul"] == 4 and s["bits_sbs_ul"] == 300
    assert s["bits_per_param_mean"] == pytest.approx(1300 / (6 * 100))


def test_link_graph_depth2_keys_byte_identical_to_legacy():
    """Back-compat contract of the tier-boundary link graph: a default
    (depth-2) ledger keeps the EXACT historical four link names — its
    snapshot keys are byte-identical to the pre-refactor ones — and
    ``link_names(2)`` IS the legacy LINKS tuple."""
    from repro.comm.accounting import LINKS, boundary_links, link_names

    assert link_names(2) == LINKS == ("mu_ul", "sbs_dl", "sbs_ul", "mbs_dl")
    assert boundary_links(0) == ("mu_ul", "sbs_dl")
    assert boundary_links(1) == ("sbs_ul", "mbs_dl")
    assert boundary_links(3) == ("t3_ul", "t3_dl")
    led = PayloadLedger(codec="bitmap", size=100)
    assert led.links == LINKS
    assert sorted(led.summary()) == sorted(
        [f"bits_{l}" for l in LINKS] + [f"events_{l}" for l in LINKS]
        + ["codec", "payload_size"])
    # boundary 1 keeps the historic fronthaul names at ANY depth, so
    # depth-2 metric/trace keys survive a deepened tree unchanged
    assert link_names(4)[:6] == LINKS + ("t2_ul", "t2_dl")


def test_link_graph_depth3_ledger_routes_boundaries():
    from repro.comm.accounting import link_names

    led = PayloadLedger(codec="bitmap", size=100, links=link_names(3))
    led.record("mu_ul", 800, events=4)
    led.record("sbs_ul", 300)
    led.record("t2_ul", 70)
    led.record("t2_dl", 30)
    # access = boundary 0; fronthaul = every boundary above it
    assert led.bits_access_total == 800
    assert led.bits_fronthaul_total == 400
    s = led.summary()
    assert s["bits_t2_ul"] == 70 and s["events_t2_ul"] == 1


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 5), st.data())
def test_property_per_tier_link_sums_equal_totals(depth, data):
    """Hypothesis property of the link graph: for any depth and any
    recorded traffic, the per-tier link sums reproduce the access and
    fronthaul totals exactly (no bits leak between tier boundaries)."""
    from repro.comm.accounting import ACCESS_LINKS, link_names

    links = link_names(depth)
    led = PayloadLedger(codec="bitmap", size=100, links=links)
    for link in links:
        n = data.draw(st.integers(0, 4), label=f"events_{link}")
        for _ in range(n):
            led.record(link, data.draw(
                st.floats(0, 1e12, allow_nan=False), label=link))
    s = led.summary()
    assert led.bits_access_total == sum(
        s[f"bits_{l}"] for l in ACCESS_LINKS)
    assert led.bits_fronthaul_total == sum(
        s[f"bits_{l}"] for l in links if l not in ACCESS_LINKS)
    assert led.bits_access_total + led.bits_fronthaul_total \
        == pytest.approx(sum(s[f"bits_{l}"] for l in links))


def _measured_engine(discipline="lockstep", codec="delta-varint", **hfl_kw):
    kw = dict(num_clusters=3, mus_per_cluster=2, period=2,
              sync_mode="sparse", payload_accounting="measured", codec=codec)
    kw.update(hfl_kw)
    hfl = HFLConfig(**kw)
    topo = HCNTopology(num_clusters=3, seed=0)
    fleet = DeviceFleet(topo, 2, seed=0)
    sim = SimConfig(scenario="custom", discipline=discipline)
    lp = LatencyParams(model_params=1e5)
    eng = SimEngine(period=2, hfl_cfg=hfl, sim_cfg=sim, topo=topo,
                    fleet=fleet, lp=lp)
    return hfl, eng


def _run(hfl, eng, steps=4, sync_mode=None):
    state = _tiny_state(hfl)
    train = jax.jit(make_cluster_train_step(_quad_loss, SGDM(momentum=0.0),
                                            lambda t: 0.2))
    sync = jax.jit(make_sync_step(hfl, mesh=None))
    rng = np.random.default_rng(1)

    def batches():
        while True:
            yield jnp.asarray(
                rng.normal(size=(hfl.num_clusters, 4, D)).astype(np.float32))

    return eng.run(state, train, sync, batches(), steps)


def test_engine_measured_lockstep_prices_real_bits():
    hfl, eng = _measured_engine()
    _, trace = _run(hfl, eng)
    m = trace.meta
    assert m["payload_accounting"] == "measured"
    assert m["codec"] == "delta-varint" and m["payload_size"] == D
    # two sync events, 3 uplink payloads each
    assert m["events_sbs_ul"] == 6 and m["events_mbs_dl"] == 2
    assert m["bits_sbs_ul"] > 0 and m["bits_mbs_dl"] > 0
    assert m["bits_fronthaul_total"] == m["bits_sbs_ul"] + m["bits_mbs_dl"]
    # trace rows carry the per-event measured bits and their sum matches
    rows = [r for r in trace.rows if r["kind"] == "sync"]
    assert sum(r["bits_sbs_ul"] for r in rows) == m["bits_sbs_ul"]
    # access links are charged per train launch from the codec measure
    assert m["bits_access_total"] == m["bits_mu_ul"] + m["bits_sbs_dl"]
    assert m["bits_mu_ul"] == 4 * 6 * access_bits("delta-varint", D,
                                                  hfl.phi_mu_ul)
    # virtual time still advances monotonically
    ts = trace.times()
    assert all(b >= a for a, b in zip(ts, ts[1:])) and ts[0] > 0


def test_engine_measured_replays_bit_identically():
    h1, e1 = _measured_engine()
    h2, e2 = _measured_engine()
    _, t1 = _run(h1, e1)
    _, t2 = _run(h2, e2)
    assert t1.rows == t2.rows and t1.meta == t2.meta


def test_measured_mode_warns_on_index_bits():
    from repro.comm.accounting import _reset_index_bits_warning

    _reset_index_bits_warning()
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1,
                    payload_accounting="measured")
    topo = HCNTopology(num_clusters=2, seed=0)
    fleet = DeviceFleet(topo, 1, seed=0)
    lp = LatencyParams(model_params=1e5, index_bits=32.0)
    with pytest.warns(DeprecationWarning):
        SimEngine(period=2, hfl_cfg=hfl, sim_cfg=SimConfig(),
                  topo=topo, fleet=fleet, lp=lp)
    # once per process: a second engine must NOT warn again
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        SimEngine(period=2, hfl_cfg=hfl, sim_cfg=SimConfig(),
                  topo=topo, fleet=fleet, lp=lp)


def test_analytic_mode_warns_on_index_bits():
    """The deprecation fires under ANALYTIC accounting too (measured-era
    params on the legacy pricing path double-charge just the same)."""
    from repro.comm.accounting import _reset_index_bits_warning

    _reset_index_bits_warning()
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1)  # analytic default
    topo = HCNTopology(num_clusters=2, seed=0)
    fleet = DeviceFleet(topo, 1, seed=0)
    with pytest.warns(DeprecationWarning):
        SimEngine(period=2, hfl_cfg=hfl, sim_cfg=SimConfig(),
                  topo=topo, fleet=fleet,
                  lp=LatencyParams(model_params=1e5, index_bits=32.0))
    # index_bits=0 (the paper default) stays silent
    _reset_index_bits_warning()
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        SimEngine(period=2, hfl_cfg=hfl, sim_cfg=SimConfig(),
                  topo=topo, fleet=fleet,
                  lp=LatencyParams(model_params=1e5))


def test_measured_mode_requires_wireless():
    hfl = HFLConfig(payload_accounting="measured")
    with pytest.raises(ValueError):
        SimEngine(period=2, hfl_cfg=hfl)


def test_measured_mode_rejects_leaf_layout():
    """The probe mirrors the flat whole-model sync; measuring it under the
    leaf layout would report bits that were never transmitted."""
    hfl, eng = _measured_engine(sync_layout="leaf")
    with pytest.raises(ValueError):
        _run(hfl, eng)


def test_measured_mode_warns_on_wire_mismatch():
    """A q8 codec prices 8-bit values, but sync_mode=sparse exchanges f32:
    the engine must surface the fidelity mismatch."""
    hfl, eng = _measured_engine(codec="delta-varint-q8")
    with pytest.warns(UserWarning, match="wire format"):
        _run(hfl, eng, steps=2)


def test_measured_mode_dense_sync_prices_raw_f32():
    hfl, eng = _measured_engine(sync_mode="dense", codec="dense-f32")
    _, trace = _run(hfl, eng)
    m = trace.meta
    # every fronthaul hop ships the raw 32·Q model
    assert m["bits_sbs_ul"] == m["events_sbs_ul"] * 32 * D
    assert m["bits_mbs_dl"] == m["events_mbs_dl"] * 32 * D


# ---------------------------------------------------------------------------
# Async sparse downlink (per-cluster DL error buffers)
# ---------------------------------------------------------------------------


def test_async_sparse_dl_reduces_to_dense_at_phi0():
    """φ_mbs_dl=0 sends everything: the sparse-DL path must equal the
    historical dense adoption exactly."""
    hfl = HFLConfig(num_clusters=3, mus_per_cluster=1, period=1,
                    sync_mode="sparse", phi_sbs_ul=0.5, phi_mbs_dl=0.0,
                    beta_s=0.0, beta_m=0.0)
    dense = make_async_sync_step(hfl)
    sparse = make_async_sync_step(hfl, dl_sparse=True)
    s1 = _tiny_state(hfl, drift_seed=3)
    s2 = _tiny_state(hfl, drift_seed=3)
    e_dl = init_dl_error(s2, hfl)
    o1 = dense(s1, jnp.int32(1), jnp.float32(0.25))
    o2, e_dl = sparse(s2, e_dl, jnp.int32(1), jnp.float32(0.25))
    np.testing.assert_allclose(np.asarray(o1.w_ref["w"]),
                               np.asarray(o2.w_ref["w"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(o1.params["w"]),
                               np.asarray(o2.params["w"]), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(e_dl[1]), 0.0, atol=1e-6)


def test_async_sparse_dl_buffers_the_missing_part():
    """With a sparse downlink the cluster receives only the top-(1-φ) of
    what it is missing; e_dl must hold EXACTLY the rest per cluster."""
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1, period=1,
                    sync_mode="sparse", phi_sbs_ul=0.0, phi_mbs_dl=0.75,
                    beta_s=0.0, beta_m=1.0)
    sync = make_async_sync_step(hfl, dl_sparse=True)
    state = _tiny_state(hfl, drift_seed=4)
    wn0 = np.asarray(state.params["w"], np.float32).copy()
    e_dl = init_dl_error(state, hfl)
    out, e_dl = sync(state, e_dl, jnp.int32(0), jnp.float32(0.5))
    wref = np.asarray(out.w_ref["w"])  # wref + 0.5 * dense drift
    recv = np.asarray(out.params["w"][0]) - wn0[0]
    # conservation: received + buffered == the full gap to the reference
    np.testing.assert_allclose(recv + np.asarray(e_dl[0]), wref - wn0[0],
                               rtol=1e-5, atol=1e-6)
    # sparse: at most keep_count entries moved
    assert np.count_nonzero(recv) <= sp.keep_count(D, 0.75)
    # the OTHER cluster's buffer is untouched
    np.testing.assert_allclose(np.asarray(e_dl[1]), 0.0, atol=0.0)


def test_engine_async_measured_with_sparse_dl():
    hfl, eng = _measured_engine(discipline="async",
                                async_dl_sparse=True, phi_mbs_dl=0.9)
    _, trace = _run(hfl, eng, steps=8)
    m = trace.meta
    assert m["events_sbs_ul"] >= 3 and m["events_mbs_dl"] >= 3
    # sparse DL payloads are far below the dense adoption's 32·Q bits
    assert m["bits_mbs_dl"] / m["events_mbs_dl"] < 32 * D


# ---------------------------------------------------------------------------
# Per-event DL broadcast repricing (measured mode)
# ---------------------------------------------------------------------------


def test_hfl_latency_exposes_dl_rates():
    from repro.wireless.latency import hfl_latency

    topo = HCNTopology(num_clusters=3, seed=0)
    fleet = DeviceFleet(topo, 2, seed=0)
    lp = LatencyParams(model_params=1e5)
    _, aux = hfl_latency(topo, fleet.pos, fleet.cid, lp, H=2,
                         phi_sbs_dl=0.9)
    bits = lp.payload(0.9)
    expect = np.where(aux["gamma_dl"] > 0, bits / aux["gamma_dl"], np.inf)
    np.testing.assert_allclose(aux["dl_rates"], expect)
    assert np.isfinite(aux["dl_rates"]).any()


def test_measured_sync_reprices_broadcast_from_actual_bits():
    """The sync's SBS->MU broadcast leg must be priced from the ACTUAL
    encoded consensus payload (per-event dl bits over the realized
    broadcast rates), not the static per-iteration sbs_dl estimate — and
    its bits must land in the ledger's sbs_dl link."""
    hfl, eng = _measured_engine()
    _, trace = _run(hfl, eng)
    m = trace.meta
    rows = [r for r in trace.rows if r["kind"] == "sync"]
    assert rows and all("bits_sync_bcast" in r for r in rows)
    aux = eng._latency_aux()
    finite = np.isfinite(aux["dl_rates"])
    n_bcast = int(finite.sum())
    for r in rows:
        assert r["bits_sync_bcast"] == pytest.approx(
            n_bcast * r["bits_mbs_dl"])
        # the broadcast leg is priced from THIS event's dl payload over
        # the realized rates (the fleet is static, so aux is the round's):
        # bcast_max <= sync_s <= fronthaul(ul_sum + dl) + bcast_max
        expect_bcast = (r["bits_mbs_dl"] / aux["dl_rates"][finite]).max()
        assert r["sync_s"] >= expect_bcast
        assert r["sync_s"] <= ((r["bits_sbs_ul"] + r["bits_mbs_dl"])
                               / aux["fh_rate"] + expect_bcast + 1e-12)
    # ledger: sbs_dl carries both the per-iteration access broadcasts and
    # the per-sync consensus broadcasts
    train_launches = m["train_launches"]
    n_syncs = m["sync_launches"]
    per_iter = access_bits(hfl.codec, D, hfl.phi_sbs_dl)
    expected_sbs_dl = (train_launches * hfl.num_clusters * per_iter
                       + sum(r["bits_sync_bcast"] for r in rows))
    assert m["bits_sbs_dl"] == pytest.approx(expected_sbs_dl)
    assert m["events_sbs_dl"] == (train_launches * hfl.num_clusters
                                  + n_syncs * n_bcast)
