"""Event-driven HCN simulator tests: event ordering, virtual-time
monotonicity, deadline drop, async staleness weighting, bit-identical
replay, Fig. 3 latency ordering, and the donated sync step."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import HFLConfig, SimConfig
from repro.core.hfl import (
    hfl_init, jit_sync_step, make_cluster_train_step, make_sync_step,
)
from repro.core.schedule import run_hfl
from repro.optim import SGDM
from repro.sim.devices import DeviceFleet
from repro.sim.engine import SimEngine, async_weight, make_async_sync_step
from repro.sim.events import Event, EventQueue
from repro.sim.scenarios import (
    SCENARIOS, apply_hfl_overrides, build_engine, get_scenario,
    run_scale_sampling,
)
from repro.wireless.latency import LatencyParams
from repro.wireless.topology import HCNTopology

# ---------------------------------------------------------------------------
# A tiny quadratic "model" so engine tests run in milliseconds
# ---------------------------------------------------------------------------

D = 12


def _quad_loss(params, batch):
    return jnp.mean((params["w"][None, :] - batch) ** 2), {}


def _setup(hfl, seed=0):
    params = {"w": jnp.zeros((D,), jnp.float32)}
    opt = SGDM(momentum=0.0)
    state = hfl_init(params, opt, hfl)
    train = jax.jit(make_cluster_train_step(_quad_loss, opt, lambda t: 0.2))
    sync = jax.jit(make_sync_step(hfl, mesh=None))
    return state, train, sync


def _batches(hfl, bpm=2, seed=1):
    rng = np.random.default_rng(seed)
    N, B = hfl.num_clusters, hfl.mus_per_cluster * bpm

    def gen():
        while True:
            yield jnp.asarray(rng.normal(size=(N, B, D)).astype(np.float32))

    return gen()


# ---------------------------------------------------------------------------
# Event queue
# ---------------------------------------------------------------------------


def test_event_queue_orders_by_time():
    q = EventQueue()
    q.push(3.0, Event("c"))
    q.push(1.0, Event("a"))
    q.push(2.0, Event("b"))
    assert [q.pop()[1].kind for _ in range(3)] == ["a", "b", "c"]


def test_event_queue_fifo_on_ties():
    q = EventQueue()
    for i in range(5):
        q.push(1.0, Event("e", cluster=i))
    assert [q.pop()[1].cluster for _ in range(5)] == [0, 1, 2, 3, 4]


def test_event_queue_rejects_past_and_advances_now():
    q = EventQueue()
    q.push(2.0, Event("a"))
    t, _ = q.pop()
    assert t == 2.0 and q.now == 2.0
    with pytest.raises(ValueError):
        q.push(1.0, Event("late"))
    q.push(2.0, Event("same-time-ok"))
    assert q.pop()[0] == 2.0


# ---------------------------------------------------------------------------
# Devices
# ---------------------------------------------------------------------------


def test_fleet_mobility_and_reassociation():
    topo = HCNTopology(seed=0)
    fleet = DeviceFleet(topo, 2, speed_mps=10.0, seed=0)
    p0 = fleet.pos.copy()
    fleet.advance(5.0)
    moved = np.linalg.norm(fleet.pos - p0, axis=1)
    assert (moved <= 50.0 + 1e-9).all() and moved.max() > 0
    cid = fleet.reassociate()
    d = np.linalg.norm(fleet.pos[:, None] - topo.sbs_pos[None], axis=2)
    np.testing.assert_array_equal(cid, d.argmin(axis=1))


def test_fleet_compute_and_availability_deterministic():
    topo = HCNTopology(seed=0)
    f1 = DeviceFleet(topo, 3, compute_sigma=1.0, dropout=0.4, seed=7)
    f2 = DeviceFleet(topo, 3, compute_sigma=1.0, dropout=0.4, seed=7)
    np.testing.assert_array_equal(f1.compute_mult, f2.compute_mult)
    np.testing.assert_array_equal(f1.draw_available(), f2.draw_available())
    assert f1.compute_mult.std() > 0  # actually heterogeneous


# ---------------------------------------------------------------------------
# run_hfl is now an adapter over the engine: call order must be unchanged
# ---------------------------------------------------------------------------


def test_run_hfl_adapter_preserves_call_order():
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=2, period=2,
                    sync_mode="sparse")
    state, train, sync = _setup(hfl)
    calls = []
    wtrain = lambda s, b: (calls.append("train"), train(s, b))[1]
    wsync = lambda s: (calls.append("sync"), sync(s))[1]
    on_step = lambda t, s, l: calls.append(f"on{t}")
    run_hfl(state, wtrain, wsync, _batches(hfl), 2, 5, on_step)
    assert calls == ["train", "on0", "train", "sync", "on1",
                     "train", "on2", "train", "sync", "on3", "train", "on4"]


def test_run_hfl_adapter_trains():
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=2, period=2,
                    sync_mode="sparse")
    state, train, sync = _setup(hfl)
    losses = []
    run_hfl(state, train, sync, _batches(hfl), 2, 12,
            lambda t, s, l: losses.append(float(jnp.mean(l))))
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# Virtual-time monotonicity + Fig. 3 ordering
# ---------------------------------------------------------------------------


def _run_scenario(name, hfl_base=None, lp=None, steps=None, seed=0):
    scn = get_scenario(name)
    hfl = apply_hfl_overrides(
        scn, hfl_base or HFLConfig(num_clusters=3, mus_per_cluster=2, period=2)
    )
    engine = build_engine(scn, hfl, lp=lp, seed=seed)
    state, train, sync = _setup(hfl)
    steps = steps if steps is not None else 2 * hfl.period
    return engine.run(state, train, sync, _batches(hfl), steps)


@pytest.mark.parametrize("name", ["stragglers", "mobility", "dropout", "async"])
def test_virtual_time_monotone(name):
    _, trace = _run_scenario(name, lp=LatencyParams(model_params=1e5))
    ts = trace.times()
    assert len(ts) > 0
    assert all(b >= a for a, b in zip(ts, ts[1:]))
    assert ts[0] > 0  # virtual time actually advances


def test_paper_fig3_latency_ordering():
    """Fig. 3: at the paper's φ and topology, HFL beats FL on the time
    axis — per-iteration (Γ^HFL = Γ^period/H < T^FL) AND one whole HFL
    period (H iterations + consensus) completes before a single FL
    iteration (speedup > H at the pinned K=4, H=2 point)."""
    _, trace = _run_scenario("paper-fig3")  # paper payload (Q=11.2M)
    m = trace.meta
    assert m["wireless"]
    assert m["t_hfl_iter_s"] < m["t_fl_iter_s"]
    assert m["t_hfl_period_s"] < m["t_fl_iter_s"]
    # the trace's own per-period wall time agrees with the meta estimate
    syncs = trace.times("sync")
    assert len(syncs) == 2
    assert syncs[0] == pytest.approx(m["t_hfl_period_s"], rel=0.25)


def test_replay_is_bit_identical():
    """Same (scenario, seed) -> identical trace and identical final model."""
    s1, t1 = _run_scenario("stragglers", lp=LatencyParams(model_params=1e5))
    s2, t2 = _run_scenario("stragglers", lp=LatencyParams(model_params=1e5))
    assert t1.rows == t2.rows
    assert t1.meta == t2.meta
    np.testing.assert_array_equal(np.asarray(s1.params["w"]),
                                  np.asarray(s2.params["w"]))


# ---------------------------------------------------------------------------
# Deadline discipline: straggler drop
# ---------------------------------------------------------------------------


def _engine_with_straggler(discipline, *, mult=200.0, deadline_factor=1.5):
    hfl = HFLConfig(num_clusters=3, mus_per_cluster=2, period=2,
                    sync_mode="sparse")
    topo = HCNTopology(num_clusters=3, seed=0)
    compute_mult = np.ones(6)
    compute_mult[0] = mult  # MU 0 is pathologically slow
    fleet = DeviceFleet(topo, 2, seed=0, compute_mult=compute_mult)
    sim = SimConfig(scenario="custom", discipline=discipline,
                    base_compute_s=0.05, deadline_factor=deadline_factor)
    lp = LatencyParams(model_params=1e5)
    return hfl, SimEngine(period=2, hfl_cfg=hfl, sim_cfg=sim, topo=topo,
                          fleet=fleet, lp=lp)


def test_deadline_drops_straggler_and_caps_round():
    hfl, eng_dl = _engine_with_straggler("deadline")
    state, train, sync = _setup(hfl)
    _, tr_dl = eng_dl.run(state, train, sync, _batches(hfl), 4)
    # the straggler was dropped every round
    sync_rows = [r for r in tr_dl.rows if r["kind"] == "sync"]
    assert all(r["dropped"] >= 1 for r in sync_rows)
    # each round's iteration wall time respects the deadline (the consensus
    # adds its fronthaul time on top, which the deadline does not govern)
    for r in sync_rows:
        assert r["deadline_s"] is not None
        assert 2 * r["iter_s"] <= r["deadline_s"] + 1e-9

    # lockstep with the same straggler must be much slower
    hfl2, eng_ls = _engine_with_straggler("lockstep")
    state2, train2, sync2 = _setup(hfl2)
    _, tr_ls = eng_ls.run(state2, train2, sync2, _batches(hfl2), 4)
    assert tr_dl.wallclock < 0.25 * tr_ls.wallclock


def test_dropout_skips_empty_clusters_without_crashing():
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1, period=2,
                    sync_mode="sparse")
    topo = HCNTopology(num_clusters=2, seed=0)
    fleet = DeviceFleet(topo, 1, dropout=0.9, seed=0)
    sim = SimConfig(scenario="custom", discipline="lockstep", dropout=0.9)
    eng = SimEngine(period=2, hfl_cfg=hfl, sim_cfg=sim, topo=topo,
                    fleet=fleet, lp=LatencyParams(model_params=1e5))
    state, train, sync = _setup(hfl)
    _, trace = eng.run(state, train, sync, _batches(hfl), 4)
    assert any(r["dropped"] >= 1 for r in trace.rows)


# ---------------------------------------------------------------------------
# Async discipline: staleness weighting
# ---------------------------------------------------------------------------


def test_async_weight_discounts_staleness():
    N = 4
    assert async_weight(0, N) == pytest.approx(1.0 / N)
    assert async_weight(1, N) == pytest.approx(1.0 / (2 * N))
    ws = [async_weight(s, N) for s in range(5)]
    assert all(a > b for a, b in zip(ws, ws[1:]))
    # exponent sharpens the discount
    assert async_weight(3, N, exp=2.0) < async_weight(3, N, exp=1.0)


def test_async_sync_step_applies_weighted_drift():
    """With φ_sbs_ul=0 the uplink is dense: the MBS must move by exactly
    weight * drift, and the cluster must adopt the new reference."""
    hfl = HFLConfig(num_clusters=3, mus_per_cluster=1, period=1,
                    sync_mode="sparse", phi_sbs_ul=0.0, beta_s=0.0)
    drift = jnp.arange(D, dtype=jnp.float32)
    sync_n = make_async_sync_step(hfl)
    for staleness in (0, 2):
        # fresh state each time: the async sync donates its input buffers
        params = {"w": jnp.zeros((D,), jnp.float32)}
        state = hfl_init(params, SGDM(momentum=0.0), hfl)
        state = state._replace(
            params={"w": state.params["w"].at[1].add(drift)})
        w = async_weight(staleness, hfl.num_clusters)
        out = sync_n(state, jnp.int32(1), jnp.float32(w))
        applied = np.asarray(out.w_ref["w"]) - 0.0
        np.testing.assert_allclose(applied, w * np.asarray(drift), rtol=1e-6)
        # the syncing cluster adopts the fresh reference; others untouched
        np.testing.assert_allclose(np.asarray(out.params["w"][1]),
                                   np.asarray(out.w_ref["w"]), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out.params["w"][0]), 0.0)


def test_async_engine_rows_carry_consistent_weights():
    _, trace = _run_scenario("async", lp=LatencyParams(model_params=1e5),
                             steps=8)
    rows = [r for r in trace.rows if r["kind"] == "sync"]
    assert len(rows) >= 4
    N = 3
    for r in rows:
        assert r["weight"] == pytest.approx(
            async_weight(r["staleness"], N,
                         SCENARIOS["async"].sim.staleness_exp))
    # heterogeneous compute (σ=0.5) must actually desynchronise the clocks
    assert any(r["staleness"] > 0 for r in rows)
    # every cluster keeps making progress
    assert {r["cluster"] for r in rows} == {0, 1, 2}


def test_async_honors_dropout():
    """The availability trace applies on the async path too: rounds either
    drop MUs (resampled batch) or idle the cluster entirely."""
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=2, period=2,
                    sync_mode="sparse")
    topo = HCNTopology(num_clusters=2, seed=0)
    fleet = DeviceFleet(topo, 2, dropout=0.6, seed=0)
    sim = SimConfig(scenario="custom", discipline="async", dropout=0.6)
    eng = SimEngine(period=2, hfl_cfg=hfl, sim_cfg=sim, topo=topo,
                    fleet=fleet, lp=LatencyParams(model_params=1e5))
    state, train, sync = _setup(hfl)
    _, trace = eng.run(state, train, sync, _batches(hfl), 8)
    assert any(r.get("dropped", 0) >= 1 or r["kind"] == "idle"
               for r in trace.rows)
    # idle rounds still advance the round counter -> the run terminates
    # with every cluster having been scheduled for all its rounds
    assert max(r["round"] for r in trace.rows) == 3


# ---------------------------------------------------------------------------
# Donated sync buffers (satellite: peak-memory lever)
# ---------------------------------------------------------------------------


def test_jit_sync_step_donates_state_buffers():
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1, period=1,
                    sync_mode="sparse")
    params = {"w": jnp.zeros((64,), jnp.float32)}
    state = hfl_init(params, SGDM(momentum=0.0), hfl)
    sync = jit_sync_step(make_sync_step(hfl, mesh=None))
    out = sync(state)
    # the input buffers were donated: deleted, not copied
    assert state.params["w"].is_deleted()
    assert state.w_ref["w"].is_deleted()
    assert state.eps["w"].is_deleted()
    assert state.e["w"].is_deleted()
    # and the outputs are live and correct-shaped
    assert out.params["w"].shape == (2, 64)
    assert not out.params["w"].is_deleted()


def test_jit_dense_sync_returns_untouched_buffers_without_copies():
    # dense averaging reads w_ref only for its dtype and leaves opt, eps and
    # e alone: each donated buffer must come back as itself, not as a copy
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1, period=1,
                    sync_mode="dense")
    params = {"w": jnp.zeros((64,), jnp.float32),
              "v": jnp.zeros((8, 64), jnp.float32)}
    state = hfl_init(params, SGDM(momentum=0.9), hfl)
    sync = jit_sync_step(make_sync_step(hfl, mesh=None))
    text = sync.lower(state).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    assert " copy(" not in entry
    n_buffers = len(jax.tree.leaves(state))
    assert entry.count(" parameter(") == n_buffers


# ---------------------------------------------------------------------------
# scale-100k sampling scenario
# ---------------------------------------------------------------------------


def test_scale_sampling_aggregates_only():
    scn = get_scenario("scale-100k")
    stats = run_scale_sampling(scn, n_users=20_000, chunk=5_000)
    assert stats["n_users"] == 20_000
    assert 0 < stats["rate_min_bps"] <= stats["rate_p50_bps"] <= stats["rate_max_bps"]
    assert stats["t_ul_worst_s"] >= stats["t_ul_median_s"] > 0
    # deterministic in the seed
    stats2 = run_scale_sampling(scn, n_users=20_000, chunk=5_000)
    assert stats == stats2
