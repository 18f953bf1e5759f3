"""Simulation engine: wall-clock scenario runs of the real training loop.

Couples three layers that never met before this subsystem:

  * the wireless model (``repro.wireless.latency``) — per-cluster UL/DL
    times, fronthaul, frequency reuse — evaluated against the fleet's
    *current* positions each round, so mobility changes the time axis;
  * the device runtime model (``repro.sim.devices``) — per-MU compute
    times, availability, mobility;
  * the *real* jitted training loop (``make_cluster_train_step`` /
    ``make_sync_step``) — the accuracy axis is produced by actual SGD on
    actual models, not a convergence proxy.

Time is virtual (``repro.sim.events``): a run is a pure function of
(scenario, seed) and replays bit-identically.

The hot paths are *cluster-granular over vectorized per-MU state*: events
carry cluster ids only, and every per-MU quantity (round times, masks,
survivor aggregates, slot sources) is computed with flat [K] numpy array
ops — no per-MU Python loops — so the same engine runs the paper's 28-MU
cells and million-MU fleets (``scale-1m``). ``sim.legacy.LegacySimEngine``
keeps the pre-vectorization per-MU loop bodies as a frozen reference; the
equivalence tests pin the rewrite bit-identical to it on the small
scenarios. Fleet-scale knobs: ``SimConfig.fleet_mus_per_cluster``
oversubscribes the training slots (cluster-subsampled batches via the
residency tracker), ``rate_model='single'`` prices UL with streamed
single-subcarrier M-QAM rates instead of Alg. 2 (which needs M >= K), and
``reprice_interval_s`` batches mobility bookkeeping between events.

Three sync disciplines:

  * ``lockstep`` — the paper's schedule: every cluster runs H intra-cluster
    iterations, the MBS consensus happens when the slowest cluster arrives
    (Γ^period = H·max_n Γ_n + Θ^U + Θ^D, eq. 21). Reproduces Fig. 3's
    HFL-vs-FL latency ordering.
  * ``deadline`` — straggler drop: each round has a deadline
    (``deadline_factor`` × median per-MU round time); MUs that would finish
    late are dropped for the round (their data is resampled from the
    participants) and the round completes at the slowest *surviving* MU.
  * ``async`` — clusters sync with the MBS on their own clocks; each
    cluster's contribution is applied with a staleness-discounted weight
    (``async_weight``), trading consensus freshness for zero straggler
    stalls.

Payload accounting (``HFLConfig.payload_accounting``): ``analytic`` prices
every transfer with the paper's idealized ``Q·(1-φ)·bits_per_param``;
``measured`` prices with byte-accurate codec streams (``repro.comm``) —
the REAL ``(values, indices)`` fronthaul payloads are measured per sync
event (a jitted probe re-runs the sync's Ω selection on the same state),
the per-iteration access links with the codec on synthetic exact-k
payloads, and a per-link ``PayloadLedger`` lands in the trace meta.

Mobility sources: the built-in random-waypoint integrator, or *trace
replay* (``sim.traces``) — the fleet reads recorded positions off an
external CSV/JSONL trace (or a synthetic generator) at the engine's
virtual time, so real mobility datasets drive the byte-accurate time axis.

Data residency (``data.federated.ResidencyTracker``): by default data is
static — MU k always trains in cluster ``k // mus_per_cluster`` — while
*radio* association follows mobility. With a tracker attached, each
re-association remaps shards under a policy (``move`` / ``duplicate`` /
``stale``), and the engine gathers every cluster's batch rows from its
*resident* MUs' data slots, so cluster gradient distributions actually
shift as the fleet moves. Under ``duplicate`` the replicated shards'
rows are weighted ``1/n_copies`` (via the loss's ``row_weight`` leaf) so
the cluster sum conserves the effective data distribution, and compute
pricing follows the data too: a resident shard trains at its host MU's
speed multiplier (``_round_ctx`` / ``_cluster_round_time``), not at the
radio membership's.

Remaining modelling simplifications (documented, not hidden): the async
downlink applies the fresh reference densely unless
``HFLConfig.async_dl_sparse`` enables the per-cluster-error sparse
downlink; async event *times* are scheduled from the static measured
estimates (payloads are only known at the event); and the async/trace
disciplines compute all N clusters per launch unless the caller supplies
``masked_train_step`` (``core.hfl.make_masked_cluster_train_step``), which
slices out the active cluster and cuts per-launch FLOPs to ~1/N.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import HFLConfig, SimConfig
from repro.obs.telemetry import make_telemetry
from repro.sim.devices import DeviceFleet
from repro.sim.events import Event, EventQueue
from repro.wireless.latency import (
    LatencyParams, fl_latency, fl_latency_single, hfl_latency,
    hfl_latency_single,
)
from repro.wireless.topology import HCNTopology


# ---------------------------------------------------------------------------
# Trace
# ---------------------------------------------------------------------------


@dataclass
class Trace:
    """Deterministic wall-clock-vs-training record of one simulation run."""

    meta: dict = field(default_factory=dict)
    rows: list = field(default_factory=list)

    def add(self, **row) -> None:
        self.rows.append(row)

    @property
    def wallclock(self) -> float:
        return self.rows[-1]["t"] if self.rows else 0.0

    def times(self, kind: Optional[str] = None):
        return [r["t"] for r in self.rows if kind is None or r["kind"] == kind]

    def losses(self):
        return [(r["t"], r["loss"]) for r in self.rows if "loss" in r]

    def to_json(self) -> dict:
        return {"meta": self.meta, "rows": self.rows}


# ---------------------------------------------------------------------------
# Async staleness-weighted consensus
# ---------------------------------------------------------------------------


def async_weight(staleness: int, num_clusters: int, exp: float = 1.0) -> float:
    """MBS application weight of one cluster's async contribution.

    ``1/N`` matches the lockstep mean when every cluster arrives fresh;
    the ``(1+s)^-exp`` discount shrinks contributions computed against a
    reference that ``s`` other syncs have since moved.
    """
    return (1.0 / num_clusters) * (1.0 + float(staleness)) ** (-float(exp))


def make_async_sync_step(
    hfl_cfg: HFLConfig, *, dl_sparse: bool = False, codec=None,
    collect_stats: bool = False,
) -> Callable:
    """Per-cluster staleness-weighted sparse sync.

    The uplink is the paper's Ω (whole-model top-(1-φ) of the drift, with
    the SBS error buffer, wire-rounded under ``quantized_sparse``); the MBS
    applies ``weight * sent`` instead of the lockstep ``mean``.

    Downlink, two flavours:

      * dense (``dl_sparse=False``, historical): the cluster adopts the
        fresh reference verbatim — ``(state, n, weight) -> state``.
      * sparse (``dl_sparse=True``): the MBS sends Ω of what the cluster
        is missing (``φ_mbs_dl``), buffered by a PER-CLUSTER downlink
        error ``e_dl [N, Q]`` (``β_m``-discounted, mirroring the global
        ``e`` of the lockstep consensus) that the caller threads through:
        ``(state, e_dl, n, weight) -> (state, e_dl)``. Build the initial
        buffer with ``init_dl_error``.

    With ``codec`` set (a ``repro.comm.codecs`` codec or name), each call
    additionally returns a dict of traced measured-bit counts for the
    payloads actually sent: ``{"sbs_ul": ...}`` plus ``"mbs_dl"`` when the
    downlink is sparse (the dense adoption's bits are static in Q — the
    engine charges them from ``comm.accounting.access_bits``).
    """
    from repro.core import sparsify as sp
    from repro.core.hfl import _wire_round, wire_format_of
    from repro.utils import flatten as fl

    if isinstance(codec, str):
        from repro.comm.codecs import get_codec

        codec = get_codec(codec)
    impl = hfl_cfg.omega_impl
    wire = wire_format_of(hfl_cfg)

    def _core(state, e_dl, n, weight):
        wref, ref_spec = fl.pack(state.w_ref)
        wn_all, p_spec = fl.pack_stacked(state.params)
        eps_all, eps_spec = fl.pack_stacked(state.eps)
        Q = ref_spec.total
        bits = {}

        # --- uplink (Alg.5 l.24-27 for ONE cluster) ---
        s = wn_all[n] - wref + hfl_cfg.tiers[1].beta_up * eps_all[n]
        vals, idx = sp.pack_phi(s, hfl_cfg.tiers[1].phi_up, impl=impl)
        if wire:
            # the residual buffers the wire error too (receivers only
            # ever see the rounded value), matching the lockstep paths
            vals = _wire_round(vals, wire)
        if codec is not None:
            bits["sbs_ul"] = codec.measure_bits_jax(vals, idx, Q)
        sent = sp.unpack_topk(vals, idx, Q)
        new_eps_n = s - sent

        # --- MBS: staleness-weighted application ---
        new_wref = wref + weight * sent

        # --- downlink ---
        if dl_sparse:
            diff = new_wref - wn_all[n] + hfl_cfg.tiers[1].beta_down * e_dl[n]
            dvals, didx = sp.pack_phi(diff, hfl_cfg.tiers[1].phi_down, impl=impl)
            if wire:
                dvals = _wire_round(dvals, wire)
            if codec is not None:
                bits["mbs_dl"] = codec.measure_bits_jax(dvals, didx, Q)
            recv = sp.unpack_topk(dvals, didx, Q)
            new_row = wn_all[n] + recv
            e_dl = e_dl.at[n].set(diff - recv)
        else:
            new_row = new_wref  # dense adoption of the fresh reference

        new_wn = wn_all.at[n].set(new_row)
        new_eps = eps_all.at[n].set(new_eps_n)
        stats = None
        if collect_stats:
            # health-monitor signals for THIS cluster (scalar variants of
            # the lockstep ``_flat_sync_stats``); computed from values the
            # sync already holds, so no extra HBM round-trips
            wbar = jnp.mean(new_wn, axis=0)
            wnorm = jnp.maximum(jnp.linalg.norm(wbar), 1e-30)
            stats = {
                "drift": jnp.linalg.norm(new_wn[n] - wbar) / wnorm,
                "eps_norm": jnp.linalg.norm(new_eps_n),
                "wref_norm": jnp.linalg.norm(new_wref),
                "update_norm": jnp.linalg.norm(weight * sent),
                "ul_idx": idx,
            }
            if dl_sparse:
                stats["e_dl_norm"] = jnp.linalg.norm(e_dl[n])
                stats["dl_idx"] = didx
        state = state._replace(
            params=fl.unpack_stacked(new_wn, p_spec),
            w_ref=fl.unpack(new_wref, ref_spec),
            eps=fl.unpack_stacked(new_eps, eps_spec),
        )
        return state, e_dl, bits, stats

    if dl_sparse:

        @partial(jax.jit, donate_argnums=(0, 1))
        def async_sync_dl(state, e_dl, n, weight):
            state, e_dl, bits, stats = _core(state, e_dl, n, weight)
            out = (state, e_dl)
            if codec is not None:
                out = out + (bits,)
            if collect_stats:
                out = out + (stats,)
            return out

        async_sync_dl.collect_stats = collect_stats
        return async_sync_dl

    @partial(jax.jit, donate_argnums=0)
    def async_sync(state, n, weight):
        state, _, bits, stats = _core(state, None, n, weight)
        if codec is None and not collect_stats:
            return state
        out = (state,)
        if codec is not None:
            out = out + (bits,)
        if collect_stats:
            out = out + (stats,)
        return out

    async_sync.collect_stats = collect_stats
    return async_sync


def init_dl_error(state, hfl_cfg: HFLConfig):
    """Zero per-cluster downlink error buffer [N, Q] for the sparse-DL
    async sync (flat layout, same offsets as the packed ``w_ref``)."""
    from repro.utils import flatten as fl

    Q = fl.spec_of(state.w_ref).total
    return jnp.zeros((hfl_cfg.num_clusters, Q), jnp.float32)


# ---------------------------------------------------------------------------
# State merge helpers — jitted with the outgoing state donated: one fused
# program writing in place, instead of an eager per-leaf copy of the whole
# stacked state (donating `new` too would leave surplus unaliasable buffers)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=2, donate_argnums=0)
def _take_cluster_row(old, new, n: int):
    """Keep only cluster ``n``'s update out of a full vmapped train step."""
    row = lambda o, w: o.at[n].set(w[n])
    return old._replace(
        params=jax.tree.map(row, old.params, new.params),
        opt=jax.tree.map(row, old.opt, new.opt),
        step=new.step,
    )


@partial(jax.jit, donate_argnums=0)
def _merge_clusters(old, new, keep):
    """Keep updates only for clusters where ``keep[n]`` (others sat out)."""
    k = jnp.asarray(keep)
    sel = lambda o, w: jnp.where(k.reshape((-1,) + (1,) * (w.ndim - 1)), w, o)
    return old._replace(
        params=jax.tree.map(sel, old.params, new.params),
        opt=jax.tree.map(sel, old.opt, new.opt),
        step=new.step,
    )


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class SimEngine:
    """Drives (train_step, sync_step) under a scenario's wall clock.

    With ``topo``/``fleet``/``lp`` unset the engine runs in *null-wireless*
    mode: unit virtual time per iteration, zero comms time — exactly the
    timeless lockstep loop ``core.schedule.run_hfl`` used to be (and now
    adapts to).
    """

    def __init__(
        self,
        *,
        period: int,
        hfl_cfg: Optional[HFLConfig] = None,
        sim_cfg: Optional[SimConfig] = None,
        topo: Optional[HCNTopology] = None,
        fleet: Optional[DeviceFleet] = None,
        lp: Optional[LatencyParams] = None,
        record: bool = True,
        residency=None,
        obs=None,
    ):
        # record=False skips trace rows (and the per-step loss
        # materialisation they force): the run_hfl adapter discards the
        # trace, and blocking the host on every step's loss would stop
        # dispatch from running ahead like the historical loop did.
        self._record = record
        self.period = int(period)
        self.hfl = hfl_cfg
        self.sim = sim_cfg if sim_cfg is not None else SimConfig()
        # telemetry (repro.obs): an explicit handle wins (callers sharing
        # one tracer across runs); otherwise resolved from SimConfig.obs.
        # The default collapses to the shared NULL_TELEMETRY whose
        # ``enabled`` flag guards every emit site — virtual time, bit
        # totals and RNG draws are never touched by instrumentation, so
        # runs stay bit-identical with tracing on, off, or absent.
        self.obs = obs if obs is not None else make_telemetry(
            getattr(self.sim, "obs", None))
        self.topo, self.fleet, self.lp = topo, fleet, lp
        self.wireless = topo is not None and fleet is not None and lp is not None
        # oversubscribed fleets: more physical MUs than training slots
        # (SimConfig.fleet_mus_per_cluster > hfl.mus_per_cluster). Each
        # round subsamples the resident shards into the slots, so batches
        # stay [N, localB] while pricing/availability run fleet-wide.
        self._oversub = False
        if self.wireless:
            assert hfl_cfg is not None, "wireless simulation needs hfl_cfg"
            slots = hfl_cfg.num_clusters * hfl_cfg.mus_per_cluster
            self._oversub = fleet.K > slots
            if self._oversub:
                assert residency is not None, (
                    "an oversubscribed fleet (K > num_clusters * "
                    "mus_per_cluster) needs a residency tracker to pick "
                    "which resident shards fill the training slots")
            else:
                assert fleet.K == slots
            if self.sim.rate_model == "maxmin" and fleet.K > lp.M:
                raise ValueError(
                    f"rate_model='maxmin' (Alg. 2) needs M >= K sub-carriers "
                    f"but M={lp.M} < K={fleet.K}; use rate_model='single' "
                    f"for fleet-scale runs")
            if self.sim.rate_model not in ("maxmin", "single"):
                raise ValueError(
                    f"unknown rate_model {self.sim.rate_model!r}")
        # data residency tracker (data.federated.ResidencyTracker): when
        # set, batch rows follow the resident shards instead of the static
        # slot layout. None = legacy static residency (bit-identical).
        self.residency = residency
        self._slot_rot = 0  # per-round rotation of the resident selection
        if residency is not None:
            assert self.wireless, "residency tracking needs the fleet"
            assert residency.K == fleet.K and \
                residency.N == hfl_cfg.num_clusters
        self._aux = None  # cached hfl_latency aux for the current positions
        self._crt = None  # cached per-cluster round times (same lifetime)
        self._move_accum = 0.0  # virtual s of motion deferred by the
        #                         reprice_interval_s throttle
        self._vt = 0.0  # current virtual time (diurnal availability clock)
        self._train_launches = 0
        self._sync_launches = 0
        self._bits_access = 0.0
        self._bits_fronthaul = 0.0
        # fleet-health bookkeeping (obs on only): per-cluster rounds seen /
        # rounds contributed, feeding sim.participation_rate and the
        # drop-fairness Gini at _finish_run
        self._rounds_part = None
        self._rounds_seen = None
        # measured-bits accounting (repro.comm): byte-accurate codec streams
        # replace the analytic Q·(1-φ)·bits_per_param in both event pricing
        # and the trace's byte totals. Ledger/probe are sized to the REAL
        # flat model length at run() (the analytic lp.model_params may
        # describe a different architecture than the one being trained).
        self._acc = getattr(hfl_cfg, "payload_accounting", "analytic") \
            if hfl_cfg is not None else "analytic"
        if self._acc not in ("analytic", "measured"):
            raise ValueError(f"unknown payload_accounting {self._acc!r}")
        # client selection (sim.selection): caps each cluster's
        # participants at ceil(prate * size) under a policy. None = the
        # identity (prate >= 1, uniform) — no RNG stream is even created,
        # so existing scenarios replay bit-identically.
        self.selector = None
        if self.wireless:
            from repro.sim.selection import make_selector

            self.selector = make_selector(hfl_cfg, self.sim)
        elif (float(getattr(sim_cfg, "prate", 1.0) if sim_cfg else 1.0) < 1.0
              or getattr(sim_cfg, "selection", "uniform") != "uniform"):
            raise ValueError(
                "client selection (prate < 1 or a non-uniform policy) "
                "needs the wireless fleet (topo/fleet/lp)")
        self._codec = None
        self.ledger = None
        self._probe = None
        self._ab = None  # static per-link access bits (synthetic payloads)
        if self._acc == "measured":
            if not self.wireless:
                raise ValueError("payload_accounting='measured' needs the "
                                 "wireless model (topo/fleet/lp)")
            from repro.comm.codecs import get_codec

            self._codec = get_codec(self.hfl.codec)
        if self.wireless:
            # index_bits deprecation fires under BOTH accounting modes now
            # (analytic pricing reads it too); once per process
            from repro.comm.accounting import warn_index_bits_deprecated

            warn_index_bits_deprecated(self.lp)

    # --- public entry ----------------------------------------------------

    def run(
        self,
        state,
        train_step: Callable,
        sync_step: Callable,
        batches: Iterable,
        num_steps: int,
        on_step: Optional[Callable] = None,
        masked_train_step: Optional[Callable] = None,
    ):
        """-> (final_state, Trace). Deterministic in (scenario, seed) for a
        FRESH engine: the fleet RNG and positions advance across calls, so
        reusing one engine continues its world rather than replaying it —
        build a new engine (``scenarios.build_engine``) per replayed run.

        Under the ``async`` discipline ``sync_step`` is unused: per-cluster
        consensus cannot be expressed by the all-cluster sync, so the
        engine derives a staleness-weighted per-cluster sync from
        ``hfl_cfg`` (``make_async_sync_step``) instead. ``masked_train_step``
        (``core.hfl.make_masked_cluster_train_step``, jitted by the caller)
        lets async rounds compute ONLY the active cluster — ~1/N the FLOPs
        of the vmapped ``train_step``, which is used as the fallback.
        """
        # fresh launch/byte accumulators so a reused engine's meta counts
        # only its own run (its fleet state still advances, see above)
        self._train_launches = 0
        self._sync_launches = 0
        self._bits_access = 0.0
        self._bits_fronthaul = 0.0
        self._slot_rot = 0
        if self.obs.enabled and self.hfl is not None:
            n_cl = self.hfl.num_clusters
            self._rounds_part = np.zeros(n_cl, np.int64)
            self._rounds_seen = np.zeros(n_cl, np.int64)
        else:
            self._rounds_part = self._rounds_seen = None
        self.obs.reset_run()
        self._setup_measured(state)
        hier = bool(getattr(sync_step, "hier", False))
        if hier and self.hfl is None:
            # null-wireless adapter (core.schedule.run_hfl): adopt the
            # tiered sync's own config for the hierarchy bookkeeping
            self.hfl = sync_step.cfg
        cut, deadline = self._tier_disciplines(hier)
        if cut is None:
            return self._run_lockstep(
                state, train_step, sync_step, batches, num_steps, on_step,
                deadline=deadline,
            )
        if not hier:
            # depth-2 flat async: the per-cluster staleness-weighted
            # consensus (make_async_sync_step) — the degenerate single-
            # boundary instance of the unit scheduler, kept as its own
            # loop so the historical event/RNG trajectory replays
            # bit-identically
            return self._run_async(state, train_step, batches, num_steps,
                                   on_step, masked_train_step)
        return self._run_units(state, train_step, sync_step, batches,
                               num_steps, on_step, cut=cut)

    def _tier_disciplines(self, hier: bool):
        """Resolve the run's sync disciplines -> ``(cut, deadline)``:
        ``cut`` is the lowest ASYNC tier boundary (every boundary at or
        above it runs clock-free; ``None`` = fully synchronous run) and
        ``deadline`` flags the boundary-1 per-MU straggler drop.

        Two spellings coexist: the legacy fleet-wide ``SimConfig.
        discipline`` knob, and per-tier ``TierConfig.discipline`` entries
        (PR 9). When every tier keeps the default lockstep, the legacy
        knob maps onto the tree — ``deadline`` onto boundary 1, ``async``
        onto the TOP boundary (the same boundary at depth 2) — otherwise
        the explicit per-tier entries win. Async boundaries must form a
        contiguous top suffix of the tree (a synchronous barrier cannot
        run above children on their own clocks), and ``deadline`` is only
        meaningful at boundary 1, below any async cut."""
        sim_disc = self.sim.discipline
        if sim_disc not in ("lockstep", "deadline", "async"):
            raise ValueError(f"unknown discipline {sim_disc!r}")
        if self.hfl is None or not hier:
            # flat depth-2 runs keep the legacy fleet-wide knob verbatim
            if sim_disc == "async":
                return 1, False
            return None, sim_disc == "deadline"
        d = [tc.discipline for tc in self.hfl.tiers[1:]]
        if all(x == "lockstep" for x in d) and sim_disc != "lockstep":
            if sim_disc == "deadline":
                d[0] = "deadline"
            else:
                d[-1] = "async"
        cut = None
        for i, x in enumerate(d):
            if x == "async":
                cut = i + 1
                break
        if cut is not None and any(x != "async" for x in d[cut - 1:]):
            raise ValueError(
                f"async tier boundaries must form a contiguous top suffix "
                f"of the tree (got disciplines {tuple(d)}): a synchronous "
                f"barrier cannot run above children on their own clocks")
        if any(x == "deadline" for x in d[1:]):
            raise ValueError(
                "the deadline discipline applies at tier boundary 1 only "
                "(the per-MU round deadline); higher boundaries are "
                "lockstep or async")
        deadline = d[0] == "deadline"
        if deadline and cut is not None:
            raise ValueError(
                "a deadline boundary below an async cut is not supported "
                "yet (the unit scheduler prices rounds without drops)")
        return cut, deadline

    # --- wireless plumbing -----------------------------------------------

    def _setup_measured(self, state) -> None:
        """Size the ledger/probe to the run's real flat model length."""
        if self._acc != "measured":
            return
        from repro.comm import accounting as acct
        from repro.core.hfl import wire_format_of
        from repro.utils import flatten as fl

        if self.hfl.sync_mode != "dense" \
                and getattr(self.hfl, "sync_layout", "flat") != "flat":
            # the probe mirrors the flat whole-model sync; leaf payloads
            # have per-leaf keep_count rounding and leaf-local index
            # statistics, so measuring the flat payloads would report bits
            # that were never transmitted
            raise ValueError(
                "payload_accounting='measured' requires sync_layout='flat' "
                "(the probe measures the whole-model payloads)")
        wire = wire_format_of(self.hfl) or "f32"
        vf = getattr(self._codec, "value_format", None)
        if vf is not None and vf != "mixed" and vf != wire:
            import warnings

            warnings.warn(
                f"codec {self._codec.name!r} carries {vf} values but the "
                f"sync's wire format is {wire}: measured bits price a "
                f"fidelity the simulation does not exchange", stacklevel=2)
        Q = fl.spec_of(state.w_ref).total
        depth = len(self.hfl.tiers)
        self.ledger = acct.PayloadLedger(
            codec=self._codec.name, size=Q,
            links=acct.link_names(depth),
            registry=self.obs.registry if self.obs.enabled else None)
        # depth 2 probes the flat whole-model sync; deeper trees probe the
        # tiered cascade's per-boundary Omega payloads (same codec streams)
        self._probe = (acct.make_sync_probe(self.hfl, self._codec)
                       if depth == 2
                       else acct.make_hier_sync_probe(self.hfl, self._codec))
        # static per-boundary access bits on synthetic exact-k payloads:
        # boundary t's uplink prices tiers[t].phi_up, its downlink
        # tiers[t].phi_down (depth-2 keys: mu_ul/sbs_dl/sbs_ul/mbs_dl)
        self._ab = {
            # the async dense adoption ships the raw reference: price it as
            # dense-f32 regardless of the (sparse) codec in use
            "dense": acct.access_bits("dense-f32", Q, 0.0),
        }
        for ti, tc in enumerate(self.hfl.tiers):
            ul_l, dl_l = acct.boundary_links(ti)
            self._ab[ul_l] = acct.access_bits(self._codec, Q, tc.phi_up)
            self._ab[dl_l] = acct.access_bits(self._codec, Q, tc.phi_down)
        self._aux = None  # re-price the radio with measured payloads

    def _payload_overrides(self):
        """Static measured per-link bits for the analytic-formula slots
        (the per-event fronthaul θ is re-priced from ACTUAL probe bits)."""
        if self.ledger is None:
            return None
        return {k: float(self._ab[k])
                for k in ("mu_ul", "sbs_dl", "sbs_ul", "mbs_dl")}

    def _price_hfl(self):
        """(per_iter, aux) under the configured rate model: exact max-min
        allocation (``maxmin``, the paper's Alg. 2) or the fleet-scale
        shared-single-subcarrier model (``single``, any K)."""
        fn = (hfl_latency_single if self.sim.rate_model == "single"
              else hfl_latency)
        return fn(
            self.topo, self.fleet.pos, self.fleet.cid, self.lp,
            H=self.period,
            phi_mu_ul=self.hfl.tiers[0].phi_up, phi_sbs_dl=self.hfl.tiers[0].phi_down,
            phi_sbs_ul=self.hfl.tiers[1].phi_up, phi_mbs_dl=self.hfl.tiers[1].phi_down,
            reuse=self.sim.reuse,
            payload_bits=self._payload_overrides(),
        )

    def _latency_aux(self) -> dict:
        if self._aux is None:
            _, self._aux = self._price_hfl()
        return self._aux

    def _meta(self) -> dict:
        meta = {
            "scenario": self.sim.scenario,
            "discipline": self.sim.discipline,
            "seed": self.sim.seed,
            "period": self.period,
            "payload_accounting": self._acc,
            "residency": (self.residency.policy if self.residency is not None
                          else "static"),
        }
        if self.fleet is not None and self.fleet.trace is not None:
            meta["trace_replay"] = True
            meta["trace_duration_s"] = self.fleet.trace.duration
        if self.ledger is not None:
            meta["codec"] = self.ledger.codec
            meta["payload_size"] = self.ledger.size
        if not self.wireless:
            meta["wireless"] = False
            return meta
        comp_max = float(
            self.sim.base_compute_s * self.fleet.compute_mult.max())
        pb = self._payload_overrides()
        fl_fn = (fl_latency_single if self.sim.rate_model == "single"
                 else fl_latency)
        t_fl, _ = fl_fn(
            self.topo, self.fleet.pos, self.lp,
            phi_ul=self.hfl.tiers[0].phi_up, phi_dl=self.hfl.tiers[1].phi_down,
            ul_bits=None if pb is None else pb["mu_ul"],
            dl_bits=None if pb is None else pb["mbs_dl"],
        )
        per_iter, aux = self._price_hfl()
        self._aux = aux
        meta.update(
            wireless=True,
            t_fl_iter_s=t_fl + comp_max,
            t_hfl_iter_s=per_iter + comp_max,
            t_hfl_period_s=self.period * (per_iter + comp_max),
        )
        return meta

    def _round_ctx(self, deadline: bool) -> dict:
        """Latency/participation context for ONE upcoming H-period round.

        Fully vectorized over the flat [K] fleet state: per-MU round times
        are one fused expression over the scattered rate vector, the
        survivor aggregates are exact group min/max scatters, and the slot
        sources come from one CSR pass — no per-MU Python loops, so a round
        costs the same few vector passes at 28 MUs or a million. Values are
        bit-identical to the historical per-cluster loop (same elementwise
        expressions; the ufunc reductions return an element of each group,
        exactly like the loop's ``.min()``/``.max()``). Only the Alg. 2
        sub-carrier reclamation stays a per-*affected-cluster* loop — it is
        skipped entirely under ``rate_model='single'`` (m=1 rates are
        allocation-free).
        """
        if not self.wireless:
            return dict(iter_s=self.sim.base_compute_s, sync_s=0.0,
                        mask=None, keep_clusters=None, dropped=0,
                        participants=None, deadline_s=None)
        hfl, lp, H = self.hfl, self.lp, self.period
        aux = self._latency_aux()
        cid = self.fleet.cid
        comp = self.fleet.compute_times(self.sim.base_compute_s)
        avail = self.fleet.draw_available(self._vt)
        fault = getattr(self.sim, "fault_dead_cluster", None)
        if fault is not None:
            # fault injection lands AFTER the RNG draw so the availability
            # stream (and thus every other cluster's trajectory) is
            # untouched — the faulted cluster's members just never come up
            avail = avail & (cid != fault)
        if self.selector is not None:
            # participation cap AFTER the availability/fault draws (the
            # selector only ever shrinks the mask, from its own RNG stream)
            avail = self.selector.select(avail, self.fleet, self._vt)
        N = hfl.num_clusters
        ul_pay = (float(self._ab["mu_ul"]) if self.ledger is not None
                  else lp.payload(hfl.tiers[0].phi_up))

        # per-MU round time: H iterations of own compute + own UL + cluster DL
        rate_flat = aux["mu_rate_flat"]
        r = H * (comp + ul_pay / rate_flat + aux["gamma_dl"][cid])

        mask = avail.copy()
        deadline_s = None
        if deadline and self.sim.deadline_factor > 0:
            finite = r[np.isfinite(r)]
            deadline_s = self.sim.deadline_factor * float(np.median(finite))
            mask &= r <= deadline_s

        # residency-aware compute placement: the MUs whose shards actually
        # train this round (the slot sources) set each cluster's compute
        # time — a shard that moved clusters brings its HOST MU's speed
        # multiplier along, so straggler behavior follows the data instead
        # of the (possibly stale) radio membership
        src = None
        if self.residency is not None:
            src = self._slot_sources(None if mask.all() else mask)

        # cluster iteration time over the SURVIVING MUs only
        sizes = self.fleet.cluster_sizes()
        surv = np.bincount(cid[mask], minlength=N)
        min_rate = np.full(N, np.inf)
        np.minimum.at(min_rate, cid[mask], rate_flat[mask])
        if src is not None:
            # max is idempotent: duplicate slot sources reduce the same as
            # the historical np.unique pass
            valid = src >= 0
            comp_src = np.where(valid, comp[np.where(valid, src, 0)], -np.inf)
            comp_term = np.where(valid.any(axis=1), comp_src.max(axis=1), 0.0)
        else:
            comp_term = np.full(N, -np.inf)
            np.maximum.at(comp_term, cid[mask], comp[mask])
        if self.sim.rate_model != "single":
            # a dropped/unavailable MU's sub-carriers are reclaimed: re-run
            # the max-min allocation (Alg. 2) over each AFFECTED cluster's
            # survivors with the cluster's full budget, so they inherit the
            # bandwidth instead of leaving it dark
            affected = np.nonzero((surv > 0) & (surv < sizes))[0]
            if affected.size:
                from repro.wireless.subcarrier import reallocate_after_drop

                for n in affected:
                    members = self.fleet.cluster_members(n)
                    d = self.topo.dist_to_sbs(
                        self.fleet.pos[members], cid[members])
                    rates = reallocate_after_drop(
                        d, mask[members], aux["m_cluster"],
                        B0=lp.B0, Pmax=lp.p_mu, N0=lp.n0,
                        alpha=lp.alpha, ber=lp.ber)
                    min_rate[n] = rates[mask[members]].min()
        it_n = np.where(
            surv > 0, ul_pay / min_rate + aux["gamma_dl"] + comp_term, 0.0)
        iter_s = float(it_n.max()) if it_n.max() > 0 else self.sim.base_compute_s
        sync_s = float(aux["theta_u"] + aux["theta_d"] + aux["gamma_dl"].max())

        keep_clusters = None
        if not self._oversub:
            # static data layout: MU k trains in cluster k // mus_per_cluster
            keep_clusters = mask.reshape(N, hfl.mus_per_cluster).any(axis=1)
        ctx = dict(
            iter_s=iter_s, sync_s=sync_s,
            mask=None if mask.all() else mask,
            keep_clusters=(None if keep_clusters is None or keep_clusters.all()
                           else keep_clusters),
            dropped=int((~mask).sum()),
            participants=int(mask.sum()),
            deadline_s=deadline_s,
        )
        if self.obs.enabled:
            # per-cluster phase decomposition for the trace viz (time
            # only, clamped to surviving clusters; payload bits ride the
            # link spans, one per ledger record)
            with np.errstate(divide="ignore", invalid="ignore"):
                ctx["phases"] = {
                    "surv": surv,
                    "comp": np.where(surv > 0, comp_term, 0.0),
                    "ul": np.where(surv > 0, ul_pay / min_rate, 0.0),
                    "dl": np.where(surv > 0, aux["gamma_dl"], 0.0),
                }
        if src is not None:
            # accounting charges the DISTINCT shards that actually train
            ctx["src"] = src
            ctx["participants"] = int(sum(
                np.unique(row[row >= 0]).size for row in src))
            ctx["active_clusters"] = int((src[:, 0] >= 0).sum())
        return ctx

    def _advance_fleet(self, dt: float, now: Optional[float] = None) -> None:
        """Advance positions (waypoint integration or trace replay),
        re-associate to the nearest SBS, propagate the new association to
        the residency tracker, and invalidate the cached radio pricing.

        With ``sim.reprice_interval_s > 0`` motion is batched: deferred
        virtual time accumulates until the interval elapses, then one
        advance/re-associate/re-price covers it all (positions integrate
        the full accumulated budget, so distance travelled is conserved).
        0 keeps the legacy every-event cadence bit-identically.

        ``now`` is the virtual time of the triggering event: with telemetry
        on, each effective advance lands as a ``reprice`` instant on the
        fleet track carrying the covered motion and re-association count.
        """
        if self.fleet is None or not self.fleet.mobile:
            return
        if self.sim.reprice_interval_s > 0:
            self._move_accum += dt
            if self._move_accum < self.sim.reprice_interval_s:
                return
            dt, self._move_accum = self._move_accum, 0.0
        spans = self.obs.enabled and now is not None
        old_cid = self.fleet.cid.copy() if spans else None
        self.fleet.advance(dt)
        self.fleet.reassociate()
        if self.residency is not None:
            self.residency.update(self.fleet.cid)
        self._aux = None  # positions changed: re-price the radio
        self._crt = None  # per-cluster round times follow the pricing
        if spans:
            moved = int((self.fleet.cid != old_cid).sum())
            self.obs.tracer.instant(
                "reprice", track="fleet", t=now,
                args={"dt_s": dt, "reassociations": moved})
            self.obs.registry.counter("sim.reprices").inc()
            self.obs.registry.counter("sim.reassociations").inc(moved)
            self.obs.health.ingest_churn(moved, t=now)

    # --- data residency ---------------------------------------------------

    def _slot_sources(self, mask: Optional[np.ndarray]) -> np.ndarray:
        """Source MU id per (cluster, slot) under the residency map [N, mpc].

        Slot ``(n, j)`` is filled by cycling over cluster ``n``'s available
        resident MUs (mirroring ``_apply_participation``'s resample rule);
        a ``-1`` row marks a cluster with no available resident shard —
        it sits the round out. A deterministic per-round rotation spreads
        the selection over ALL residents when a cluster holds more shards
        than slots (the duplicate policy's steady state; a fixed start
        would train the lowest-id shards forever).
        """
        N, mpc = self.hfl.num_clusters, self.hfl.mus_per_cluster
        src = np.full((N, mpc), -1, np.int64)
        off = self._slot_rot
        self._slot_rot += 1
        # one CSR pass over the (availability-masked) holds matrix replaces
        # N per-cluster member scans; each cluster's candidate slice is the
        # same ascending id list the scans produced, so the cycled fill is
        # bit-identical
        cols, starts = self.residency.members_csr(mask)
        sizes = np.diff(starts)
        has = sizes > 0
        if has.any():
            idx = (np.arange(mpc)[None, :] + off * mpc) \
                % np.maximum(sizes, 1)[:, None]
            # gather only the non-empty rows (an empty cluster's start can
            # sit one past the end of cols)
            src[has] = cols[(starts[:-1, None] + idx)[has]]
        return src

    def _gather_batch(self, batch, src: np.ndarray):
        """Rebuild the [N, localB] batch so cluster ``n``'s rows come from
        its resident MUs' data slots (MU k's rows live at
        ``[k // mpc, (k % mpc)*bpm : (k % mpc + 1)*bpm]`` of the generated
        batch). -> (batch, keep) with ``keep`` a bool[N] mask of clusters
        that have resident data (None when all do).

        Under the ``duplicate`` residency policy the gathered batch also
        carries ``row_weight`` [N, localB]: ``1/n_copies`` of each row's
        source shard (``ResidencyTracker.shard_weights``), which the loss
        (``launch.steps.make_loss_fn``) applies as a weighted mean — so a
        shard replicated into c clusters still contributes one shard's
        worth of gradient to the cluster sum, not c.
        """
        leaves = jax.tree.leaves(batch)
        if not leaves or leaves[0].ndim < 2:
            return batch, None
        N, mpc = self.hfl.num_clusters, self.hfl.mus_per_cluster
        localB = leaves[0].shape[1]
        if self._oversub:
            # fleet-scale (cluster-subsampled) batches: the generated
            # [N, localB] rows carry no per-MU identity — there are more
            # shards than data slots — so the subsampled slots train on the
            # cluster's rows as-is while ``src`` still drives pricing,
            # accounting, idling and the duplicate-policy row weights
            keep = src[:, 0] >= 0
            out = batch
            if (isinstance(batch, dict) and localB % mpc == 0
                    and self.residency.policy == "duplicate"):
                w_slot = np.where(
                    src >= 0,
                    self.residency.shard_weights_at(np.maximum(src, 0)), 1.0)
                out = dict(batch)
                out["row_weight"] = jnp.asarray(
                    np.repeat(w_slot, localB // mpc, axis=1), jnp.float32)
            return out, (None if keep.all() else keep)
        if localB % mpc:
            return batch, None  # unknown row layout; leave untouched
        bpm = localB // mpc
        keep = src[:, 0] >= 0
        static = (np.arange(N) * mpc)[:, None] + np.arange(mpc)[None, :]
        srcf = np.where(src >= 0, src, static)  # kept-out rows: identity
        cl = np.repeat(srcf // mpc, bpm, axis=1)  # [N, localB]
        row = (np.repeat((srcf % mpc) * bpm, bpm, axis=1)
               + np.tile(np.arange(bpm), (N, mpc)))
        clj, rowj = jnp.asarray(cl), jnp.asarray(row)
        take = lambda leaf: leaf[clj, rowj] if leaf.ndim >= 2 else leaf
        out = jax.tree.map(take, batch)
        if (isinstance(out, dict) and self.residency is not None
                and self.residency.policy == "duplicate"):
            w = np.repeat(self.residency.shard_weights()[srcf], bpm, axis=1)
            out["row_weight"] = jnp.asarray(w, jnp.float32)
        return out, (None if keep.all() else keep)

    def _gather_row(self, batch, src_n: np.ndarray, n: int):
        """Row-only variant of ``_gather_batch`` for the masked path:
        cluster ``n``'s [localB] rows gathered from its resident MUs' data
        slots, without materializing the N-1 clusters the masked step
        would immediately discard. ``src_n`` must have no -1 entries
        (the caller idles those rounds)."""
        leaves = jax.tree.leaves(batch)
        take_row = lambda leaf: (leaf[n] if getattr(leaf, "ndim", 0) >= 2
                                 else leaf)
        if not leaves or leaves[0].ndim < 2:
            return jax.tree.map(take_row, batch)
        mpc = self.hfl.mus_per_cluster
        localB = leaves[0].shape[1]
        if self._oversub:
            # see _gather_batch: subsampled slots train on the cluster's
            # generated rows, weighted by their source shards' copy counts
            out = jax.tree.map(take_row, batch)
            if (isinstance(out, dict) and localB % mpc == 0
                    and self.residency.policy == "duplicate"):
                w = np.repeat(self.residency.shard_weights_at(src_n),
                              localB // mpc)
                out["row_weight"] = jnp.asarray(w, jnp.float32)
            return out
        if localB % mpc:
            return jax.tree.map(take_row, batch)  # unknown layout: slice
        bpm = localB // mpc
        cl = np.repeat(src_n // mpc, bpm)  # [localB]
        row = np.repeat((src_n % mpc) * bpm, bpm) + np.tile(np.arange(bpm), mpc)
        clj, rowj = jnp.asarray(cl), jnp.asarray(row)
        take = lambda leaf: leaf[clj, rowj] if leaf.ndim >= 2 else leaf
        out = jax.tree.map(take, batch)
        if (isinstance(out, dict) and self.residency is not None
                and self.residency.policy == "duplicate"):
            w = np.repeat(self.residency.shard_weights()[src_n], bpm)
            out["row_weight"] = jnp.asarray(w, jnp.float32)
        return out

    def _apply_participation(self, batch, mask: Optional[np.ndarray]):
        """Resample dropped MUs' batch rows from their cluster's survivors."""
        if mask is None:
            return batch
        N, mpc = self.hfl.num_clusters, self.hfl.mus_per_cluster
        leaves = jax.tree.leaves(batch)
        if not leaves or leaves[0].ndim < 2:
            return batch
        localB = leaves[0].shape[1]
        if localB % mpc:
            return batch  # unknown row layout; leave the batch untouched
        bpm = localB // mpc
        idx = np.tile(np.arange(localB)[None], (N, 1))
        for n in range(N):
            kept = [j for j in range(mpc) if mask[n * mpc + j]]
            if not kept or len(kept) == mpc:
                continue
            src = [kept[j % len(kept)] for j in range(mpc)]
            idx[n] = np.concatenate(
                [np.arange(s * bpm, (s + 1) * bpm) for s in src]
            )
        idxj = jnp.asarray(idx)
        rowsel = jnp.arange(N)[:, None]
        take = lambda leaf: leaf[rowsel, idxj] if leaf.ndim >= 2 else leaf
        return jax.tree.map(take, batch)

    # --- byte accounting --------------------------------------------------

    def _count_train(self, participants: Optional[int], clusters: int):
        """-> ``(ul_bits, dl_bits)`` charged to the access links this
        launch (zeros in null-wireless mode). Measured mode returns the
        ledger's own recorded floats so the caller's link spans mirror the
        books exactly (the teardown conservation check is bit-for-bit)."""
        self._train_launches += 1
        if not self.wireless:
            return 0.0, 0.0
        p = self.fleet.K if participants is None else participants
        if self.ledger is not None:
            # access links are never materialized by the fused train step:
            # measured mode charges the codec on synthetic exact-k payloads
            ul = self.ledger.record("mu_ul", p * self._ab["mu_ul"], events=p)
            dl = self.ledger.record(
                "sbs_dl", clusters * self._ab["sbs_dl"], events=clusters
            )
        else:
            lp, hfl = self.lp, self.hfl
            ul = p * lp.payload(hfl.tiers[0].phi_up)
            dl = clusters * lp.payload(hfl.tiers[0].phi_down)
        self._bits_access += ul + dl
        return ul, dl

    def _count_sync(self, clusters: int):
        """Analytic fronthaul charge -> ``(ul_bits, dl_bits)``."""
        self._sync_launches += 1
        if not self.wireless:
            return 0.0, 0.0
        lp, hfl = self.lp, self.hfl
        ul = clusters * lp.payload(hfl.tiers[1].phi_up)
        dl = lp.payload(hfl.tiers[1].phi_down)
        self._bits_fronthaul += ul + dl
        return ul, dl

    def _count_sync_hier(self, top: int):
        """Analytic fronthaul charge of one tiered-consensus boundary up to
        tier ``top`` -> ``(ul_bits, dl_bits)``: each firing tier t prices
        ``A_{t-1}`` child uplinks and ``A_t`` parent downlinks at that tier
        boundary's link payloads (``latency.tier_payload_bits``; the
        depth-2 ``top=1`` instance is exactly ``_count_sync(N)``)."""
        self._sync_launches += 1
        if not self.wireless:
            return 0.0, 0.0
        from repro.comm.accounting import boundary_links
        from repro.wireless.latency import tier_payload_bits

        hfl = self.hfl
        pb = tier_payload_bits(self.lp, hfl.tiers)
        ul = dl = 0.0
        for ti in range(1, top + 1):
            ul_l, dl_l = boundary_links(ti)
            ul += hfl.agg_count(ti - 1) * pb[ul_l]
            dl += hfl.agg_count(ti) * pb[dl_l]
        self._bits_fronthaul += ul + dl
        return ul, dl

    def _hier_sync_extra_s(self, top: int) -> float:
        """Serial fronthaul time the tiers ABOVE the SBS ring add to one
        boundary (tier 1's θ^U/θ^D already live in ``ctx['sync_s']``):
        every extra hop ships its Ω payload pair over the fronthaul rate."""
        if not self.wireless or top < 2:
            return 0.0
        aux = self._latency_aux()
        from repro.comm.accounting import boundary_links
        from repro.wireless.latency import tier_payload_bits

        pb = tier_payload_bits(self.lp, self.hfl.tiers)
        extra = 0.0
        for ti in range(2, top + 1):
            ul_l, dl_l = boundary_links(ti)
            extra += (pb[ul_l] + pb[dl_l]) / aux["fh_rate"]
        return extra

    def _count_sync_unit(self, utop: int, cut: int):
        """Analytic fronthaul charge of ONE unit's consensus cascade up to
        tier ``utop`` — the within-unit slice of ``_count_sync_hier``:
        boundary t prices its subtree's child uplinks at ``phi_up`` and
        parent downlinks at ``phi_down``. The depth-3 ``utop=1, cut=2``
        instance is the historical single-edge tier-1 consensus charge."""
        self._sync_launches += 1
        if not self.wireless:
            return 0.0, 0.0
        lp, tiers = self.lp, self.hfl.tiers

        def width(j: int) -> int:  # tier-j aggregators per unit
            out = 1
            for k in range(j + 1, cut):
                out *= tiers[k].fanout
            return out

        ul = dl = 0.0
        for ti in range(1, utop + 1):
            ul += width(ti - 1) * lp.payload(tiers[ti].phi_up)
            dl += width(ti) * lp.payload(tiers[ti].phi_down)
        self._bits_fronthaul += ul + dl
        return ul, dl

    def _count_sync_push(self, t: int):
        """Analytic fronthaul charge of one async push across tier
        boundary ``t``: Ω uplink at the tier's ``phi_up``, dense reference
        adoption downlink (the child pulls the parent's whole reference)."""
        self._sync_launches += 1
        if not self.wireless:
            return 0.0, 0.0
        tc = self.hfl.tiers[t]
        ul = self.lp.payload(tc.phi_up)
        dl = self.lp.payload(0.0)  # dense adoption ships the raw reference
        self._bits_fronthaul += ul + dl
        return ul, dl

    def _measure_sync_hier(self, state, hbufs, top: int):
        """Measure the REAL per-boundary payloads of one tiered consensus
        (depth > 2 measured accounting) -> ``(ul_bits, dl_bits, sync_s,
        bcast_bits, legs, row_bits)``. The hier probe re-runs the
        cascade's Ω selection on the same ``(state, bufs)``; each
        boundary's payloads land on ITS ledger links (boundary 1 keeps the
        historic ``sbs_ul``/``mbs_dl`` names, boundary t >= 2 uses
        ``t{t}_ul``/``t{t}_dl``), the sync time is re-priced from the
        actual bits — the slowest child of each boundary fans in over the
        fronthaul, every boundary a serial hop pair — and the
        post-consensus SBS->MU broadcast ships each cluster's ACTUAL
        adopted tier-1 delta at its realized DL rate. ``legs`` carries
        (link, bits, dur) span pairs holding exactly the ledger-recorded
        floats, so the span/ledger conservation bugcheck is bit-for-bit."""
        from repro.comm.accounting import boundary_links

        uls, dls = self._probe(state, hbufs, top)
        self._sync_launches += 1
        aux = self._latency_aux()
        legs = []
        row_bits = {}
        ul_tot = dl_tot = sync_s = 0.0
        for ti in range(1, top + 1):
            ub = np.asarray(uls[ti - 1], np.float64)
            db = np.asarray(dls[ti - 1], np.float64)
            ul_l, dl_l = boundary_links(ti)
            u_rec = self.ledger.record(ul_l, float(ub.sum()),
                                       events=int(ub.size))
            d_rec = self.ledger.record(dl_l, float(db.sum()),
                                       events=int(db.size))
            ul_tot += u_rec
            dl_tot += d_rec
            u_dur = float(ub.max()) / aux["fh_rate"]
            d_dur = float(db.max()) / aux["fh_rate"]
            sync_s += u_dur + d_dur
            legs.append((ul_l, u_rec, u_dur, dl_l, d_rec, d_dur))
            row_bits[f"bits_{ul_l}"] = u_rec
            row_bits[f"bits_{dl_l}"] = d_rec
        self._bits_fronthaul += ul_tot + dl_tot
        # post-consensus broadcast: cluster n adopts its tier-1
        # aggregator's delta (its dls[0] row) and re-broadcasts it to its
        # MUs; clusters mobility has emptied report dl_rate=inf (no
        # broadcast time, no audience) and are charged neither
        db0 = np.asarray(dls[0], np.float64)
        per_cluster = np.repeat(db0, self.hfl.tiers[1].fanout)
        finite = np.isfinite(aux["dl_rates"])
        t_bcast = np.where(finite, per_cluster / aux["dl_rates"], 0.0)
        n_bcast = int(finite.sum())
        bcast_b = None
        if n_bcast:
            bcast_b = self.ledger.record(
                "sbs_dl", float(per_cluster[finite].sum()), events=n_bcast)
            self._bits_access += bcast_b
            sync_s += float(t_bcast[finite].max())
        row_bits["bits_sync_bcast"] = (
            float(per_cluster[finite].sum()) if n_bcast else 0.0)
        return ul_tot, dl_tot, sync_s, bcast_b, legs, row_bits

    def _count_sync_measured(self, ul_bits, dl_bits: float):
        """Record the REAL fronthaul payload bits of one sync event
        -> the ledger's recorded ``(ul_bits, dl_bits)`` floats."""
        self._sync_launches += 1
        ul_bits = np.atleast_1d(np.asarray(ul_bits, np.float64))
        ul = self.ledger.record("sbs_ul", float(ul_bits.sum()),
                                events=len(ul_bits))
        dl = self.ledger.record("mbs_dl", float(dl_bits))
        self._bits_fronthaul += ul + dl
        return ul, dl

    def _totals(self) -> dict:
        out = {
            "train_launches": self._train_launches,
            "sync_launches": self._sync_launches,
            "bits_access_total": self._bits_access,
            "bits_fronthaul_total": self._bits_fronthaul,
        }
        if self.ledger is not None:
            out.update(self.ledger.summary())
        return out

    def _finish_run(self) -> None:
        """Engine teardown: final registry totals, then the span/ledger
        payload-bit conservation bugcheck (measured accounting) — every
        link's span bits must equal the ledger's total bit-for-bit."""
        if not self.obs.enabled:
            return
        reg = self.obs.registry
        reg.counter("sim.train_launches").inc(self._train_launches)
        reg.counter("sim.sync_launches").inc(self._sync_launches)
        reg.counter("sim.bits_access").inc(self._bits_access)
        reg.counter("sim.bits_fronthaul").inc(self._bits_fronthaul)
        part, seen = self._rounds_part, self._rounds_seen
        if part is not None and int(seen.sum()) > 0:
            rate = part / np.maximum(seen, 1)
            for n in range(part.size):
                reg.gauge("sim.participation_rate").set(
                    float(rate[n]), cluster=f"c{n}")
            # drop-fairness: Gini over rounds contributed (0 = every
            # cluster trained equally often, ->1 = one cluster hogs)
            x = np.sort(part.astype(np.float64))
            k, s = x.size, float(x.sum())
            gini = 0.0 if s <= 0 or k < 2 else float(
                2.0 * np.sum(np.arange(1, k + 1) * x) / (k * s)
                - (k + 1) / k)
            reg.gauge("sim.drop_gini").set(gini)
        if self.ledger is not None:
            self.obs.check_conservation(self.ledger)

    def _mark_round(self, n: int, participated: bool, t: float) -> None:
        """Per-cluster round outcome under async (obs on only): feeds the
        participation/Gini tallies and the dead-cluster health signal."""
        if self._rounds_seen is None:
            return
        self._rounds_seen[n] += 1
        if participated:
            self._rounds_part[n] += 1
        self.obs.health.ingest_cluster_round(int(n), participated, t=t)

    # --- span emission (telemetry on only; never touches sim state) ------

    def _trace_train_step(self, step: int, t0: float, ctx: dict,
                          ul_bits: float, dl_bits: float) -> None:
        """Virtual-clock spans of one lockstep training iteration: the
        engine-track iter span, per-cluster compute/UL/DL phase spans, and
        the two access-link payload spans (bits = the ledger's floats)."""
        tr = self.obs.tracer
        dur = ctx["iter_s"]
        tr.span("iter", track="engine", t0=t0, dur=dur,
                args={"step": step, "dropped": ctx["dropped"],
                      "participants": ctx["participants"]})
        ph = ctx.get("phases")
        if ph is not None:
            for n in np.nonzero(ph["surv"] > 0)[0]:
                tt = t0
                for phase in ("comp", "ul", "dl"):
                    d = float(ph[phase][n])
                    tr.span(phase, track=f"cluster{int(n)}", t0=tt, dur=d)
                    tt += d
        if self.wireless:
            tr.link_span("mu_ul", t0=t0, dur=dur, bits=ul_bits,
                         name="train_ul",
                         args={"participants": ctx["participants"]})
            tr.link_span("sbs_dl", t0=t0, dur=dur, bits=dl_bits,
                         name="train_dl")

    def _trace_sync(self, step: int, t0: float, sync_s: float,
                    ul_bits: float, dl_bits: float, bcast_bits,
                    fh_parts, extra: dict, legs=None) -> None:
        """Virtual-clock spans of one global consensus: the engine-track
        sync span plus fronthaul UL/DL link spans and (measured mode) the
        repriced SBS->MU broadcast span. ``fh_parts`` carries the measured
        per-leg durations; the analytic path falls back to the aux θ's.
        ``legs`` (depth > 2 measured) replaces the fixed fronthaul pair
        with one tier-labeled span pair per cascade boundary, each
        carrying exactly the ledger-recorded bits (the span/ledger
        conservation bugcheck is bit-for-bit), laid out serially up the
        tree."""
        tr = self.obs.tracer
        tr.span("sync", track="engine", t0=t0, dur=sync_s,
                args={"step": step, **extra})
        if not self.wireless:
            return
        if legs is not None:
            tt = t0
            for ul_l, ub, ud, dl_l, db, dd in legs:
                tr.link_span(ul_l, t0=tt, dur=ud, bits=ub, name="sync_ul")
                tt += ud
                tr.link_span(dl_l, t0=tt, dur=dd, bits=db, name="sync_dl")
                tt += dd
            if bcast_bits is not None:
                tr.link_span("sbs_dl", t0=tt,
                             dur=max(sync_s - (tt - t0), 0.0),
                             bits=bcast_bits, name="sync_bcast")
            return
        if fh_parts is not None:
            fh_ul, fh_dl, t_bc = fh_parts
        else:
            aux = self._latency_aux()
            fh_ul, fh_dl = float(aux["theta_u"]), float(aux["theta_d"])
            t_bc = max(sync_s - fh_ul - fh_dl, 0.0)
        tr.link_span("sbs_ul", t0=t0, dur=fh_ul, bits=ul_bits,
                     name="sync_ul")
        tr.link_span("mbs_dl", t0=t0 + fh_ul, dur=fh_dl, bits=dl_bits,
                     name="sync_dl")
        if bcast_bits is not None:
            tr.link_span("sbs_dl", t0=t0 + fh_ul + fh_dl, dur=t_bc,
                         bits=bcast_bits, name="sync_bcast")

    # --- lockstep / deadline ---------------------------------------------

    def _price_sync(self, step: int, state, hbufs, ctx: dict, sync_step,
                    hier: bool, N):
        """The virtual-clock price of the sync at the end of ``step``,
        measured from the state before the donating sync step consumes it:
        -> (top, sync_s, sync_ul, sync_dl, bcast_b, fh_parts, legs,
        row_extra)."""
        sync_s = ctx["sync_s"]
        row_extra = {}
        sync_ul = sync_dl = 0.0
        bcast_b = fh_parts = legs = None
        top = None
        if hier:
            top = sync_step.fire_top((step + 1) // self.period)
            row_extra = {"tier": int(top)}
            if self.ledger is not None:
                # measure the cascade's REAL per-boundary payloads and
                # re-price the whole boundary from the actual bit counts
                (sync_ul, sync_dl, sync_s, bcast_b, legs,
                 row_bits) = self._measure_sync_hier(state, hbufs, top)
                row_extra.update(row_bits)
            else:
                sync_ul, sync_dl = self._count_sync_hier(top)
                sync_s += self._hier_sync_extra_s(top)
        elif self.ledger is not None:
            # measure the REAL fronthaul payloads this sync sends and
            # re-price θ^U/θ^D from the actual bit counts
            ul_b, dl_b = self._probe(state)
            ul_b, dl_b = np.asarray(ul_b, np.float64), float(dl_b)
            sync_ul, sync_dl = self._count_sync_measured(ul_b, dl_b)
            aux = self._latency_aux()
            # the post-consensus SBS->MU broadcast carries the ACTUAL
            # consensus payload (dl_b bits), not the static per-iteration
            # sbs_dl estimate: re-price each cluster's broadcast leg from
            # its realized DL rate and charge the access link for the real
            # bits; clusters mobility has emptied report dl_rate=inf (no
            # broadcast time, no audience): charge neither time nor bits
            # for them
            finite = np.isfinite(aux["dl_rates"])
            t_bcast = np.where(finite, dl_b / aux["dl_rates"], 0.0)
            n_bcast = int(finite.sum())
            if n_bcast:
                bcast_b = self.ledger.record(
                    "sbs_dl", n_bcast * dl_b, events=n_bcast)
                self._bits_access += bcast_b
            sync_s = float(
                (ul_b.max() + dl_b) / aux["fh_rate"]
                + (t_bcast[finite].max() if n_bcast else 0.0)
            )
            row_extra = {"bits_sbs_ul": float(ul_b.sum()),
                         "bits_mbs_dl": dl_b,
                         "bits_sync_bcast": n_bcast * dl_b}
            if self.obs.enabled:
                # viz-only leg durations; sync_s itself stays the single
                # fused expression above (bit-identity)
                fh_parts = (
                    float(ul_b.max()) / aux["fh_rate"],
                    dl_b / aux["fh_rate"],
                    float(t_bcast[finite].max()) if n_bcast else 0.0,
                )
        else:
            sync_ul, sync_dl = self._count_sync(N if N is not None else 1)
        return (top, sync_s, sync_ul, sync_dl, bcast_b, fh_parts, legs,
                row_extra)

    def _run_lockstep(
        self, state, train_step, sync_step, batches, num_steps, on_step,
        *, deadline: bool,
    ):
        H = self.period
        it = iter(batches)
        trace = Trace(meta=self._meta())
        t = 0.0
        ctx: dict = {}
        N = self.hfl.num_clusters if self.hfl is not None else None
        # health stats ride the sync step only when BOTH the monitor is on
        # and the caller built the sync with collect_stats (jit_sync_step
        # propagates the flag onto the jitted callable)
        stats_on = (self.obs.health.enabled
                    and bool(getattr(sync_step, "collect_stats", False)))
        # depth > 2: the tiered sync threads its own side buffers and fires
        # a variable-height boundary (hier_fire_top) each period
        hier = bool(getattr(sync_step, "hier", False))
        hbufs = sync_step.init_bufs(state) if hier else None
        for step in range(num_steps):
            with self.obs.host_span("hfl.step", step=step):
                if step % H == 0:
                    # _round_ctx draws the slot sources itself (residency
                    # runs) so compute pricing can follow the resident
                    # shards; the virtual clock feeds the diurnal
                    # availability curve
                    with self.obs.host_span("hfl.round"):
                        self._vt = t
                        ctx = self._round_ctx(deadline)
                    if self._rounds_seen is not None:
                        src = ctx.get("src")
                        if src is not None:
                            part = src[:, 0] >= 0
                        elif ctx["keep_clusters"] is not None:
                            part = np.asarray(ctx["keep_clusters"], bool)
                        else:
                            part = np.ones(N, bool)
                        self._rounds_seen += 1
                        self._rounds_part += part
                        self.obs.health.ingest_round(part, t=t)
                with self.obs.host_span("hfl.batch"):
                    if self.residency is not None:
                        batch, keep = self._gather_batch(next(it),
                                                         ctx["src"])
                    else:
                        batch = self._apply_participation(next(it),
                                                          ctx["mask"])
                        keep = ctx["keep_clusters"]
                with self.obs.host_span("hfl.train"):
                    new_state, loss = train_step(state, batch)
                if keep is not None:
                    state = _merge_clusters(state, new_state, keep)
                else:
                    state = new_state
                t_iter0 = t
                t += ctx["iter_s"]
                ul_b, dl_b = self._count_train(
                    ctx["participants"],
                    ctx.get("active_clusters", N if N is not None else 1))
                if self.obs.enabled:
                    self._trace_train_step(step, t_iter0, ctx, ul_b, dl_b)
                if self._record or self.obs.health.enabled:
                    loss_mean = float(jnp.mean(loss))
                    self.obs.health.ingest_loss(loss_mean, t=t)
                    if self._record:
                        trace.add(kind="train", t=t, step=step, loss=loss_mean,
                                  dropped=ctx["dropped"])
                if (step + 1) % H == 0:
                    with self.obs.host_span("hfl.sync"):
                        (top, sync_s, sync_ul, sync_dl, bcast_b, fh_parts,
                         legs, row_extra) = self._price_sync(
                             step, state, hbufs, ctx, sync_step, hier, N)
                        if hier:
                            state, hbufs = sync_step(state, hbufs, top)
                        elif stats_on:
                            state, sstats = sync_step(state)
                        else:
                            state = sync_step(state)
                    t_sync0 = t
                    t += sync_s
                    if self.obs.enabled:
                        self._trace_sync(step, t_sync0, sync_s, sync_ul,
                                         sync_dl, bcast_b, fh_parts, row_extra,
                                         legs=legs)
                    if stats_on:
                        self.obs.health.ingest_sync_stats(sstats, t=t)
                        self.obs.health.ingest_payload(sync_ul + sync_dl, t=t)
                    if self._record:
                        trace.add(kind="sync", t=t, step=step,
                                  dropped=ctx["dropped"],
                                  deadline_s=ctx["deadline_s"],
                                  iter_s=ctx["iter_s"], sync_s=sync_s,
                                  **row_extra)
                    self._advance_fleet(H * ctx["iter_s"] + sync_s, now=t)
                if on_step is not None:
                    with self.obs.host_span("hfl.on_step"):
                        on_step(step, state, loss)
                self.obs.tick()
        self._finish_run()
        trace.meta.update(self._totals())
        return state, trace

    # --- async ------------------------------------------------------------

    def _cluster_round_times(self, comp: Optional[np.ndarray]) -> np.ndarray:
        """Async round times for ALL clusters at the current pricing [N],
        cached until the fleet moves: one scatter-max over the resident (or
        radio) membership replaces the historical per-event member scan, so
        scheduling an event is O(1) in the fleet size."""
        if self._crt is not None:
            return self._crt
        N = self.hfl.num_clusters
        if not self.wireless:
            self._crt = np.full(N, self.period * self.sim.base_compute_s)
            return self._crt
        aux = self._latency_aux()
        # compute follows the DATA: with a residency tracker the round's
        # trainers are the resident shards' host MUs, whose speed
        # multipliers price the round (radio terms stay with the radio)
        if self.residency is not None:
            cols, starts = self.residency.members_csr()
            counts = np.diff(starts)
            comp_n = np.full(N, -np.inf)
            np.maximum.at(comp_n, np.repeat(np.arange(N), counts), comp[cols])
        else:
            counts = self.fleet.cluster_sizes()
            comp_n = self.fleet.cluster_comp_max(self.sim.base_compute_s)
        comp_n = np.where(counts > 0, comp_n, self.sim.base_compute_s)
        g = aux["gamma_ul"] + aux["gamma_dl"]
        self._crt = (self.period * (comp_n + g)
                     + aux["theta_u"] + aux["theta_d"])
        return self._crt

    def _cluster_round_time(self, n: int, comp: Optional[np.ndarray]) -> float:
        return float(self._cluster_round_times(comp)[n])

    def _run_async(self, state, train_step, batches, num_steps, on_step,
                   masked_train_step=None):
        hfl = self.hfl
        if hfl is None:
            raise ValueError("async discipline needs hfl_cfg")
        N, H = hfl.num_clusters, self.period
        rounds = num_steps // H
        trace = Trace(meta=self._meta())
        if rounds == 0:
            trace.meta.update(self._totals())
            return state, trace
        it = iter(batches)
        q = EventQueue()
        dl_sparse = bool(getattr(hfl, "async_dl_sparse", False))
        measured = self.ledger is not None
        stats_on = self.obs.health.enabled
        sync_n = make_async_sync_step(
            hfl, dl_sparse=dl_sparse,
            codec=self._codec if measured else None,
            collect_stats=stats_on,
        )
        e_dl = init_dl_error(state, hfl) if dl_sparse else None
        comp = (
            self.fleet.compute_times(self.sim.base_compute_s)
            if self.fleet is not None else None
        )
        for n in range(N):
            q.push(self._cluster_round_time(n, comp),
                   Event("cluster_done", cluster=n, round=0))
        global_updates = 0
        last_pull = [0] * N
        steps_done = 0
        fleet_time = 0.0
        mpc = hfl.mus_per_cluster
        # per-cluster round start times (virtual): round r of cluster n
        # occupies [round_t0[n], its pop time]; tracked for the trace spans
        round_t0 = np.zeros(N)
        while len(q):
            t, ev = q.pop()
            n = ev.cluster
            if self.fleet is not None and self.fleet.mobile:
                self._advance_fleet(t - fleet_time, now=t)
                fleet_time = t
            # availability trace (dropout): unavailable MUs in this cluster's
            # data slots — static layout, or the resident shards when a
            # residency tracker is attached — sit the round out (their rows
            # are resampled from the survivors); a cluster with no available
            # data idles the whole round. Round *times* are not
            # availability-adjusted.
            mask = None
            src = None
            dropped = 0
            n_res = 0
            self._vt = t
            avail = (self.fleet.draw_available(t)
                     if self.fleet is not None and self.fleet.dropout > 0
                     else None)
            fault = getattr(self.sim, "fault_dead_cluster", None)
            if fault is not None and self.fleet is not None:
                # post-draw fault masking, same contract as _round_ctx
                if avail is None:
                    avail = np.ones(self.fleet.K, bool)
                avail = avail & (self.fleet.cid != fault)
            if self.selector is not None:
                if avail is None:
                    avail = np.ones(self.fleet.K, bool)
                avail = self.selector.select(avail, self.fleet, t)
            if self.residency is not None:
                src = self._slot_sources(avail)
                # resident/survivor counts as boolean row sums (the member
                # id lists the historical scan built are never needed here)
                row_n = self.residency.holds[n]
                n_res = int(row_n.sum())
                if avail is not None:
                    dropped = n_res - int((row_n & avail).sum())
                if src[n, 0] < 0:  # no available resident shard this round
                    if self._record:
                        trace.add(kind="idle", t=t, cluster=int(n),
                                  round=int(ev.round), dropped=dropped)
                    if self.obs.enabled:
                        self.obs.tracer.span(
                            "idle", track=f"cluster{n}", t0=round_t0[n],
                            dur=t - round_t0[n],
                            args={"round": int(ev.round), "dropped": dropped})
                    self._mark_round(n, False, t)
                    round_t0[n] = t
                    self.obs.tick()
                    if ev.round + 1 < rounds:
                        q.push(t + self._cluster_round_time(n, comp),
                               Event("cluster_done", cluster=n,
                                     round=ev.round + 1))
                    continue
            elif avail is not None:
                slots = slice(n * mpc, (n + 1) * mpc)
                dropped = int((~avail[slots]).sum())
                if not avail[slots].any():
                    if self._record:
                        trace.add(kind="idle", t=t, cluster=int(n),
                                  round=int(ev.round), dropped=dropped)
                    if self.obs.enabled:
                        self.obs.tracer.span(
                            "idle", track=f"cluster{n}", t0=round_t0[n],
                            dur=t - round_t0[n],
                            args={"round": int(ev.round), "dropped": dropped})
                    self._mark_round(n, False, t)
                    round_t0[n] = t
                    self.obs.tick()
                    if ev.round + 1 < rounds:
                        q.push(t + self._cluster_round_time(n, comp),
                               Event("cluster_done", cluster=n,
                                     round=ev.round + 1))
                    continue
                if dropped:
                    mask = np.ones(self.fleet.K, bool)
                    mask[slots] = avail[slots]
            members = (
                int(self.fleet.cluster_sizes()[n]) if self.fleet is not None
                else hfl.mus_per_cluster
            )
            # access-link accounting charges the MUs whose data actually
            # trains this round: _slot_sources fills at most mpc slots, so
            # under a tracker that is min(available residents, mpc) — the
            # duplicate policy can accrue far more holders than train —
            # and the surviving radio members otherwise
            participants = (min(n_res - dropped, mpc)
                            if self.residency is not None
                            else max(members - dropped, 0))
            # staleness is fixed before this round's own consensus lands
            # (the train loop never touches the global update counter):
            # compute the round's weight up front so the trace's round span
            # is emitted first — per-track span starts stay monotone
            staleness = global_updates - last_pull[n]
            w = async_weight(staleness, N, self.sim.staleness_exp)
            iter_w = sync_tail = 0.0
            if self.obs.enabled:
                # round window [round_t0, t]: H iteration windows plus the
                # θ^U+θ^D sync tail (clamped — pricing may have moved since
                # the round was scheduled); viz decomposition only
                W = t - round_t0[n]
                if self.wireless:
                    aux = self._latency_aux()
                    sync_tail = min(float(aux["theta_u"] + aux["theta_d"]),
                                    W)
                iter_w = max(W - sync_tail, 0.0) / H
                self.obs.tracer.span(
                    "round", track=f"cluster{n}", t0=round_t0[n], dur=W,
                    args={"round": int(ev.round),
                          "staleness": int(staleness),
                          "weight": float(w), "dropped": dropped})
            # state.step feeds step-indexed LR schedules; pin it to THIS
            # cluster's per-round progress (round*H .. round*H + H), not the
            # global launch count, which inflates N-fold under async and
            # would decay the schedule N times too early.
            state = state._replace(step=jnp.asarray(ev.round * H, jnp.int32))
            nj = jnp.int32(n)
            wj = jnp.float32(w)
            loss = None
            for h in range(H):
                batch = next(it)
                if masked_train_step is not None:
                    # masked step: compute ONLY the active cluster (~1/N
                    # the FLOPs of the vmapped step; see core.hfl) — and
                    # gather only ITS rows, not the N-1 it would discard
                    if self.residency is not None:
                        batch_n = self._gather_row(batch, src[n], n)
                    else:
                        batch_n = jax.tree.map(
                            lambda l: (l[n] if getattr(l, "ndim", 0) >= 2
                                       else l),
                            self._apply_participation(batch, mask))
                    with self.obs.host_span("hfl.train"):
                        state, loss = masked_train_step(state, batch_n, nj)
                else:
                    if self.residency is not None:
                        batch, _keep = self._gather_batch(batch, src)
                    else:
                        batch = self._apply_participation(batch, mask)
                    with self.obs.host_span("hfl.train"):
                        new_state, loss = train_step(state, batch)
                    state = _take_cluster_row(state, new_state, n)
                steps_done += 1
                ul_b, dl_b = self._count_train(participants, 1)
                if self.obs.enabled and self.wireless:
                    # async link spans live on the cluster track: rounds
                    # overlap across clusters, so shared link tracks would
                    # break per-track time ordering
                    it0 = round_t0[n] + h * iter_w
                    tr_ = self.obs.tracer
                    tr_.link_span("mu_ul", t0=it0, dur=iter_w, bits=ul_b,
                                  name="train_ul", track=f"cluster{n}")
                    tr_.link_span("sbs_dl", t0=it0, dur=iter_w, bits=dl_b,
                                  name="train_dl", track=f"cluster{n}")
            bits = None
            sstats = None
            with self.obs.host_span("hfl.sync"):
                # variants append (bits?, stats?) after the carried state
                if dl_sparse:
                    out = sync_n(state, e_dl, nj, wj)
                    state, e_dl, rest = out[0], out[1], out[2:]
                elif measured or stats_on:
                    out = sync_n(state, nj, wj)
                    state, rest = out[0], out[1:]
                else:
                    # bare-state return; HFLState is itself a NamedTuple,
                    # so an isinstance(tuple) arity probe would unpack it
                    state, rest = sync_n(state, nj, wj), ()
                if measured:
                    bits, rest = rest[0], rest[1:]
                if stats_on:
                    sstats = rest[0]
            global_updates += 1
            last_pull[n] = global_updates
            if measured:
                # dense adoption pulls the whole reference: static Q bits
                dl_b = (float(bits["mbs_dl"]) if dl_sparse
                        else float(self._ab["dense"]))
                s_ul, s_dl = self._count_sync_measured(
                    [float(bits["sbs_ul"])], dl_b)
            else:
                s_ul, s_dl = self._count_sync(1)
            if self.obs.enabled:
                self.obs.registry.histogram("sim.staleness").observe(
                    float(staleness), cluster=f"c{n}")
            if sstats is not None:
                self.obs.health.ingest_async_sync_stats(
                    sstats, n, staleness, t=t)
                self.obs.health.ingest_payload(s_ul + s_dl, t=t)
            self._mark_round(n, True, t)
            if self.obs.enabled:
                tr_ = self.obs.tracer
                t_s0 = t - sync_tail
                tr_.span("sync", track=f"cluster{n}", t0=t_s0,
                         dur=sync_tail,
                         args={"round": int(ev.round),
                               "staleness": int(staleness),
                               "weight": float(w)})
                if self.wireless:
                    tr_.link_span("sbs_ul", t0=t_s0, dur=sync_tail,
                                  bits=s_ul, name="sync_ul",
                                  track=f"cluster{n}")
                    tr_.link_span("mbs_dl", t0=t_s0, dur=sync_tail,
                                  bits=s_dl, name="sync_dl",
                                  track=f"cluster{n}")
            if self._record or stats_on:
                # the ACTIVE cluster's loss: the vmapped fallback computes
                # all N rows but only row n was merged (the masked step
                # returns row n's scalar directly)
                loss_n = float(loss if jnp.ndim(loss) == 0 else loss[n])
                self.obs.health.ingest_loss(loss_n, t=t)
                if self._record:
                    trace.add(kind="sync", t=t, step=steps_done - 1,
                              cluster=int(n), round=int(ev.round),
                              staleness=int(staleness), weight=float(w),
                              dropped=dropped, loss=loss_n)
            if on_step is not None:
                on_step(steps_done - 1, state, loss)
            if ev.round + 1 < rounds:
                q.push(t + self._cluster_round_time(n, comp),
                       Event("cluster_done", cluster=n, round=ev.round + 1))
            round_t0[n] = t
            self.obs.tick()
        self._finish_run()
        trace.meta.update(self._totals())
        return state, trace

    # --- mixed-discipline hierarchy: async boundaries above a cut ----------

    def _run_units(self, state, train_step, sync_step, batches, num_steps,
                   on_step, *, cut: int):
        """Tier-recursive async scheduler: every tier boundary at or above
        ``cut`` runs clock-free, everything below stays lockstep. The
        subtree under one tier-``cut-1`` aggregator is a scheduling
        **unit** (the depth-3 async-root "edge"): it runs tier-1 rounds on
        its own clock — H intra-cluster iterations of ITS clusters, then
        its within-unit consensus cascade (boundaries ``1..cut-1`` at
        their lockstep cadences) — and every ``prod(tiers[2..cut].
        period)`` unit-rounds pushes its reference across the cut with a
        staleness-discounted weight (``async_weight`` over the
        ``tiers[cut].fanout`` siblings). A push landing on a parent may
        cascade further up: boundary ``t > cut`` fires after every
        ``tiers[t].period`` pushes the parent RECEIVES, so stragglers
        below never stall anything above. The depth-3 async-root path
        (``cut == 2``) replays the historical behaviour bit-identically.
        """
        hfl = self.hfl
        tiers = hfl.tiers
        T = len(tiers)
        if self.residency is not None or self._oversub:
            raise ValueError(
                "async tier boundaries do not support residency "
                "tracking or oversubscribed fleets yet")
        if self.ledger is not None:
            raise ValueError(
                "payload_accounting='measured' is not supported above an "
                "async tier boundary at depth > 2 yet: the hier probe "
                "mirrors the synchronous cascade, not per-unit push "
                "payloads")
        H = self.period
        N = hfl.num_clusters
        U = hfl.agg_count(cut - 1)  # async units (tier cut-1 aggregators)
        G = N // U                  # clusters per unit
        # unit-rounds between cut pushes: the cut boundary keeps its
        # lockstep cadence relative to the tiers below it (hier_fire_top's
        # period product), it just fires on the unit's OWN clock
        Hc = 1
        for ti in range(2, cut + 1):
            Hc *= tiers[ti].period
        mpc = hfl.mus_per_cluster
        rounds = num_steps // H
        trace = Trace(meta=self._meta())
        trace.meta["hier_depth"] = T
        if rounds == 0:
            trace.meta.update(self._totals())
            return state, trace
        from repro.core.hfl import hier_fire_top

        it = iter(batches)
        q = EventQueue()
        bufs = sync_step.init_bufs(state)
        unit_sync, push = sync_step.unit_ops(cut)
        comp = (self.fleet.compute_times(self.sim.base_compute_s)
                if self.fleet is not None else None)

        def unit_rt(u: int) -> float:
            crt = self._cluster_round_times(comp)
            return float(crt[u * G:(u + 1) * G].max())

        for u in range(U):
            q.push(unit_rt(u), Event("unit_done", cluster=u, round=0))
        # per-boundary async bookkeeping (boundaries cut..T-1): pushes
        # LANDED per parent, each child's parent-counter at its last pull,
        # and (above the cut) pushes a parent has received since it last
        # fired upward
        updates = {tb: [0] * hfl.agg_count(tb) for tb in range(cut, T)}
        last_pull = {tb: [0] * hfl.agg_count(tb - 1)
                     for tb in range(cut, T)}
        pending = {tb: [0] * hfl.agg_count(tb - 1)
                   for tb in range(cut + 1, T)}
        steps_done = 0
        fleet_time = 0.0
        round_t0 = np.zeros(U)
        while len(q):
            t, ev = q.pop()
            u = ev.cluster
            if self.fleet is not None and self.fleet.mobile:
                self._advance_fleet(t - fleet_time, now=t)
                fleet_time = t
            self._vt = t
            avail = (self.fleet.draw_available(t)
                     if self.fleet is not None and self.fleet.dropout > 0
                     else None)
            fault = getattr(self.sim, "fault_dead_cluster", None)
            if fault is not None and self.fleet is not None:
                if avail is None:
                    avail = np.ones(self.fleet.K, bool)
                avail = avail & (self.fleet.cid != fault)
            slots = slice(u * G * mpc, (u + 1) * G * mpc)
            if self.selector is not None:
                if avail is None:
                    avail = np.ones(self.fleet.K, bool)
                # per-tier selection hook: the policy runs over THIS
                # unit's clusters at ITS round time (other units keep
                # their own clocks, draws and masks)
                sel = self.selector.select(
                    avail, self.fleet, t,
                    clusters=range(u * G, (u + 1) * G))
                avail = avail.copy()
                avail[slots] = sel[slots]
            unit_clusters = np.zeros(N, bool)
            unit_clusters[u * G:(u + 1) * G] = True
            mask = None
            dropped = 0
            if avail is not None:
                mask = None if avail.all() else avail
                dropped = int((~avail[slots]).sum())
            # clusters in the unit with at least one participant update;
            # the rest (and every other unit) keep their state untouched
            keep = unit_clusters
            if mask is not None:
                keep = unit_clusters & mask.reshape(N, mpc).any(axis=1)
            participants = (int(avail[slots].sum()) if avail is not None
                            else G * mpc)
            # step-indexed LR schedules follow THIS unit's round progress,
            # same contract as the flat async loop
            state = state._replace(
                step=jnp.asarray(ev.round * H, jnp.int32))
            loss = None
            for _h in range(H):
                batch = self._apply_participation(next(it), mask)
                with self.obs.host_span("hfl.train"):
                    new_state, loss = train_step(state, batch)
                state = _merge_clusters(state, new_state, keep)
                steps_done += 1
                self._count_train(participants, int(keep.sum()))
            # within-unit consensus: boundaries 1..utop at their lockstep
            # cadences, capped below the cut (higher boundaries are
            # clock-free pushes, not barriers)
            utop = min(hier_fire_top(tiers, ev.round + 1), cut - 1)
            if utop >= 1:
                with self.obs.host_span("hfl.sync"):
                    state, bufs = unit_sync(state, bufs, u, utop)
                s_ul, s_dl = self._count_sync_unit(utop, cut)
            loss_u = float(jnp.mean(loss) if jnp.ndim(loss) == 0
                           else jnp.mean(loss[u * G:(u + 1) * G]))
            if self.obs.enabled:
                self.obs.tracer.span(
                    "round", track=f"edge{u}", t0=round_t0[u],
                    dur=t - round_t0[u],
                    args={"round": int(ev.round), "dropped": dropped})
            for c in range(u * G, (u + 1) * G):
                self._mark_round(c, bool(keep[c]), t)
            if self._record and utop >= 1:
                trace.add(kind="sync", t=t, step=steps_done - 1,
                          tier=int(utop), edge=int(u), round=int(ev.round),
                          dropped=dropped, loss=loss_u,
                          bits_ul=s_ul, bits_dl=s_dl)
            self.obs.health.ingest_loss(loss_u, t=t)
            if (ev.round + 1) % Hc == 0:
                # async push across the cut, cascading up through any
                # counted boundaries above it: staleness counts the
                # updates siblings landed on the parent since this child
                # last pulled its reference
                a, tb = u, cut
                while tb < T:
                    p = a // tiers[tb].fanout
                    staleness = updates[tb][p] - last_pull[tb][a]
                    w = async_weight(staleness, tiers[tb].fanout,
                                     self.sim.staleness_exp)
                    with self.obs.host_span("hfl.sync"):
                        state, bufs = push(state, bufs, tb, a, w)
                    updates[tb][p] += 1
                    last_pull[tb][a] = updates[tb][p]
                    r_ul, r_dl = self._count_sync_push(tb)
                    t_push = 0.0
                    if self.wireless:
                        aux = self._latency_aux()
                        t_push = (r_ul + r_dl) / aux["fh_rate"]
                    t += t_push
                    if self.obs.enabled:
                        label = f"e{a}" if tb == cut else f"t{tb}a{a}"
                        self.obs.registry.histogram(
                            "sim.staleness").observe(
                                float(staleness), cluster=label)
                        self.obs.tracer.span(
                            "sync", track=f"edge{u}", t0=t - t_push,
                            dur=t_push,
                            args={"round": int(ev.round), "tier": int(tb),
                                  "staleness": int(staleness),
                                  "weight": float(w)})
                    if self._record:
                        trace.add(kind="sync", t=t, step=steps_done - 1,
                                  tier=int(tb), edge=int(a),
                                  round=int(ev.round),
                                  staleness=int(staleness),
                                  weight=float(w),
                                  bits_ul=r_ul, bits_dl=r_dl)
                    if tb + 1 >= T:
                        break
                    pend = pending[tb + 1]
                    pend[p] += 1
                    if pend[p] % tiers[tb + 1].period != 0:
                        break
                    a, tb = p, tb + 1
            if on_step is not None:
                on_step(steps_done - 1, state, loss)
            if ev.round + 1 < rounds:
                q.push(t + unit_rt(u),
                       Event("unit_done", cluster=u, round=ev.round + 1))
            round_t0[u] = t
            self.obs.tick()
        self._finish_run()
        trace.meta.update(self._totals())
        return state, trace
