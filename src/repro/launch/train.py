"""End-to-end HFL training driver.

Trains an (optionally reduced) architecture with the hierarchical-FL engine
on synthetic LM data: N clusters x M MUs, intra-cluster aggregation every
step, sparse cross-cluster consensus every H steps, checkpointing, and a
final held-out eval. On CPU this drives the reduced configs; on a real TPU
fleet the same script runs the full configs over the production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --steps 200 --clusters 4 --period 4 --sync sparse

With ``--scenario`` the run goes through the event-driven HCN simulator
(``repro.sim``): the same jitted train/sync steps, but driven on a virtual
wall clock priced by the wireless model, emitting a deterministic
wall-clock-vs-loss trace (``--trace-out`` to save it as JSON):

  PYTHONPATH=src python -m repro.launch.train --scenario paper-fig3 \
      --steps 8 --trace-out trace.json

Observability (``repro.obs``): ``--trace-viz out.json`` exports a
Chrome/Perfetto trace of every simulator event on the virtual clock plus
host-clock spans of the engine's host path (also recorded by a running
``jax.profiler`` trace); ``--metrics-out run.jsonl`` streams every
console line as a structured JSONL event and appends the final metrics-
registry snapshot; ``--obs-health`` turns on the learning-health
monitor (per-cluster drift/residual/Ω-overlap from the jitted sync,
staleness + participation fairness from the simulator, streaming anomaly
rules -> JSONL ``health`` events + Perfetto counter tracks). Reporting also
splits first-step trace+compile time from the steady-state s/step (the
historical figure silently folded the compile stall into every step).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config
from repro.configs.base import (
    HFLConfig, parse_tiers_spec, warn_legacy_cli_flag,
)
from repro.core.hfl import (
    SyncPlan, hfl_init, jit_sync_step, make_cluster_train_step, make_sync,
    serving_params,
)
from repro.core.schedule import run_hfl
from repro.data import SyntheticLM
from repro.launch.steps import make_loss_fn
from repro.models.frontends import fake_frontend_embeds
from repro.models.transformer import forward, init_model
from repro.obs import ObsConfig, RunLogger, StepClock, make_telemetry
from repro.optim import SGDM, warmup_step_decay
from repro.utils.compile_cache import use_compile_cache


def _jsonable(obj):
    """numpy scalars -> python floats/ints so traces dump cleanly."""
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.floating, np.integer)):
        return obj.item()
    return obj


class TrainResult(NamedTuple):
    """What :func:`train` hands back: per-step mean losses, the held-out
    eval loss of the consensus model, the final ``HFLState`` and the
    ``StepClock`` summary."""

    hist: list
    eval_loss: float
    state: Any
    timing: dict


def main(argv=None):
    """Parse the command line, resolve the configs and run :func:`train`.
    Returns ``(per-step losses, eval loss)``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--tiers", default=None,
                    help="hierarchy spec FANOUTS[:H=PERIODS][:async]: "
                         "fan-outs root-down (4x2 = 4 clusters x 2 MUs), "
                         "aggregation periods bottom-up (H=4, or H=4,2 "
                         "for a depth-3 root every 2 tier-1 rounds), "
                         "':async' makes the root tier clock-free. "
                         "Replaces --clusters/--mus/--period")
    ap.add_argument("--clusters", type=int, default=None,
                    help="DEPRECATED alias of --tiers CxM:H=P")
    ap.add_argument("--mus", type=int, default=None,
                    help="DEPRECATED alias of --tiers CxM:H=P")
    ap.add_argument("--period", type=int, default=None,
                    help="DEPRECATED alias of --tiers CxM:H=P")
    ap.add_argument("--sync", default="sparse",
                    choices=["dense", "sparse", "quantized_sparse"])
    ap.add_argument("--omega-impl", default="topk",
                    choices=["topk", "hist", "pallas", "fused"],
                    help="Ω selection implementation for sparse syncs "
                         "(fused = kernels/fused_sync threshold+compaction, "
                         "selection bit-identical to topk)")
    ap.add_argument("--sync-layout", default="flat", choices=["flat", "leaf"],
                    help="flat = whole-model Ω (paper-exact, one fused "
                         "top-k/collective per sync); leaf = legacy per-leaf "
                         "reference path")
    ap.add_argument("--flat-shards", type=int, default=1,
                    help="shard the padded flat vector into this many "
                         "contiguous pieces (requires --omega-impl fused; "
                         "single-process emulation of the (data, model) "
                         "mesh sharding)")
    ap.add_argument("--payload-accounting", default="analytic",
                    choices=["analytic", "measured"],
                    help="analytic = the paper's Q·(1-φ)·bits/param; "
                         "measured = byte-accurate codec streams of the "
                         "real sync payloads (repro.comm), priced into the "
                         "simulator's virtual clock")
    ap.add_argument("--codec", default="delta-varint",
                    help="payload codec for measured accounting "
                         "(repro.comm.codecs registry: dense-f32, "
                         "dense-bf16, bitmap, delta-varint, delta-gamma, "
                         "*-q8, best)")
    ap.add_argument("--wire-format", default="bf16", choices=["bf16", "q8"],
                    help="wire value rounding under --sync "
                         "quantized_sparse (error feeds back through the "
                         "eps/e buffers)")
    ap.add_argument("--batch-per-mu", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.25)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--scenario", default=None,
                    help="run through the HCN simulator (repro.sim): "
                         "paper-fig3 | stragglers | mobility | dropout | "
                         "async | trace-replay | manhattan | diurnal | "
                         "flash-crowd | scale-1m (live 1.05M-MU fleet) | "
                         "scale-100k (deprecated alias of scale-1m) | "
                         "hier-3tier (depth-3 tiered consensus) | "
                         "prate-biased (rate-biased client selection). "
                         "A scenario may pin HFL settings (paper-fig3 pins "
                         "the paper's 7-cluster topology, K=4, H=2, φ).")
    ap.add_argument("--sim-seed", type=int, default=0,
                    help="fleet/scenario seed (replay is bit-identical)")
    ap.add_argument("--trace-out", default=None,
                    help="write the wall-clock trace JSON here")
    ap.add_argument("--trace-in", default=None,
                    help="replay an external mobility trace (CSV with a "
                         "t,mu_id,x,y header, or JSONL with those keys) "
                         "instead of the scenario's built-in mobility; "
                         "mu count must equal clusters*mus")
    ap.add_argument("--residency", default=None,
                    choices=["static", "move", "duplicate", "stale"],
                    help="data residency policy as mobility re-associates "
                         "MUs (overrides the scenario): static = shards "
                         "pinned to birth slots; move = shard follows the "
                         "radio; duplicate = visited clusters keep a copy; "
                         "stale = tracked but never moves")
    ap.add_argument("--trace-viz", default=None,
                    help="export a Chrome/Perfetto trace-event JSON of the "
                         "run (virtual-clock simulator spans + host-clock "
                         "jit boundaries; load in chrome://tracing or "
                         "ui.perfetto.dev). Scenario runs only.")
    ap.add_argument("--metrics-out", default=None,
                    help="stream structured run events as JSONL here "
                         "(config, per-step losses, compile/steady timing, "
                         "sim summary, final metrics-registry snapshot)")
    ap.add_argument("--obs-heartbeat", type=int, default=0,
                    help="print an events/s + live-memory heartbeat to "
                         "stderr every N simulator events (0 = off)")
    ap.add_argument("--obs-health", action="store_true",
                    help="learning-health monitor: per-cluster consensus "
                         "drift / residual norms / Ω overlap from the "
                         "jitted sync, staleness + participation fairness "
                         "from the simulator, streaming anomaly rules "
                         "(divergence blowup, dead cluster, loss spike, "
                         "...). Emits health.* gauges, health JSONL "
                         "events, and Perfetto counter tracks; the run "
                         "itself stays bit-identical")
    args = ap.parse_args(argv)

    obs_cfg = None
    if (args.trace_viz or args.metrics_out or args.obs_heartbeat
            or args.obs_health):
        obs_cfg = ObsConfig(
            trace_path=args.trace_viz, metrics_path=args.metrics_out,
            heartbeat_events=args.obs_heartbeat,
            health=bool(args.obs_health))
    log = RunLogger(args.metrics_out)

    scenario = None
    if args.scenario is not None:
        from repro.sim.scenarios import get_scenario, run_scale_sampling
        scenario = get_scenario(args.scenario)
        if scenario.kind == "sampling":
            # no registry scenario is sampling-kind anymore (scale-100k
            # silently skipped training; it now aliases the live scale-1m
            # path) — kept for out-of-registry Scenario objects
            from repro.utils.format import format_metrics
            stats = _jsonable(run_scale_sampling(scenario))
            log.log("sampling", f"[sim] {args.scenario}: "
                    + format_metrics(stats, skip=("scenario",)), **stats)
            if args.trace_out:
                with open(args.trace_out, "w") as f:
                    json.dump(stats, f, indent=1)
            log.close()
            return stats, None

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    legacy_flags = {"--clusters": args.clusters, "--mus": args.mus,
                    "--period": args.period}
    given = {f: v for f, v in legacy_flags.items() if v is not None}
    if args.tiers is not None:
        if given:
            raise SystemExit(
                f"--tiers conflicts with {'/'.join(sorted(given))}; the "
                "hierarchy is fully specified by the --tiers spec")
        tiers = parse_tiers_spec(args.tiers)
    else:
        for f in sorted(given):
            warn_legacy_cli_flag(
                f, "--tiers CLUSTERSxMUS:H=PERIOD "
                   "(fan-outs root-down, periods bottom-up)")
        clusters = args.clusters if args.clusters is not None else 4
        mus = args.mus if args.mus is not None else 2
        period = args.period if args.period is not None else 4
        tiers = parse_tiers_spec(f"{clusters}x{mus}:H={period}")
    hfl = HFLConfig(
        tiers=tiers,
        sync_mode=args.sync, omega_impl=args.omega_impl,
        sync_layout=args.sync_layout, flat_shards=args.flat_shards,
        payload_accounting=args.payload_accounting, codec=args.codec,
        wire_format=args.wire_format,
    )
    if scenario is not None:
        from repro.sim.scenarios import apply_hfl_overrides
        hfl = apply_hfl_overrides(scenario, hfl)
    res = train(cfg, hfl, steps=args.steps, batch_per_mu=args.batch_per_mu,
                seq=args.seq, lr=args.lr, log_every=args.log_every,
                ckpt_dir=args.ckpt_dir, scenario=scenario,
                sim_seed=args.sim_seed, trace_in=args.trace_in,
                residency=args.residency, trace_out=args.trace_out,
                obs_cfg=obs_cfg, log=log)
    log.close()
    return res.hist, res.eval_loss


def train(cfg, hfl, *, steps: int, batch_per_mu: int = 8, seq: int = 64,
          lr: float = 0.25, log_every: int = 20,
          ckpt_dir: Optional[str] = None, scenario=None, sim_seed: int = 0,
          trace_in: Optional[str] = None, residency: Optional[str] = None,
          trace_out: Optional[str] = None, obs_cfg=None,
          log: Optional[RunLogger] = None) -> TrainResult:
    """Train ``cfg`` with the HFL engine under ``hfl`` on synthetic LM data.

    ``hfl_init``, the jitted cluster train step (state donated), the
    donated sync step from ``make_sync``, then ``run_hfl`` (or the
    simulator engine when ``scenario`` is given), and the held-out eval
    of the consensus model. Data and weights come from fixed seeds.
    """
    log = log if log is not None else RunLogger()
    log.log(
        "config",
        f"[train] arch={cfg.name} clusters={hfl.num_clusters} "
        f"mus/cluster={hfl.mus_per_cluster} H={hfl.tiers[1].period} sync={hfl.sync_mode} "
        f"layout={hfl.sync_layout} omega={hfl.omega_impl}"
        + (f" scenario={scenario.name}" if scenario is not None else ""),
        arch=cfg.name, clusters=hfl.num_clusters,
        mus_per_cluster=hfl.mus_per_cluster, period=hfl.tiers[1].period,
        sync=hfl.sync_mode, layout=hfl.sync_layout, omega=hfl.omega_impl,
        payload_accounting=hfl.payload_accounting,
        scenario=(scenario.name if scenario is not None else None),
        steps=steps, seq=seq, batch_per_mu=batch_per_mu,
    )

    # the telemetry handle is created BEFORE the step builders run so their
    # build-time counters land in this run's registry (the engine adopts
    # the handle; non-scenario runs hold it directly)
    engine = None
    if scenario is not None:
        from repro.sim.scenarios import build_engine
        engine = build_engine(scenario, hfl, seed=sim_seed,
                              trace_file=trace_in, residency=residency,
                              obs=obs_cfg)
        tele = engine.obs
    else:
        tele = make_telemetry(obs_cfg)
    if tele.health.enabled:
        # anomalies stream to the JSONL runlog as structured health events
        tele.health.runlog = log

    opt = SGDM(momentum=0.9, weight_decay=1e-4)
    sched = warmup_step_decay(lr * hfl.total_mus * batch_per_mu / 128,
                              warmup_steps=max(steps // 20, 1),
                              decay_steps=(steps // 2, 3 * steps // 4))
    # the initial model is not kept beside the state: the state holds N
    # copies of it, and the donated steps need the device memory
    state = hfl_init(init_model(jax.random.PRNGKey(0), cfg), opt, hfl)

    loss_fn = make_loss_fn(cfg)
    # both steps consume-and-replace the whole state: donate it, so its
    # buffers are reused for the outputs instead of living twice
    train_step = jax.jit(make_cluster_train_step(loss_fn, opt, sched),
                         donate_argnums=0)
    # with --obs-health on a scenario run the sync also returns its in-jit
    # health statistics (supported on the local flat/fused/dense paths;
    # sharded layouts raise in make_sync_step, so gate on the flags)
    # in-sync health stats are a depth-2 local-flat feature; deeper
    # hierarchies run the tiered cascade which rejects collect_stats
    collect = bool(tele.health.enabled and scenario is not None
                   and hfl.sync_layout == "flat" and hfl.flat_shards == 1
                   and hfl.depth == 2)
    sync_step = jit_sync_step(
        make_sync(SyncPlan.from_config(hfl, collect_stats=collect)))

    lm = SyntheticLM(cfg.vocab_size, seed=1)
    rng = np.random.default_rng(2)
    local_b = hfl.mus_per_cluster * batch_per_mu
    F = cfg.frontend_tokens if cfg.frontend != "none" else 0

    def make_batches(lm_, rng_):
        while True:
            toks = lm_.sample(hfl.num_clusters * local_b, seq, rng_)
            b = {"tokens": jnp.asarray(toks.reshape(hfl.num_clusters, local_b, seq))}
            if F:
                fe = fake_frontend_embeds(jax.random.PRNGKey(int(rng_.integers(1 << 30))),
                                          cfg, hfl.num_clusters * local_b)
                b["frontend"] = fe.reshape(hfl.num_clusters, local_b, *fe.shape[1:])
            yield b

    hist = []
    clock = StepClock()

    def on_step(t, s, loss):
        l = float(loss.mean())  # blocks until the step actually finished
        clock.step()
        hist.append(l)
        if (t + 1) % log_every == 0:
            ss = clock.steady_s_per_step
            # steady rate once a post-compile sample exists; the first
            # window falls back to the compile-inclusive mean
            rate = (ss if ss is not None
                    else (time.perf_counter() - clock.t0) / clock.steps)
            log.log("step", f"  step {t+1:5d}  loss {l:.4f}  ({rate:.2f}s/step)",
                    step=t + 1, loss=l, s_per_step=rate,
                    steady=ss is not None)

    trace = None
    if scenario is not None:
        from repro.core.hfl import make_masked_cluster_train_step
        # async/trace rounds advance ONE cluster: the masked step computes
        # only that cluster (~1/N the FLOPs of the vmapped step)
        masked_step = jax.jit(
            make_masked_cluster_train_step(loss_fn, opt, sched),
            donate_argnums=0)
        state, trace = engine.run(state, train_step, sync_step,
                                  make_batches(lm, rng),
                                  steps, on_step=on_step,
                                  masked_train_step=masked_step)
        m = trace.meta
        log.log("sim_summary",
                f"[sim] scenario={scenario.name} discipline={m['discipline']} "
                f"residency={m['residency']} "
                f"virtual-wallclock={trace.wallclock:.3f}s "
                f"syncs={m['sync_launches']} "
                f"fronthaul={m['bits_fronthaul_total']/8e6:.2f}MB",
                **_jsonable(m))
        if m.get("payload_accounting") == "measured":
            bpp = m.get("bits_per_param_mean")
            log.log("sim_measured",
                    f"[sim] measured payloads: codec={m['codec']} "
                    f"Q={m['payload_size']} "
                    f"sbs_ul={m['bits_sbs_ul']/8e6:.3f}MB "
                    f"mbs_dl={m['bits_mbs_dl']/8e6:.3f}MB "
                    + (f"bits/param={bpp:.3f}" if bpp is not None else ""))
        if m.get("wireless"):
            log.log("sim_latency",
                    f"[sim] t_fl_iter={m['t_fl_iter_s']:.3f}s "
                    f"t_hfl_iter={m['t_hfl_iter_s']:.3f}s "
                    f"t_hfl_period={m['t_hfl_period_s']:.3f}s "
                    f"(period<fl_iter: {m['t_hfl_period_s'] < m['t_fl_iter_s']})")
        if trace_out:
            with open(trace_out, "w") as f:
                json.dump(_jsonable(trace.to_json()), f, indent=1)
            log.log("trace_out", f"[sim] trace -> {trace_out}",
                    path=trace_out)
        trace_viz = obs_cfg.trace_path if obs_cfg is not None else None
        if trace_viz and tele.enabled:
            tele.export_chrome(trace_viz,
                               metadata={"engine_meta": _jsonable(m)})
            log.log("trace_viz", f"[obs] chrome trace -> {trace_viz}",
                    path=trace_viz, events=len(tele.tracer.events),
                    dropped=tele.tracer.dropped)
    else:
        state = run_hfl(state, train_step, sync_step, make_batches(lm, rng),
                        hfl.tiers[1].period, steps, on_step)

    timing = clock.summary()
    if timing["steps"]:
        cs, ss = timing["compile_s"], timing["steady_s_per_step"]
        log.log("timing",
                f"[train] compile_s={cs:.2f}"
                + (f"  steady={ss:.3f}s/step" if ss is not None
                   else "  (one step; no steady-state sample)"),
                **timing)

    # held-out eval with the consensus model, 8 sequences at a time: the
    # f32 log-probs of all 32 at a full vocabulary would not fit beside
    # the state on one chip
    sp = serving_params(state)
    toks = jnp.asarray(lm.sample(32, seq, np.random.default_rng(99)))
    fe = fake_frontend_embeds(jax.random.PRNGKey(7), cfg, 32) if F else None
    nll = []
    for i in range(0, 32, 8):
        tk = toks[i:i + 8]
        logits, _ = forward(sp, tk, cfg,
                            frontend_embeds=None if fe is None else fe[i:i + 8])
        lp = jax.nn.log_softmax(logits[:, -seq:].astype(jnp.float32), -1)
        nll.append(-jnp.take_along_axis(lp[:, :-1], tk[:, 1:, None], -1).mean())
    eval_loss = float(jnp.mean(jnp.stack(nll)))
    if hist:  # async with steps < H completes zero rounds -> no train losses
        log.log("eval",
                f"[train] first-loss={hist[0]:.4f} last-loss={hist[-1]:.4f} "
                f"eval-loss={eval_loss:.4f}",
                first_loss=hist[0], last_loss=hist[-1], eval_loss=eval_loss)
    else:
        log.log("eval",
                f"[train] no training rounds completed; "
                f"eval-loss={eval_loss:.4f}", eval_loss=eval_loss)

    if ckpt_dir:
        path = save_checkpoint(ckpt_dir, steps, state._asdict())
        log.log("checkpoint", f"[train] checkpoint -> {path}", path=str(path))
    if tele.health.enabled:
        hs = tele.health.summary()
        log.log("health_summary",
                f"[health] anomalies={hs['anomalies']} "
                f"by_rule={hs['by_rule'] or '{}'} "
                f"signals={len(hs['signals'])}",
                **hs)
    if tele.enabled:
        snap = tele.registry.snapshot()
        # histogram quantiles on the console (the full snapshot is
        # JSONL-only below — it is large and structured)
        for name, m in sorted(snap.items()):
            if m.get("kind") != "histogram":
                continue
            for lbl, s in m["series"].items():
                where = f"{{{lbl}}}" if lbl else ""
                print(f"[obs] {name}{where}: n={s['count']} "
                      f"p50={s['p50']:.4g} p95={s['p95']:.4g} "
                      f"p99={s['p99']:.4g} max={s['max']:.4g}")
        log.log("metrics", None, metrics=snap)
    return TrainResult(hist, eval_loss, state, timing)


def cli() -> None:
    """Console entry: the persistent compile cache, then :func:`main`."""
    use_compile_cache()
    main()


if __name__ == "__main__":
    cli()
