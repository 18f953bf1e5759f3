"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are named in
``BENCHMARK.json`` at the root of the checkout; each lives in a file of its
own (``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/limits/<cell>.json``), and each metric has a reader
``bench/metrics/<metric>.py``. Nothing here branches on a cell's name.

The timed path is the program's own: ``hfl_init`` of weights made from the
seed, the donated cluster train step and sync step that
``repro.launch.train.train`` builds, driven one HFL period at a time by
``repro.core.schedule.run_hfl``. Set-up makes the weights, draws the token
batches, and runs the first periods, which compile both programs (or find
them in the persistent cache) and give the readings that the plain
reference checks once the window has closed. The window then runs whole
periods until ``--seconds`` have passed, each ending on
``block_until_ready``. With ``--trace 1`` the profiler records the window
(at most ``TRACE_SECONDS`` of it) and the per-layer metrics are read from
the trace; otherwise the end-to-end metrics are reported.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each compared number with its limit, also printed as the
last lines of stderr). Without a TPU, or with fewer chips than the cell
needs, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, feed, trace_reduce, work  # noqa: E402
from bench.weights import leaf_names, make_weights  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import (DEFAULT_TIERS, HFLConfig, ShapeConfig,  # noqa: E402
                                TierConfig)
from repro.core import hfl as hfl_mod  # noqa: E402
from repro.core import schedule  # noqa: E402
from repro.launch.steps import make_loss_fn, train_input_specs  # noqa: E402
from repro.models.transformer import init_model  # noqa: E402
from repro.optim import SGDM, constant_lr  # noqa: E402
from repro.utils.compile_cache import use_compile_cache  # noqa: E402
from repro.utils.jaxcompat import make_mesh  # noqa: E402

TRACE_SECONDS = 8.0
OUT = BENCH / "out"


# ---------------------------------------------------------------------------
# The cell, from its files
# ---------------------------------------------------------------------------


def load_cell(name: str, overrides: dict | None = None) -> dict:
    """The cell ``name`` of BENCHMARK.json with its configuration, traffic,
    limits and metric entries. ``overrides`` replaces keys of the cell,
    configuration, traffic, deployment or limits (tests run a cell at a
    size a CPU holds, with limits set for that size)."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    ov = overrides or {}
    cell = {**cells[name], **ov.get("cell", {})}
    conf_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    conf = json.loads((ROOT / conf_entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    conf.update(ov.get("config", {}))
    conf["deployment"] = {**conf["deployment"], **ov.get("deployment", {})}
    traffic.update(ov.get("traffic", {}))

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "name": name, "cell": cell, "config": conf,
        "reduced": conf_entry["reduced"] + list(ov.get("config", {})),
        "traffic": traffic,
        "limits": {**check.limits(name), **ov.get("limits", {})},
        "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
        "per_layer": [m for m in spec["per_layer"] if mine(m)],
    }


def model_config(conf: dict, reduced: list):
    """The program's config of ``conf['arch']`` with the file's sizes; every
    key that differs from the program's published config must be listed in
    ``reduced``, and every size the file states must be the one run."""
    base = get_config(conf["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    changes = {k: v for k, v in conf.items()
               if k in fields and getattr(base, k) != v}
    stray = set(changes) - set(reduced)
    if stray:
        raise SystemExit(f"bench: {conf['arch']} differs from the program's "
                         f"config in {sorted(stray)}, not listed in reduced")
    return dataclasses.replace(base, **changes)


def hfl_config(dep: dict, t: dict) -> HFLConfig:
    """Two tiers: MUs averaged inside each cluster every step (``HFLConfig``'s
    default tier 0 with the deployment's fan-out), clusters synced every
    ``period`` steps with the traffic's φ and β. Everything else keeps
    ``HFLConfig``'s defaults."""
    tiers = (
        dataclasses.replace(DEFAULT_TIERS[0], fanout=dep["mus_per_cluster"]),
        TierConfig(fanout=dep["clusters"], period=t["period"],
                   phi_up=t["phi_up"], phi_down=t["phi_down"],
                   beta_up=t["beta_up"], beta_down=t["beta_down"]),
    )
    return HFLConfig(tiers=tiers, sync_mode=t["sync_mode"])


# ---------------------------------------------------------------------------
# Readings of the program's state (see bench/check.py)
# ---------------------------------------------------------------------------


@jax.jit
def _stacked_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                             axis=tuple(range(1, x.ndim))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _sync_norms(w_ref, e, eps, w0):
    d = [jnp.sqrt(jnp.sum(jnp.square(r.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
         for r, b in zip(jax.tree.leaves(w_ref), jax.tree.leaves(w0))]
    ee = [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(e)]
    return d, ee, _stacked_norms(eps)


@jax.jit
def _change_norms(params, w0):
    return [jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32)
                                        - b.astype(jnp.float32)[None]),
                             axis=tuple(range(1, p.ndim))))
            for p, b in zip(jax.tree.leaves(params), jax.tree.leaves(w0))]


class Readings:
    """Takes the program's readings of the first steps from ``on_step``."""

    def __init__(self, names, period, weights_fn):
        self.names, self.period, self.weights = names, period, weights_fn
        self.out = {"loss": [], "grad": {}, "sync": {}, "change": {}}
        self.step = 0

    def __call__(self, state, loss):
        g, self.step = self.step, self.step + 1
        if g < 3:
            self.out["loss"].append(np.asarray(loss, np.float64).tolist())
        if g == 0:
            for nm, v in zip(self.names, _stacked_norms(state.opt["m"])):
                self.out["grad"][nm] = np.asarray(v).tolist()
        if (g + 1) % self.period == 0 and not self.out["sync"]:
            d, e, eps = _sync_norms(state.w_ref, state.e, state.eps,
                                    self.weights())
            for nm, a, b, c in zip(self.names, d, e, eps):
                self.out["sync"]["d." + nm] = [float(a)]
                self.out["sync"]["e." + nm] = [float(b)]
                self.out["sync"]["eps." + nm] = np.asarray(c).tolist()
        if g == 2:
            for nm, v in zip(self.names,
                             _change_norms(state.params, self.weights())):
                self.out["change"][nm] = np.asarray(v).tolist()

    @property
    def steps_needed(self) -> int:
        return max(3, self.period)


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------


def initial_weights(seed, shapes, vocab, mesh):
    w = make_weights(seed, shapes, vocab)
    if mesh is not None:
        w = jax.device_put(w, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))
    return w


class Program:
    """The compiled train and sync steps with their state and feed, built
    from the seed; the same object runs set-up and the window."""

    def __init__(self, c: dict, seed: int, devices):
        conf, t, dep = c["config"], c["traffic"], c["config"]["deployment"]
        self.cfg = model_config(conf, c["reduced"])
        self.hfl = hfl = hfl_config(dep, t)
        self.period = t["period"]
        N, M = dep["clusters"], dep["mus_per_cluster"]
        rows = M * t["batch_per_mu"]
        self.tokens_per_step = N * rows * t["seq"]
        opt = SGDM(momentum=t["momentum"], weight_decay=t["weight_decay"])
        lr = t["base_lr"] * N * rows / t["lr_batch"]

        self.mesh = pspecs = None
        self.batch_sharding = jax.sharding.SingleDeviceSharding(devices[0])
        init = jax.jit(lambda w: hfl_mod.hfl_init(w, opt, hfl))
        if dep.get("mesh"):
            self.mesh = make_mesh(dep["mesh"]["shape"], dep["mesh"]["axes"])
            shape = ShapeConfig("bench", t["seq"], N * rows, "train")
            state_sds, batch_sds, pspecs = train_input_specs(
                self.cfg, shape, self.mesh, hfl, optimizer=opt)
            init = jax.jit(
                lambda w: hfl_mod.hfl_init(w, opt, hfl),
                out_shardings=jax.tree.map(lambda s: s.sharding, state_sds))
            self.batch_sharding = batch_sds["tokens"].sharding

        self.shapes = jax.eval_shape(lambda k: init_model(k, self.cfg),
                                     jax.random.PRNGKey(0))
        self.names = leaf_names(self.shapes)
        self.seed = seed
        self.state = init(self.initial_weights()())
        self.train = jax.jit(
            hfl_mod.make_cluster_train_step(make_loss_fn(self.cfg), opt,
                                            constant_lr(lr)),
            donate_argnums=0)
        self.sync = hfl_mod.jit_sync_step(hfl_mod.make_sync(
            hfl_mod.SyncPlan.from_config(hfl, mesh=self.mesh,
                                         param_specs=pspecs)))
        self.modules = {"train": f"jit_{self.train.__name__}",
                        "sync": f"jit_{self.sync.__name__}"}
        self.pool = feed.batch_pool(
            seed, vocab=conf["vocab_size"], clusters=N, rows_per_cluster=rows,
            seq=t["seq"], batches=t["pool_batches"])
        self._next = itertools.cycle(range(len(self.pool)))

    def initial_weights(self):
        """A function that remakes the initial weights, placed as the state
        is, and holds nothing of the program."""
        return functools.partial(initial_weights, self.seed, self.shapes,
                                 self.cfg.vocab_size, self.mesh)

    def batches(self):
        while True:
            with jax.profiler.TraceAnnotation("bench.feed"):
                batch = {"tokens": jax.device_put(self.pool[next(self._next)],
                                                  self.batch_sharding)}
            yield batch

    def _train(self, state, batch):
        with jax.profiler.TraceAnnotation("bench.train"):
            return self.train(state, batch)

    def _sync(self, state):
        with jax.profiler.TraceAnnotation("bench.sync"):
            return self.sync(state)

    def period_run(self, on_loss):
        """One HFL period through the program's scheduler, ended on the
        device."""
        def on_step(t, state, loss):
            with jax.profiler.TraceAnnotation("bench.loss_read"):
                on_loss(state, loss)
        with jax.profiler.TraceAnnotation("bench.period"):
            self.state = schedule.run_hfl(self.state, self._train,
                                          self._sync, self.batches(),
                                          self.period, self.period, on_step)
            self.state = jax.block_until_ready(self.state)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts XLA compilations, and their seconds, reported through
    ``jax.monitoring``."""

    def __init__(self):
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        if event.endswith("backend_compile_duration"):
            self.n += 1
            self.secs += secs


def device_info(chips: int, require_tpu: bool) -> tuple:
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise SystemExit(f"bench: JAX's platform is {d.platform!r}, not "
                         "'tpu'; the benchmark runs on a TPU only")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips], {"platform": d.platform, "kind": d.device_kind,
                          "count": chips}


def run(argv=None, *, require_tpu: bool = True,
        overrides: dict | None = None) -> dict:
    """One run of one cell; returns the result object (also printed)."""
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be a non-negative integer")

    c = load_cell(args.workload, overrides)
    chips = c["cell"]["chips"]
    devices, device = device_info(chips, require_tpu)
    if require_tpu:
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()

    split = {"start": time.perf_counter() - T_START}
    prog = Program(c, args.seed, devices)
    split["state"] = time.perf_counter() - T_START
    readings = Readings(prog.names, prog.period, prog.initial_weights())
    while readings.step < readings.steps_needed:
        prog.period_run(readings)
        split[f"period{readings.step // prog.period}"] = (
            time.perf_counter() - T_START)
    print("bench: set-up at " + " ".join(f"{k}={v:.3f}s" for k, v in
                                          split.items())
          + f"; {compiles.n} compiles, {compiles.secs:.3f} s compiling",
          file=sys.stderr)

    attempted, failed = 0, 0
    periods = []

    def on_loss(_state, loss):
        nonlocal failed
        if not np.all(np.isfinite(np.asarray(loss))):
            failed += 1

    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    trace_dir = OUT / "trace" / args.workload
    compiles_before = compiles.n
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        # one period under the profiler before the traced window: the
        # profiler's own start-up stays out of what the window reads
        prog.period_run(on_loss)
    t_win = time.perf_counter()
    setup_s = t_win - T_START
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        while True:
            t0 = time.perf_counter()
            prog.period_run(on_loss)
            periods.append(time.perf_counter() - t0)
            attempted += prog.period
            if time.perf_counter() - t_win >= seconds:
                break
    window_s = time.perf_counter() - t_win
    if args.trace:
        jax.profiler.stop_trace()
    in_window = compiles.n - compiles_before

    # buffers and, apart from them, the memory the runtime reserves for the
    # programs' temporaries
    mem = [d.memory_stats() or {} for d in devices]
    peaks = [m["peak_bytes_in_use"] + m.get("peak_bytes_reserved", 0)
             for m in mem if "peak_bytes_in_use" in m]
    peak = max(peaks) if len(peaks) == len(mem) else None
    if peak is not None:
        device["memory_peak_bytes"] = int(peak)

    ctx = {
        "window": {"seconds": window_s, "periods": periods,
                   "steps": attempted},
        "setup_s": setup_s, "memory_peak_bytes": peak, "chips": chips,
        "modules": prog.modules, "traced_steps": attempted,
        "work": cell_work(c, prog),
    }
    result = {"correct": False, "attempted": attempted, "failed": failed}
    if args.trace:
        red = trace_reduce.reduce_trace(trace_reduce.find_xplane(trace_dir))
        ctx["trace"] = red
        ctx["peaks"] = peak_table(device["kind"])
        busy = sum(d["busy_ns"] for d in red["devices"]) / len(red["devices"])
        device["busy_s"] = busy / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        metrics = read_metrics(c["per_layer"], ctx)
        result["breakdown"] = trace_reduce.breakdown(red)
    else:
        metrics = read_metrics(c["end_to_end"], ctx)
    result["metrics"] = metrics
    result["device"] = device

    shapes = prog.shapes
    pool = prog.pool
    del prog
    gc.collect()
    print(f"bench: {sum(a.nbytes for a in jax.live_arrays()) / 1e9:.3f} GB "
          "live on the devices before the reference", file=sys.stderr)
    print(f"bench: window {window_s:.3f} s, {len(periods)} periods, "
          f"{attempted} steps, compiles in window {in_window}",
          file=sys.stderr)
    checks = {}
    try:
        ref = reference_readings(c, args.seed, shapes, pool, devices)
        print("bench: peak bytes in use with the reference "
              + str([(d.memory_stats() or {}).get("peak_bytes_in_use")
                     for d in devices]), file=sys.stderr)
        numbers = check.compare(readings.out, ref)
        checks = {k: {"value": numbers[k], "limit": c["limits"][k]}
                  for k in check.NUMBERS}
        result["correct"] = (check.judge(numbers, c["limits"])
                             and failed == 0 and in_window == 0)
    except Exception:  # a comparison that cannot be made is not correct
        traceback.print_exc()
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    result["checks"] = checks
    print(json.dumps(result))
    return result


def reference_readings(c, seed, shapes, pool, devices, *, mm="f32",
                       fault=None):
    """The configuration's plain reference, run from the same seed."""
    ref = importlib.import_module(f"bench.reference.{c['config']['reference']}")
    weights = make_weights(seed, shapes, c["config"]["vocab_size"])
    return ref.readings(c["config"], c["traffic"], c["config"]["deployment"],
                        weights, pool, mm=mm, fault=fault, devices=devices)


def cell_work(c: dict, prog: Program) -> dict:
    conf, t, dep = c["config"], c["traffic"], c["config"]["deployment"]
    q = sum(math.prod(s.shape) for s in jax.tree.leaves(prog.shapes))
    return {
        "tokens_per_step": prog.tokens_per_step,
        "flops_per_token": work.train_flops_per_token(conf, t["seq"]),
        "sync_bytes": work.sync_bytes(
            t["sync_mode"], q=q, clusters=dep["clusters"],
            clusters_here=dep["clusters"] // dep["chips"],
            phi_up=t["phi_up"], exchange=dep["chips"] > 1),
    }


def peak_table(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"bench/peaks.json has no peaks for device kind "
                       f"{kind!r}")
    return table[kind]


def read_metrics(entries: list, ctx: dict) -> dict:
    out = {}
    for m in entries:
        v = importlib.import_module(f"bench.metrics.{m['name']}").read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


if __name__ == "__main__":
    run()
