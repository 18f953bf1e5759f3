"""Smoke run of the HFL engine's train-and-sync path on a TPU.

One chip (no arguments): olmo-1b at its published widths with its depth
cut to 2 layers, trained through ``repro.launch.train.train`` — the
function behind ``python -m repro.launch.train`` — with 2 clusters x 2
MUs, H=2, batch 4 per MU, seq 1024 and 6 steps (3 sparse flat syncs),
then the held-out eval. After it: the Pallas kernels, compiled, against
their references, the fused selection's indices against ``lax.top_k``,
and ``omega_impl`` = topk, fused and pallas syncs on the run's final state.

Four chips (``--chips 4``): only the cross-cluster exchange and what it is
compared with. A ("pod","data","model") = (4,1,1) mesh holds 4 clusters,
one per chip, at the same widths; two train steps make the clusters
differ, then one pod ``shard_map`` sync must hold the protocol invariants
(divergence exactly 0, conservation, adoption of ``w_ref``). At the
reduced olmo config the mesh sync's ``w_ref`` is compared with the
one-device local flat sync's on the same input.

Weights and data come from fixed seeds. The script exits non-zero, without
the result line, when JAX finds no TPU or any check fails; otherwise the
last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import HFLConfig, ShapeConfig, parse_tiers_spec  # noqa: E402
from repro.core.hfl import (  # noqa: E402
    SyncPlan, _pack_drift, hfl_init, jit_sync_step, make_cluster_train_step,
    make_sync,
)
from repro.core.sparsify import keep_count  # noqa: E402
from repro.data import SyntheticLM  # noqa: E402
from repro.kernels import interpret_mode  # noqa: E402
from repro.kernels.bitpack import ops as bitpack_ops  # noqa: E402
from repro.kernels.dgc import ops as dgc_ops  # noqa: E402
from repro.kernels.dgc import ref as dgc_ref  # noqa: E402
from repro.kernels.fused_sync.ops import select_topk_rows  # noqa: E402
from repro.launch.steps import (  # noqa: E402
    default_optimizer, make_loss_fn, train_input_specs,
)
from repro.launch.train import train  # noqa: E402
from repro.models.transformer import init_model  # noqa: E402
from repro.obs.jaxprof import device_memory_stats  # noqa: E402
from repro.optim import constant_lr  # noqa: E402
from repro.utils.compile_cache import use_compile_cache  # noqa: E402
from repro.utils.jaxcompat import make_mesh  # noqa: E402

ARCH = "olmo-1b"
LAYERS = 2  # the most olmo-1b layers whose HFL state and steps fit one chip
BATCH_PER_MU = 4
SEQ = 1024


class Checks:
    """Prints each check as it is made and remembers the failures."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"[check] {name}: {'ok' if ok else 'FAILED'}  {detail}")
        if not ok:
            self.failed.append(name)


def device_or_exit() -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX's platform is {d.platform!r}, not 'tpu'; "
            "this smoke runs on a TPU only")
    print(f"[smoke] platform={d.platform} device_kind={d.device_kind} "
          f"device_count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def smoke_config():
    base = get_config(ARCH)
    cfg = dataclasses.replace(base, num_layers=LAYERS)
    print(f"[smoke] config {cfg.name}: d_model={cfg.d_model} "
          f"heads={cfg.num_heads}x{cfg.resolved_head_dim} "
          f"kv_heads={cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} tied_embeddings={cfg.tie_embeddings} "
          f"dtype={cfg.dtype}; cut: num_layers {base.num_layers} -> "
          f"{cfg.num_layers}")
    return cfg


@jax.jit
def divergence(params):
    """Largest |w_n - w_0| over clusters and leaves (0.0 after a sync)."""
    return jnp.max(jnp.stack([jnp.max(jnp.abs(p - p[:1]))
                              for p in jax.tree.leaves(params)]))


@jax.jit
def all_finite(tree):
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(x))
                              for x in jax.tree.leaves(tree)]))


def tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def check_kernels(check: Checks) -> None:
    """The Pallas kernels, compiled, against their references."""
    check("kernels compiled, not interpreted", not interpret_mode(),
          f"backend={jax.default_backend()}")
    n = 3 * (1 << 20) + 4321
    ku, kv, kg = jax.random.split(jax.random.PRNGKey(11), 3)
    u, v, g = (jax.random.normal(k, (n,)) for k in (ku, kv, kg))
    outs = dgc_ops.dgc_step_pallas(u, v, g, 0.9, 0.99)
    refs = jax.jit(
        lambda u, v, g: dgc_ref.dgc_step_ref(u, v, g, 0.9, 0.99))(u, v, g)
    close = all(bool(jnp.allclose(a, b, rtol=1e-5, atol=1e-6))
                for a, b in zip(outs, refs))
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(outs, refs))
    hlo = dgc_ops.dgc_step_pallas.lower(u, v, g, 0.9, 0.99).compile().as_text()
    check("dgc_step_pallas == kernels/dgc/ref.py", close,
          f"n={n} phi=0.99 max|diff|={err} "
          f"mosaic_calls={hlo.count('tpu_custom_call')}")

    rng = np.random.default_rng(12)
    nbits = 5 * (1 << 20) + 77
    mask = rng.random(nbits) < 0.3
    got = bitpack_ops.bitpack_bytes(jnp.asarray(mask, jnp.float32))
    want = np.packbits(mask, bitorder="little").tobytes()
    check("bitpack_bytes == np.packbits(bitorder='little')", got == want,
          f"n={nbits} bytes={len(got)}")


def check_syncs(check: Checks, state, hfl: HFLConfig):
    """The fused selection against ``lax.top_k`` on the uplink drift, then
    a topk, a fused and a pallas sync on ``state``, each called twice (the
    first call compiles, the second is timed); returns the new state."""
    t1 = hfl.tiers[1]
    k_ul = keep_count(sum(x.size for x in jax.tree.leaves(state.w_ref)),
                      t1.phi_up)

    @jax.jit
    def mismatches(row):
        _, idx_f = select_topk_rows(row[None], k_ul)
        _, idx_t = jax.lax.top_k(jnp.abs(row), k_ul)
        return jnp.sum(idx_f[0] != idx_t)

    # the uplink drift rows the fused sync selects from, one cluster at a
    # time: both selections of all rows at once would not fit beside the state
    s = jax.jit(lambda st: _pack_drift(st, t1.beta_up)[0])(state)
    mism = [int(mismatches(s[n])) for n in range(s.shape[0])]
    del s
    check("fused uplink indices == topk", not any(mism),
          f"k={k_ul} per cluster, mismatches={mism}")
    for impl in ("topk", "fused", "pallas"):
        sync = jit_sync_step(make_sync(SyncPlan.from_config(
            dataclasses.replace(hfl, omega_impl=impl))))
        t0 = time.perf_counter()
        state = sync(state)
        div = float(divergence(state.params))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        state = jax.block_until_ready(sync(state))
        again = time.perf_counter() - t0
        check(f"omega_impl={impl} sync: divergence 0.0, finite",
              div == 0.0 and bool(all_finite(state)),
              f"divergence={div} first_call_s={first:.2f} "
              f"second_call_s={again:.3f}")
    return state


def one_chip() -> Checks:
    check = Checks()
    cfg = smoke_config()
    hfl = HFLConfig(tiers=parse_tiers_spec("2x2:H=2"), sync_mode="sparse",
                    omega_impl="topk", sync_layout="flat")
    res = train(cfg, hfl, steps=6, batch_per_mu=BATCH_PER_MU, seq=SEQ,
                log_every=1)
    state = res.state
    print(f"[smoke] HFLState bytes={tree_bytes(state)} "
          f"(params, opt, eps x{hfl.num_clusters}; w_ref; e)")
    print(f"[smoke] timing {res.timing}")
    losses = res.hist + [res.eval_loss]
    check("losses finite", bool(np.all(np.isfinite(losses))),
          f"train={res.hist} eval={res.eval_loss}")
    div = float(divergence(state.params))
    check("post-sync divergence == 0.0", div == 0.0, f"divergence={div}")
    check_kernels(check)
    state = check_syncs(check, state, hfl)
    mem = device_memory_stats()
    print(f"[smoke] peak_bytes={mem.get('peak_bytes')} (in use "
          f"{mem.get('peak_bytes_in_use')} + reserved "
          f"{mem.get('peak_bytes_reserved')}) "
          f"bytes_limit={mem.get('bytes_limit')}")
    return check


# ---------------------------------------------------------------------------
# Four chips: the cross-cluster exchange
# ---------------------------------------------------------------------------


def mesh_state(cfg, hfl, mesh):
    """HFL state built sharded on ``mesh`` (pod-leading specs), after two
    train steps on per-cluster data; also returns the param specs."""
    shape = ShapeConfig("smoke", SEQ, hfl.total_mus * BATCH_PER_MU, "train")
    state_sds, batch_sds, pspecs = train_input_specs(cfg, shape, mesh, hfl)
    opt = default_optimizer()
    state = jax.jit(
        lambda: hfl_init(init_model(jax.random.PRNGKey(0), cfg), opt, hfl),
        out_shardings=jax.tree.map(lambda s: s.sharding, state_sds))()
    step = jax.jit(make_cluster_train_step(make_loss_fn(cfg), opt,
                                           constant_lr(0.05)),
                   donate_argnums=0)
    lm, rng = SyntheticLM(cfg.vocab_size, seed=1), np.random.default_rng(2)
    tok = batch_sds["tokens"]
    for _ in range(2):
        toks = lm.sample(tok.shape[0] * tok.shape[1], tok.shape[2], rng)
        batch = {"tokens": jax.device_put(toks.reshape(tok.shape),
                                          tok.sharding)}
        state, losses = step(state, batch)
    print(f"[mesh] {cfg.name}: train losses {np.asarray(losses).tolist()}")
    return state, pspecs


@jax.jit
def drift_and_ref(state):
    """Mean cluster drift and a copy of ``w_ref``, taken before the sync."""
    drift = jax.tree.map(
        lambda p, w: jnp.mean(p.astype(jnp.float32), 0) - w.astype(jnp.float32),
        state.params, state.w_ref)
    return drift, jax.tree.map(lambda w: jnp.array(w, jnp.float32, copy=True),
                               state.w_ref)


@jax.jit
def conservation(out, drift, wr0):
    """First sync, zero error buffers: applied + buffered == mean drift, as
    the largest excess over the ``rtol=1e-4, atol=1e-5`` band of the
    host-mesh test (<= 0 holds)."""
    return jnp.max(jnp.stack([
        jnp.max(jnp.abs(a - d) - (1e-5 + 1e-4 * jnp.abs(d)))
        for a, d in zip(
            [(w1 - w0) + jnp.mean(eps, 0) + e for w1, w0, eps, e in zip(
                jax.tree.leaves(out.w_ref), jax.tree.leaves(wr0),
                jax.tree.leaves(out.eps), jax.tree.leaves(out.e))],
            jax.tree.leaves(drift))]))


@jax.jit
def adoption(out):
    """Entries of the clusters' params that are not ``w_ref`` rounded to the
    params' dtype (0 after a sync). Compared as bit patterns: XLA may drop
    an f32 -> bf16 -> f32 round trip as excess precision, which would
    compare the bf16 params with the unrounded f32 ``w_ref``."""
    off = []
    for p, w in zip(jax.tree.leaves(out.params), jax.tree.leaves(out.w_ref)):
        bits = jnp.dtype(f"uint{8 * p.dtype.itemsize}")
        rounded = jax.lax.bitcast_convert_type(w.astype(p.dtype), bits)
        off.append(jnp.sum(jax.lax.bitcast_convert_type(p, bits)
                           != rounded[None]))
    return jnp.sum(jnp.stack(off))


def four_chips() -> Checks:
    check = Checks()
    mesh = make_mesh((4, 1, 1), ("pod", "data", "model"))
    hfl = HFLConfig(tiers=parse_tiers_spec("4x2:H=2"), sync_mode="sparse",
                    omega_impl="topk", sync_layout="flat")
    print(f"[mesh] {dict(mesh.shape)} over {[d.id for d in mesh.devices.flat]}")

    cfg = smoke_config()
    state, pspecs = mesh_state(cfg, hfl, mesh)
    print(f"[mesh] HFLState bytes={tree_bytes(state)} over 4 chips")
    drift, wr0 = drift_and_ref(state)
    sync = jit_sync_step(make_sync(SyncPlan.from_config(
        hfl, mesh=mesh, param_specs=pspecs)))
    t0 = time.perf_counter()
    out = sync(state)
    div = float(divergence(out.params))
    dt = time.perf_counter() - t0
    cons = float(conservation(out, drift, wr0))
    off = int(adoption(out))
    check("pod shard_map sync: divergence == 0.0", div == 0.0,
          f"divergence={div} first_call_s={dt:.2f}")
    check("conservation: applied + buffered == mean drift", cons <= 0.0,
          f"max excess over tolerance={cons}")
    check("clusters adopt w_ref (rounded to the params' dtype)", off == 0,
          f"entries that differ={off}")
    check("finite after sync", bool(all_finite(out)))
    del state, out, drift, wr0

    red = get_config(ARCH).reduced()
    state, pspecs = mesh_state(red, hfl, mesh)
    local_in = jax.device_put(state, jax.devices()[0])
    local = jax.jit(make_sync(SyncPlan.from_config(hfl)))(local_in)
    meshed = jax.jit(make_sync(SyncPlan.from_config(
        hfl, mesh=mesh, param_specs=pspecs)))(state)
    pairs = list(zip(jax.tree.leaves(meshed.w_ref), jax.tree.leaves(local.w_ref)))
    err = max(float(jnp.max(jnp.abs(jax.device_get(a) - jax.device_get(b))))
              for a, b in pairs)
    same = all(np.allclose(jax.device_get(a), jax.device_get(b),
                           rtol=1e-6, atol=1e-7) for a, b in pairs)
    check(f"{red.name}: mesh sync w_ref == one-device local flat sync w_ref",
          same, f"max|diff|={err}")
    return check


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the cross-cluster exchange on a "
                         "(4,1,1) pod mesh")
    args = ap.parse_args(argv)
    device = device_or_exit()
    if device["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} TPU chips, JAX sees {device['count']}")
    use_compile_cache()
    check = four_chips() if args.chips == 4 else one_chip()
    if check.failed:
        print(f"chip_smoke: failed checks: {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
