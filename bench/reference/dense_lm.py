"""Plain reference of a dense decoder LM trained under the HFL protocol.

Written from the published descriptions, in float32 ``jax.numpy`` with
``precision="highest"`` matmuls, no kernels, no scan, no flash attention:

* OLMo (arXiv:2402.00838): pre-norm decoder blocks with non-parametric
  LayerNorm (no scale or bias), rotary position embeddings on queries and
  keys (halves rotated, base ``rope_theta``), causal softmax attention,
  SwiGLU feed-forward ``(silu(x Wg) * x Wu) Wd``, no biases, tied input and
  output embeddings, next-token cross-entropy over the published vocabulary.
* SGD with momentum and decoupled-from-nothing L2 weight decay on every
  matrix: ``m <- mu m + g + wd p``, ``p <- p - lr m``, params kept in the
  configuration's dtype.
* The hierarchical FL consensus (arXiv:1909.02362, Alg. 5) over the whole
  model as one vector: every cluster sends the top-k of its drift plus
  discounted uplink error, the root averages, adds its discounted downlink
  error, sends back the top-k, and every cluster adopts the new reference.
  Dense averaging is the plain mean of the clusters' models.

It reads the weight tree by leaf name (``embed``; ``blocks`` stacked over
layers with ``attn.{wq,wk,wv,wo}`` and ``ffn.{w_gate,w_up,w_down}``) and
imports nothing of the program under test. ``mm`` is the one matmul every
projection and attention product goes through, so a control can swap in a
lower precision.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import leaf_names
from bench.work import keep_count


def mm_f32(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision="highest")


def _fp8(x, dtype=jnp.float8_e4m3fn):
    """Per-tensor scaled float8 rounding, as fp8 training does it."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def mm_fp8(a, b):
    """The fp8 recipe: operands rounded to e4m3, the incoming gradient to
    e5m2, each scaled per tensor; products accumulated in f32."""
    return mm_f32(_fp8(a), _fp8(b))


def _mm_fp8_fwd(a, b):
    aq, bq = _fp8(a), _fp8(b)
    return mm_f32(aq, bq), (aq, bq)


def _mm_fp8_bwd(res, g):
    _, vjp = jax.vjp(mm_f32, *res)
    return vjp(_fp8(g, jnp.float8_e5m2))


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


def _layernorm(x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, theta):
    """x [B, T, H, D]: rotate the two halves of each head by position."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
    ang = np.arange(T, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(w, tokens, m: dict, mm=mm_f32):
    """Mean next-token cross-entropy of ``tokens`` [B, T] under weights
    ``w`` (any float dtype; computed in f32)."""
    B, T = tokens.shape
    H, Hkv, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    V = m["vocab_size"]
    emb = w["embed"].astype(jnp.float32)[:V]
    x = emb[tokens]
    causal = np.tril(np.ones((T, T), bool))
    for layer in range(m["num_layers"]):
        a = jax.tree.map(lambda p: p[layer].astype(jnp.float32),
                         w["blocks"]["attn"])
        f = jax.tree.map(lambda p: p[layer].astype(jnp.float32),
                         w["blocks"]["ffn"])
        h = _layernorm(x, m["norm_eps"])
        q = _rope(mm(h, a["wq"]).reshape(B, T, H, D), m["rope_theta"])
        k = _rope(mm(h, a["wk"]).reshape(B, T, Hkv, D), m["rope_theta"])
        v = mm(h, a["wv"]).reshape(B, T, Hkv, D)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        s = mm(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1)) / np.sqrt(D)
        s = jnp.where(causal, s, -jnp.inf)
        o = mm(jax.nn.softmax(s, -1), v.transpose(0, 2, 1, 3))
        x = x + mm(o.transpose(0, 2, 1, 3).reshape(B, T, H * D), a["wo"])
        h = _layernorm(x, m["norm_eps"])
        x = x + mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"]),
                   f["w_down"])
    logits = mm(_layernorm(x, m["norm_eps"]), emb.T)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
    return jnp.mean(nll)


def loss_and_grad(w, tokens, m: dict, mm=mm_f32, *, row_block: int = 2):
    """Loss and f32 gradient of the batch mean, summed over blocks of
    ``row_block`` rows so that the activations fit beside the state."""
    fn = _value_and_grad(m, mm)
    B = tokens.shape[0]
    tot_l, tot_g = 0.0, None
    for i in range(0, B, row_block):
        blk = tokens[i:i + row_block]
        l, g = fn(w, blk)
        n = float(blk.shape[0])
        tot_l = tot_l + n * l
        tot_g = _axpy(n, g, tot_g) if tot_g is not None else _scale(n, g)
    return tot_l / B, _scale(1.0 / B, tot_g)


@jax.jit
def _axpy(a, x, y):
    return jax.tree.map(lambda u, v: a * u + v, x, y)


@jax.jit
def _scale(a, x):
    return jax.tree.map(lambda u: a * u, x)


_VG = {}


def _value_and_grad(m, mm):
    key = (json.dumps(m, sort_keys=True, default=str), mm)
    if key not in _VG:
        def f(w, toks):
            w32 = jax.tree.map(lambda p: p.astype(jnp.float32), w)
            return jax.value_and_grad(loss)(w32, toks, m, mm)
        _VG[key] = jax.jit(f)
    return _VG[key]


@jax.jit
def sgdm(p, mom, g, lr, momentum, wd):
    """One SGD-with-momentum update of a weight tree (every leaf of two or
    more axes decays). Returns (params in their own dtype, f32 momentum)."""
    def upd(p, m_, g):
        g = g + (wd * p.astype(jnp.float32) if p.ndim >= 2 else 0.0)
        m_ = momentum * m_ + g
        return (p.astype(jnp.float32) - lr * m_).astype(p.dtype), m_
    out = jax.tree.map(upd, p, mom, g)
    is_t = lambda t: isinstance(t, tuple)
    return (jax.tree.map(lambda t: t[0], out, is_leaf=is_t),
            jax.tree.map(lambda t: t[1], out, is_leaf=is_t))


# ---------------------------------------------------------------------------
# Consensus over the whole model as one vector
# ---------------------------------------------------------------------------


def flat(tree):
    """f32 vector of a tree's leaves in flattening order."""
    return jnp.concatenate([x.astype(jnp.float32).reshape(-1)
                            for x in jax.tree.leaves(tree)])


def unflat(vec, like):
    """Split ``vec`` back into the leaves of ``like`` (their dtypes)."""
    leaves, treedef = jax.tree.flatten(like)
    out, o = [], 0
    for x in leaves:
        out.append(vec[o:o + x.size].reshape(x.shape).astype(x.dtype))
        o += x.size
    return jax.tree.unflatten(treedef, out)


def top_k_part(x, k: int):
    """``x`` where it is among the k largest magnitudes, else 0. Ties at the
    k-th magnitude go to the lower index. By counting, with no sort: |x|'s
    f32 bits order as its values, so the k-th largest magnitude ``t`` is
    the largest bit pattern that at least k entries reach, found one bit at
    a time; the lowest indices among the entries equal to it then fill the
    rest, up to the least index ``cut`` at which enough of them are in,
    found by bisection. Each step is one pass of counting over ``x``."""
    a = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32)
    n = a.size

    def bit(i, t):
        c = t | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(jnp.sum(a >= c) >= k, c, t)

    t = jax.lax.fori_loop(0, 31, bit, jnp.int32(0))
    gt = a > t
    eq = a == t
    need = k - jnp.sum(gt)
    iota = jnp.arange(n, dtype=jnp.int32)

    def halve(_, lo_hi):
        lo, hi = lo_hi
        mid = lo + (hi - lo) // 2
        enough = jnp.sum(eq & (iota <= mid)) >= need
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    _, cut = jax.lax.fori_loop(0, max(n - 1, 1).bit_length(), halve,
                               (jnp.int32(0), jnp.int32(n - 1)))
    keep = gt | (eq & (iota <= cut) & (need > 0))
    return jnp.where(keep, x, 0.0)


@jax.jit
def _drift(p, wref, ep, beta):
    return (p - wref) + beta * ep


_top = jax.jit(top_k_part, static_argnums=1)
_add = jax.jit(jnp.add)
_sub = jax.jit(jnp.subtract)


@jax.jit
def _mix(total, n, e, beta):
    return total / n + beta * e


_flat = jax.jit(flat)


@jax.jit
def _tree_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _tree_diff_norms(tree, base):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            for x, b in zip(jax.tree.leaves(tree), jax.tree.leaves(base))]


def _segment_norms(vec, sizes):
    """Norm of each leaf's stretch of a flat vector."""
    return [float(v) for v in _segment_norms_dev(vec, tuple(sizes))]


@functools.partial(jax.jit, static_argnums=1)
def _segment_norms_dev(vec, sizes):
    out, o = [], 0
    for n in sizes:
        out.append(jnp.sqrt(jnp.sum(jnp.square(vec[o:o + n]))))
        o += n
    return out


def readings(m: dict, t: dict, dep: dict, weights, pool, *, mm: str = "f32",
             fault: str | None = None, devices=None) -> dict:
    """Readings of the first ``max(3, period)`` steps of the protocol from
    the initial ``weights`` on the token batches ``pool`` [batch][cluster]
    (see ``bench/check.py`` for what each reading is).

    Cluster n lives on ``devices[n % len(devices)]``; the consensus's own
    flat vectors (initial weights, reference, downlink error) wait on the
    host between uses and are computed on the first device, so that no chip
    holds more than its clusters and one sync's working vectors.

    ``mm`` picks the matmul precision (``f32``, or ``fp8`` for the
    control). ``fault`` plants one fault for the harness's own checks:
    ``half_batch`` (each step sees the first half of its rows),
    ``no_exchange`` (only cluster 0's payload reaches the consensus),
    ``answer_altered`` (the consensus update of the embedding is dropped).
    """
    devices = list(devices or jax.devices())
    N, H = dep["clusters"], t["period"]
    devs = [devices[n % len(devices)] for n in range(N)]
    lr = (t["base_lr"] * N * dep["mus_per_cluster"] * t["batch_per_mu"]
          / t["lr_batch"])
    mmf = MATMULS[mm]
    names = leaf_names(weights)
    sizes = [x.size for x in jax.tree.leaves(weights)]
    emb = names.index("embed")
    params = [jax.device_put(weights, d) for d in devs]
    mom = [jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
           for p in params]
    q = sum(sizes)
    eps = [jax.device_put(jnp.zeros((q,), jnp.float32), d) for d in devs]
    w0 = np.asarray(_flat(weights))
    wref, e = w0, np.zeros((q,), np.float32)
    out = {"loss": [], "grad": {}, "sync": {}, "change": {}}
    for step in range(max(3, H)):
        batch = pool[step % len(pool)]
        losses = []
        for n in range(N):
            toks = jax.device_put(np.asarray(batch[n]), devs[n])
            if fault == "half_batch":
                toks = toks[:toks.shape[0] // 2]
            l, g = loss_and_grad(params[n], toks, m, mmf)
            params[n], mom[n] = sgdm(params[n], mom[n], g, lr, t["momentum"],
                                     t["weight_decay"])
            del g
            losses.append(l)
        if step < 3:
            out["loss"].append([float(l) for l in losses])
        if step == 0:
            norms = [_tree_norms(mo) for mo in mom]
            for i, nm in enumerate(names):
                out["grad"][nm] = [float(nr[i]) for nr in norms]
        if (step + 1) % H == 0 and not out["sync"]:
            wref, e = _consensus(params, wref, eps, e, t, devs, q, emb, sizes,
                                 fault)
            for n, d in enumerate(devs):
                params[n] = unflat(jax.device_put(wref, d), params[n])
            for nm, v in zip(names, _segment_norms(_sub(wref, w0), sizes)):
                out["sync"]["d." + nm] = [v]
            for nm, v in zip(names, _segment_norms(e, sizes)):
                out["sync"]["e." + nm] = [v]
            per = [_segment_norms(ep, sizes) for ep in eps]
            for i, nm in enumerate(names):
                out["sync"]["eps." + nm] = [pr[i] for pr in per]
        if step == 2:
            per = [_tree_diff_norms(p, jax.device_put(weights, d))
                   for p, d in zip(params, devs)]
            for i, nm in enumerate(names):
                out["change"][nm] = [float(pr[i]) for pr in per]
    return out


def _consensus(params, wref, eps, e, t, devs, q, emb, sizes, fault):
    """One sync over flat vectors; updates ``eps`` in place and returns the
    new (reference, downlink error), on the host as ``wref`` and ``e`` are."""
    N, root = len(params), devs[0]
    if t["sync_mode"] == "dense":
        total = None
        for n, p in enumerate(params):
            if fault == "no_exchange" and n > 0:
                continue
            pf = jax.device_put(_flat(p), root)
            total = pf if total is None else _add(total, pf)
        count = 1 if fault == "no_exchange" else N
        new = _mix(total, count, jax.device_put(e, root), 0.0)
        if fault == "answer_altered":
            off = sum(sizes[:emb])
            new = new.at[off:off + sizes[emb]].set(wref[off:off + sizes[emb]])
        return np.asarray(new), e
    if t["sync_mode"] != "sparse":  # quantized_sparse needs wire rounding
        raise ValueError(f"no reference for sync_mode {t['sync_mode']!r}")
    k_up, k_dn = keep_count(q, t["phi_up"]), keep_count(q, t["phi_down"])
    total = None
    for n, (p, d) in enumerate(zip(params, devs)):
        s = _drift(_flat(p), jax.device_put(wref, d), eps[n], t["beta_up"])
        sent = _top(s, k_up)
        eps[n] = _sub(s, sent)
        del s
        if fault == "no_exchange" and n > 0:
            continue
        sent = jax.device_put(sent, root)
        total = sent if total is None else _add(total, sent)
    del sent
    delta = _mix(total, N, jax.device_put(e, root), t["beta_down"])
    del total
    dd = _top(delta, k_dn)
    if fault == "answer_altered":
        off = sum(sizes[:emb])
        dd = dd.at[off:off + sizes[emb]].set(0.0)
    e = np.asarray(_sub(delta, dd))
    del delta
    return np.asarray(_add(jax.device_put(wref, root), dd)), e
