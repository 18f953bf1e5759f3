"""Attention layers: GQA (+RoPE, sliding window) and MLA (DeepSeek-V2).

Prefill/train use a chunked online-softmax (flash-style) implementation so the
score matrix never materialises beyond [*, q_chunk, kv_chunk]; decode attends
one query against the cache. All softmax math in f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import apply_norm, apply_rope, dense_init, init_norm, rope_angles

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Chunked flash attention (GQA-aware)
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, *, q_offset=0, window=0, q_chunk=512, kv_chunk=512):
    """Causal attention. q [B,T,H,D]; k,v [B,S,Hkv,D]; returns [B,T,H,D].

    ``window`` > 0 enables sliding-window masking (key kept iff
    q_pos - window < k_pos <= q_pos). ``q_offset`` is the absolute position of
    q[0] (k positions start at 0). Its ops sit under the named scope
    ``attention``, in the forward pass and, under ``transpose(...)``, in the
    backward one.
    """
    with jax.named_scope("attention"):
        return _flash_attention(q, k, v, q_offset=q_offset, window=window,
                                q_chunk=q_chunk, kv_chunk=kv_chunk)


def _flash_attention(q, k, v, *, q_offset, window, q_chunk, kv_chunk):
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(D)

    qc = min(q_chunk, T)
    kc = min(kv_chunk, S)
    assert T % qc == 0 and S % kc == 0, (T, qc, S, kc)
    nq, nk = T // qc, S // kc

    qr = q.reshape(B, nq, qc, Hkv, G, D)
    qr = jnp.moveaxis(qr, 1, 0)  # [nq,B,qc,Hkv,G,D]
    kr = jnp.moveaxis(k.reshape(B, nk, kc, Hkv, D), 1, 0)
    vr = jnp.moveaxis(v.reshape(B, nk, kc, Hkv, D), 1, 0)
    qpos = q_offset + jnp.arange(T, dtype=jnp.int32).reshape(nq, qc)
    kpos = jnp.arange(S, dtype=jnp.int32).reshape(nk, kc)

    def q_step(_, qi):
        qblk, qp = qi  # [B,qc,Hkv,G,D], [qc]

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kp = ki
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qblk, kblk, preferred_element_type=jnp.float32
            ) * scale
            mask = qp[:, None] >= kp[None, :]
            if window:
                mask &= kp[None, :] > (qp[:, None] - window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vblk.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qc, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kr, vr, kpos))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out  # [B,Hkv,G,qc,D]

    # checkpoint per q-block: backward recomputes the kv sweep tile-by-tile
    # instead of stacking every [*, qc, kc] score matrix (O(T^2) memory).
    _, outs = jax.lax.scan(jax.checkpoint(q_step), None, (qr, qpos))  # [nq,B,Hkv,G,qc,D]
    out = jnp.moveaxis(outs, 0, 3)  # [B,Hkv,G,nq,qc,D]
    out = out.reshape(B, Hkv, G, T, D)
    out = jnp.moveaxis(out.reshape(B, Hkv * G, T, D), 1, 2)  # [B,T,H,D]
    return out.astype(q.dtype)


def decode_attention(q, k, v, slot_pos, q_pos, *, window=0):
    """One-token attention against a cache.

    q [B,1,H,D]; k,v [B,S,Hkv,D]; slot_pos [B,S] absolute position held by each
    cache slot (-1 = empty); q_pos [B] absolute position of the query.
    """
    B, _, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(D)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k, preferred_element_type=jnp.float32) * scale
    valid = (slot_pos >= 0) & (slot_pos <= q_pos[:, None])
    if window:
        valid &= slot_pos > (q_pos[:, None] - window)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA layer
# ---------------------------------------------------------------------------


def init_gqa(key, cfg):
    d, H, Hkv, D = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    return {
        "wq": dense_init(ks[0], d, H * D, dt),
        "wk": dense_init(ks[1], d, Hkv * D, dt),
        "wv": dense_init(ks[2], d, Hkv * D, dt),
        "wo": dense_init(ks[3], H * D, d, dt, scale=1.0 / np.sqrt(H * D)),
    }


def gqa_forward(p, x, cfg, *, window=None):
    """Full-sequence (train/prefill) GQA. x [B,T,d] -> [B,T,d]."""
    B, T, _ = x.shape
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, T, H, D)
    k = (x @ p["wk"]).reshape(B, T, Hkv, D)
    v = (x @ p["wv"]).reshape(B, T, Hkv, D)
    cos, sin = rope_angles(jnp.arange(T), D, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    w = cfg.sliding_window if window is None else window
    out = flash_attention(q, k, v, window=w)
    return out.reshape(B, T, H * D) @ p["wo"]


def gqa_fill_cache(p, x, cfg):
    """Compute roped k/v for the whole prompt (prefill cache production)."""
    B, T, _ = x.shape
    Hkv, D = cfg.num_kv_heads, cfg.resolved_head_dim
    k = (x @ p["wk"]).reshape(B, T, Hkv, D)
    v = (x @ p["wv"]).reshape(B, T, Hkv, D)
    cos, sin = rope_angles(jnp.arange(T), D, cfg.rope_theta)
    return apply_rope(k, cos, sin), v


def gqa_decode(p, x, cache_k, cache_v, slot_pos, slot, pos, cfg, *, window=None):
    """One-token GQA. x [B,1,d]; cache_k/v [B,S,Hkv,D]; pos [B] abs position.

    ``slot`` [B] is the (caller-computed) cache slot to write; ``slot_pos``
    must already record ``pos`` at ``slot``. Returns (out, new_k, new_v).
    """
    B = x.shape[0]
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, 1, H, D)
    k = (x @ p["wk"]).reshape(B, 1, Hkv, D)
    v = (x @ p["wv"]).reshape(B, 1, Hkv, D)
    cos, sin = rope_angles(pos[:, None], D, cfg.rope_theta)  # [B,1,D/2]
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    bidx = jnp.arange(B)
    cache_k = cache_k.at[bidx, slot].set(k[:, 0])
    cache_v = cache_v.at[bidx, slot].set(v[:, 0])
    w = cfg.sliding_window if window is None else window
    out = decode_attention(q, cache_k, cache_v, slot_pos, pos, window=w)
    out = out.reshape(B, 1, H * D) @ p["wo"]
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2)
# ---------------------------------------------------------------------------


def init_mla(key, cfg):
    d = cfg.d_model
    H = cfg.num_heads
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 7)
    dt = jnp.dtype(cfg.dtype)
    return {
        "w_dq": dense_init(ks[0], d, qr, dt),
        "w_uq": dense_init(ks[1], qr, H * (dn + dr), dt),
        "q_norm": init_norm(cfg, qr),
        "w_dkv": dense_init(ks[2], d, r + dr, dt),
        "kv_norm": init_norm(cfg, r),
        "w_uk": (jax.random.normal(ks[3], (r, H, dn)) / np.sqrt(r)).astype(dt),
        "w_uv": (jax.random.normal(ks[4], (r, H, dv)) / np.sqrt(r)).astype(dt),
        "wo": dense_init(ks[5], H * dv, d, dt),
    }


def _mla_q(p, x, cfg, positions):
    B, T, _ = x.shape
    H = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = apply_norm(p["q_norm"], x @ p["w_dq"], cfg)
    q = (cq @ p["w_uq"]).reshape(B, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    cos, sin = rope_angles(positions, dr, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _mla_ckv(p, x, cfg, positions):
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    ckv_full = x @ p["w_dkv"]
    ckv = apply_norm(p["kv_norm"], ckv_full[..., :r], cfg)
    k_rope = ckv_full[..., r:]  # [B,T,dr] shared across heads
    cos, sin = rope_angles(positions, dr, cfg.rope_theta)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]
    return ckv, k_rope


def mla_forward(p, x, cfg):
    """Train/prefill MLA: expand the latent per kv-chunk, run flash (MHA)."""
    B, T, _ = x.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    positions = jnp.arange(T)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    ckv, k_rope = _mla_ckv(p, x, cfg, positions)
    k_nope = jnp.einsum("btr,rhd->bthd", ckv, p["w_uk"])
    v = jnp.einsum("btr,rhd->bthd", ckv, p["w_uv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, dr))], axis=-1)
    if dv < dn + dr:  # pad v so flash sees uniform D, slice after
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
    out = flash_attention(q, k, v)[..., :dv]
    return out.reshape(B, T, H * dv) @ p["wo"]


def mla_fill_cache(p, x, cfg):
    positions = jnp.arange(x.shape[1])
    return _mla_ckv(p, x, cfg, positions)  # (ckv [B,T,r], k_rope [B,T,dr])


def mla_decode(p, x, cache_ckv, cache_kr, slot_pos, slot, pos, cfg):
    """Absorbed one-token MLA: score/output directly in the latent space."""
    B = x.shape[0]
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(p, x, cfg, pos[:, None])
    ckv, k_rope = _mla_ckv(p, x, cfg, pos[:, None])
    bidx = jnp.arange(B)
    cache_ckv = cache_ckv.at[bidx, slot].set(ckv[:, 0])
    cache_kr = cache_kr.at[bidx, slot].set(k_rope[:, 0])
    # absorb W_uk into q:   q_abs [B,H,r]
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], p["w_uk"])
    s = (
        jnp.einsum("bhr,bsr->bhs", q_abs.astype(jnp.float32), cache_ckv.astype(jnp.float32))
        + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32), cache_kr.astype(jnp.float32))
    ) / np.sqrt(dn + dr)
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    s = jnp.where(valid[:, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", w, cache_ckv.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bhr,rhd->bhd", o_lat, p["w_uv"]).reshape(B, 1, H * dv)
    return out @ p["wo"], cache_ckv, cache_kr
