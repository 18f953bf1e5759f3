"""Work counts computed from a configuration's shapes: the yardstick that the
utilisation and roofline readers divide by measured time.

Model FLOPs (training) follow the PaLM convention (Chowdhery et al. 2022,
appendix B): 6 FLOPs per matmul parameter per token for forward plus
backward, plus ``12 * layers * d_model * seq`` for attention scores and
values, counted over the full (not causally halved) context. Recomputation
is not counted. The vocabulary is the published one, not the padded
storage, and the tied LM head counts as a matmul while the embedding
lookup does not.

Sync bytes are the least HBM traffic one consensus step must move: every
state buffer the protocol reads, read once, and every buffer it writes,
written once. Sizes of the HFL state (bf16 params, f32 momentum, f32 error
and reference buffers) are those of ``core/hfl.hfl_init``.
"""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters that enter a matmul for each token: attention and SwiGLU
    projections of every layer, and the LM head over the published vocab."""
    d, f = m["d_model"], m["d_ff"]
    q = m["num_heads"] * m["head_dim"]
    kv = m["num_kv_heads"] * m["head_dim"]
    attn = d * q + 2 * d * kv + q * d
    ffn = 3 * d * f
    return m["num_layers"] * (attn + ffn) + d * m["vocab_size"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward plus backward FLOPs per trained token."""
    attn = 12 * m["num_layers"] * m["num_heads"] * m["head_dim"] * seq
    return 6.0 * matmul_params(m) + attn


def keep_count(size: int, phi: float) -> int:
    """Entries an Ω(φ) payload keeps (the protocol's rounding)."""
    return max(1, int(round((1.0 - phi) * size)))


def sync_bytes(mode: str, *, q: int, clusters_here: int, clusters: int,
               phi_up: float, exchange: bool) -> float:
    """Least HBM bytes one sync moves on one chip.

    ``q`` is the flat model length, ``clusters_here`` the clusters whose
    state this chip holds, ``clusters`` all of them. Sparse modes read and
    write each held cluster's bf16 params and f32 uplink error (12 B per
    entry) and the f32 reference and downlink error (16 B per entry); with a
    cross-chip ``exchange`` the gathered (f32 value, int32 index) payloads of
    every cluster are written once. Dense averaging reads and writes the
    params and writes the f32 reference.
    """
    c = clusters_here
    if mode == "dense":
        return float(4 * c * q + 4 * q)
    if mode in ("sparse", "quantized_sparse"):
        b = 12 * c * q + 16 * q
        if exchange:
            b += 8 * clusters * keep_count(q, phi_up)
        return float(b)
    raise ValueError(f"no byte count for sync_mode {mode!r}")
