"""Pallas TPU kernels for the paper's compute hot-spot: DGC top-k
sparsification (threshold histogram + fused mask/error-update) and the
bitmap codec's bit-pack. See repro.kernels.{dgc,bitpack}.{kernel,ops,ref}."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether ``pallas_call`` runs interpreted: compiled by Mosaic on a TPU,
    interpreted on the CPU (tests). No other backend lowers these kernels,
    so anything else is an error rather than a silent interpreter run."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels run on 'tpu' (compiled) or 'cpu' (interpreted), "
        f"not on {platform!r}")
