"""The least time one sync could take on the chip's HBM (the least bytes the
cell's sync_mode must move, from ``work.py``, over peak HBM bytes/s) over
the measured device time of one sync, in %. Bytes bound it: the sync does
no matmul work."""
from bench.metrics import module_ms


def read(ctx):
    ms = module_ms(ctx, "sync")
    if not ms:
        return None
    least_s = ctx["work"]["sync_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
