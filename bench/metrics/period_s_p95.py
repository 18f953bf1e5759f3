"""95th percentile over the window's HFL periods (H train steps plus one
sync, each timed on the host clock and ended by ``block_until_ready``),
linear interpolation between order statistics."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx["window"]["periods"], 95))
