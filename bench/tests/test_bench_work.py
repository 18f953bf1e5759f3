"""The work counts that the utilisation and roofline readers divide by."""
import json
from pathlib import Path

import pytest

from bench import work

ROOT = Path(__file__).resolve().parents[2]
CONF = json.loads((ROOT / "bench/configs/olmo1b-l2-c2.json").read_text())
Q = 238_026_757  # flat length of olmo-1b at 2 layers, padded vocab 50688


@pytest.mark.parametrize("layers,params,gflop", [
    (2, 237_240_320, 1.47), (3, 304_349_184, 1.90)])
def test_flops_per_token_matches_the_hand_count(layers, params, gflop):
    # layers x (4 x 2048^2 attention + 3 x 2048 x 8192 SwiGLU) plus the
    # tied head 2048 x 50304 matmul parameters; PaLM's 6N + 12 L d T at
    # T = 1024
    m = {**CONF, "num_layers": layers}
    assert work.matmul_params(m) == params
    f = work.train_flops_per_token(m, 1024)
    assert f == 6 * params + 12 * layers * 2048 * 1024
    assert f == pytest.approx(gflop * 1e9, rel=0.005)


@pytest.mark.parametrize("mode,chips,clusters,want", [
    ("sparse", 1, 2, 40 * Q),
    ("dense", 1, 2, 12 * Q),
    ("sparse", 4, 4, 28 * Q + 8 * 4 * work.keep_count(Q, 0.9)),
])
def test_sync_bytes(mode, chips, clusters, want):
    got = work.sync_bytes(mode, q=Q, clusters=clusters,
                          clusters_here=clusters // chips, phi_up=0.9,
                          exchange=chips > 1)
    assert got == want


def test_sync_bytes_refuses_an_unknown_mode():
    with pytest.raises(ValueError):
        work.sync_bytes("gossip", q=Q, clusters=2, clusters_here=2,
                        phi_up=0.9, exchange=False)
