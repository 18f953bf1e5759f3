"""The Pallas kernels, compiled by the TPU compiler for a described v5e chip.

Interpret mode (what the rest of the suite runs on the CPU) accepts kernels
that Mosaic refuses: unaligned per-block outputs, shape casts it cannot
lower, more VMEM than a core has. These tests compile each kernel with
``interpret=False`` for a v5e that is described, not attached, so such a
kernel fails here with no chip. Nothing runs; results are checked by the
interpret-mode tests and by ``chip_smoke.py`` on the chip.

The topology is described in a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.bitpack import kernel as B
from repro.kernels.dgc import kernel as K
from repro.models.transformer import init_model


@pytest.fixture(scope="module", params=["one_block", "smoke"])
def rows(request):
    """Rows of the kernels' (rows, 1024) tiling: one grid block, and the
    flat model chip_smoke.py selects over (olmo-1b at its published widths,
    cut to 2 layers), padded to whole blocks."""
    if request.param == "one_block":
        return K.BLOCK_ROWS
    cfg = dataclasses.replace(get_config("olmo-1b"), num_layers=2)
    shapes = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    block = K.BLOCK_ROWS * K.BLOCK_COLS
    return -(-n // block) * K.BLOCK_ROWS


@pytest.fixture(scope="module")
def one_chip():
    """A single-device sharding on a described v5e, with the persistent
    compile cache off (a program compiled for a described chip can be
    written to it but not read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs out of the file system
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _tiles(sharding, rows, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((rows, K.BLOCK_COLS), dtype, sharding=sharding)


def _compile_for_chip(fn, *args):
    """Compile ``fn`` for the described chip; the kernel must be there as a
    Mosaic custom call, not lowered to XLA ops by the interpreter."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_update_max_compiles_for_chip(one_chip, rows):
    t = _tiles(one_chip, rows)
    _compile_for_chip(
        lambda u, v, g: K.update_max(u, v, g, 0.9, interpret=False), t, t, t)


def test_tail_hist_compiles_for_chip(one_chip, rows):
    edges = jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one_chip)
    _compile_for_chip(lambda v, e: K.tail_hist(v, e, interpret=False),
                      _tiles(one_chip, rows), edges)


def test_apply_mask_compiles_for_chip(one_chip, rows):
    t = _tiles(one_chip, rows)
    _compile_for_chip(
        lambda u, v: K.apply_mask(u, v, 0.5, interpret=False), t, t)


def test_bitpack_compiles_for_chip(one_chip, rows):
    _compile_for_chip(lambda m: B.bitpack(m, interpret=False),
                      _tiles(one_chip, rows))
