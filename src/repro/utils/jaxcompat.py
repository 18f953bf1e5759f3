"""The one home of the jax APIs that have moved between releases.

Written against jax 0.9 (``requirements-dev.txt``). Three APIs changed shape
in the releases before it and may move again, so every caller goes through
these wrappers (``tools/lint_jaxcompat.py`` enforces it) and a later
migration is an edit of this file alone:

  * ``shard_map``     : ``jax.shard_map(..., check_vma=False)``
  * ``make_mesh``     : ``jax.make_mesh`` with explicit ``AxisType.Auto`` axes
  * ``cost_analysis`` : ``Compiled.cost_analysis()``, a flat ``dict``
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """Fully-manual shard_map with replication checking off (our sync
    functions are deliberately non-replicated over "pod")."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def make_mesh(shape, axes):
    """jax.make_mesh with explicit Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def cost_analysis_dict(compiled) -> dict:
    """Compiled.cost_analysis() as a plain dict."""
    return dict(compiled.cost_analysis())
