"""Where compiled XLA programs are kept between processes."""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore): a fixed path, so every run
# from this checkout finds the programs an earlier run compiled
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
# the checkout's root, as the interpreter may name it (symlinks resolved or
# not): stripped from the source paths in the HLO metadata
_ROOTS = sorted({str(Path(p).parents[3]) for p in (
    os.path.abspath(__file__), os.path.realpath(__file__))})


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Called from entry points only, never on
    import, so tests and library callers keep JAX's defaults.

    The HLO metadata (op names, which carry the programs' named scopes, and
    source lines) is part of the cache's key: by default JAX leaves it out,
    and a program loaded from the cache then keeps the names of whichever
    build compiled it first, which is what a profile shows. The source
    paths in that metadata are made relative to the checkout, so that a
    checkout at another path finds the same programs.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        "^(?:" + "|".join(re.escape(r + os.sep) for r in _ROOTS) + ")")
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
