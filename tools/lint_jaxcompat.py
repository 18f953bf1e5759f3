#!/usr/bin/env python
"""Lint: version-sensitive jax APIs must route through utils/jaxcompat.py.

Three jax APIs changed shape across past releases: ``shard_map`` (module
+ kwarg rename), ``make_mesh`` (the ``axis_types=``/``AxisType`` kwarg),
and ``Compiled.cost_analysis()`` (per-device list vs flat dict).
``repro/utils/jaxcompat.py`` holds the one call of each, written against
the installed jax (``requirements-dev.txt``), so the next move is an edit
of that file alone. This linter fails the build on ANY direct use
elsewhere, before a second call site can land.

AST-based, so mentions in comments/docstrings (including this one) don't
trip it. Exit 1 on findings.

  python tools/lint_jaxcompat.py [paths...]   # default: src tests benchmarks examples
"""
from __future__ import annotations

import ast
import sys
from pathlib import Path

# the one module allowed to touch the drifting APIs directly
ALLOWED = Path("src/repro/utils/jaxcompat.py")
DEFAULT_SCAN = ("src", "tests", "benchmarks", "examples", "tools")

# fully-qualified attribute chains that must not appear outside ALLOWED
BANNED_CHAINS = {
    "jax.shard_map": "repro.utils.jaxcompat.shard_map",
    "jax.experimental.shard_map.shard_map": "repro.utils.jaxcompat.shard_map",
    "jax.make_mesh": "repro.utils.jaxcompat.make_mesh",
    "jax.sharding.AxisType": "repro.utils.jaxcompat.make_mesh (Auto axes)",
}
# bare attribute accesses (any receiver) that must not appear outside ALLOWED
BANNED_ATTRS = {
    "cost_analysis": "repro.utils.jaxcompat.cost_analysis_dict",
}
# modules whose import is itself version-sensitive
BANNED_MODULES = {
    "jax.experimental.shard_map": "repro.utils.jaxcompat.shard_map",
}


def _attr_chain(node: ast.Attribute) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""  # computed receiver: not a plain a.b.c chain


def scan_file(path: Path) -> list:
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except SyntaxError as e:  # a broken file is its own CI failure
        return [(path, e.lineno or 0, f"syntax error: {e.msg}", "")]
    hits = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute):
            chain = _attr_chain(node)
            if chain in BANNED_CHAINS:
                hits.append((path, node.lineno, chain, BANNED_CHAINS[chain]))
            elif node.attr in BANNED_ATTRS:
                hits.append((path, node.lineno, f"<expr>.{node.attr}",
                             BANNED_ATTRS[node.attr]))
        elif isinstance(node, ast.Import):
            for alias in node.names:
                for mod, fix in BANNED_MODULES.items():
                    if alias.name == mod or alias.name.startswith(mod + "."):
                        hits.append((path, node.lineno,
                                     f"import {alias.name}", fix))
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            for banned, fix in BANNED_MODULES.items():
                if mod == banned or mod.startswith(banned + "."):
                    hits.append((path, node.lineno, f"from {mod} import ...",
                                 fix))
            if mod == "jax.experimental" and any(
                    a.name == "shard_map" for a in node.names):
                hits.append((path, node.lineno,
                             "from jax.experimental import shard_map",
                             BANNED_MODULES["jax.experimental.shard_map"]))
    return hits


def main(argv=None) -> int:
    roots = [Path(p) for p in (argv if argv else DEFAULT_SCAN)]
    allowed = ALLOWED.resolve()
    files = []
    for root in roots:
        if root.is_file():
            files.append(root)
        else:
            files.extend(sorted(root.rglob("*.py")))
    hits = []
    for f in files:
        if f.resolve() == allowed:
            continue
        hits.extend(scan_file(f))
    for path, line, what, fix in hits:
        print(f"{path}:{line}: version-sensitive jax API `{what}` — "
              f"use {fix} instead")
    if hits:
        print(f"lint_jaxcompat: {len(hits)} finding(s); these APIs move "
              f"between jax releases — route them through "
              f"repro/utils/jaxcompat.py", file=sys.stderr)
        return 1
    print(f"lint_jaxcompat: ok ({len(files)} files clean)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
