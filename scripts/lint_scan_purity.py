#!/usr/bin/env python
"""Scan-purity lint: keep host ops out of the engines' jitted scans.

The ≤-1-host-sync-per-revolution contract dies quietly: one
``jax.debug.print`` in a scan body becomes a per-pass host callback, a
``.block_until_ready()`` forces a sync, and a stray ``np.`` call bakes
a host-computed constant into the trace (or crashes on tracers weeks
later).  This lint walks the AST of each engine's device-program
builder (the ``_compiled`` methods, plus :func:`repro.obs.ring.record`
which runs inside them) and fails on the four footguns:

* ``jax.debug.print`` / ``jax.debug.callback`` / ``jax.debug.breakpoint``
* any ``.block_until_ready`` attribute access
* any use of ``np.`` / ``numpy.`` (host NumPy inside a traced scope)
* any use of ``jax.profiler.`` (a host span or annotation there fires
  once, while the function is traced, and times nothing on the device;
  name device work with ``jax.named_scope``)

Wired into ``scripts/check.sh``.  Exit 0 = clean, 1 = violations
(printed as ``path:line: message``), 2 = a guarded scope disappeared —
update ``SCOPES`` when refactoring the engines.
"""
from __future__ import annotations

import ast
import sys
from typing import List, Tuple

#: file (repo-relative) -> function/method names whose whole body must
#: stay device-pure (any nesting depth inside them counts)
SCOPES = {
    "src/repro/sim/device_sim.py": ("_compiled",),
    "src/repro/fleet/engine.py": ("_compiled",),
    "src/repro/serve_fleet/engine.py": ("_compiled",),
    "src/repro/obs/ring.py": ("record",),
    # the ISL exchange runs inside the fleet's jitted scan
    "src/repro/isl/exchange.py": ("async_gossip_step", "sync_exchange_step",
                                  "_charge", "_encode_planes",
                                  "_tree_where", "staleness_weight"),
    "src/repro/isl/codec.py": ("encode_delta", "residual_init"),
    "src/repro/isl/link.py": ("open_at", "contact_index", "offset_at"),
}

_DEBUG_ATTRS = {"print", "callback", "breakpoint"}
_NUMPY_NAMES = {"np", "numpy"}


def _dotted(node: ast.AST) -> str:
    """'jax.debug.print' for nested Attribute/Name chains ('' if not)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def check_scope(fn: ast.AST, path: str) -> List[Tuple[str, int, str]]:
    """All violations inside one guarded function's body."""
    hits = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute):
            dotted = _dotted(node)
            head = dotted.split(".", 1)[0]
            if (node.attr in _DEBUG_ATTRS
                    and dotted.startswith(("jax.debug.", "debug."))):
                hits.append((path, node.lineno,
                             f"{dotted} inside a scan body — a per-pass "
                             f"host callback breaks the sync contract"))
            elif node.attr == "block_until_ready":
                hits.append((path, node.lineno,
                             ".block_until_ready() inside a scan body "
                             "forces a device sync"))
            elif head in _NUMPY_NAMES:
                hits.append((path, node.lineno,
                             f"host numpy ({dotted}) inside a traced "
                             f"scope — use jnp, or hoist to __init__"))
            elif dotted.startswith(("jax.profiler.", "profiler.")):
                hits.append((path, node.lineno,
                             f"{dotted} inside a traced scope fires once "
                             f"at trace time — use jax.named_scope"))
    return hits


def lint_file(path: str, scope_names: Tuple[str, ...]
              ) -> Tuple[List[Tuple[str, int, str]], List[str]]:
    """(violations, scope names found) for one file."""
    with open(path) as fh:
        tree = ast.parse(fh.read(), filename=path)
    hits, found = [], []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in scope_names:
            found.append(node.name)
            hits.extend(check_scope(node, path))
    return hits, found


def main(argv=None) -> int:
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    all_hits, missing = [], []
    for rel, names in sorted(SCOPES.items()):
        path = os.path.join(root, rel)
        hits, found = lint_file(path, names)
        all_hits.extend(hits)
        missing.extend(f"{rel}:{n}" for n in names if n not in found)
    for path, line, msg in all_hits:
        print(f"{path}:{line}: {msg}")
    if missing:
        print("lint_scan_purity: guarded scopes not found (update SCOPES "
              "after refactoring): " + ", ".join(missing))
        return 2
    if all_hits:
        print(f"lint_scan_purity: {len(all_hits)} violation(s)")
        return 1
    print(f"lint_scan_purity: OK ({len(SCOPES)} files, scan bodies "
          f"host-op-free)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
