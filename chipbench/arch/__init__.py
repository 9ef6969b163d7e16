"""Architectures, one module each, found by a configuration file's
``"architecture"``: ``<name>.py`` in one of ``DIRS``, loaded the way the
harness loads a per-layer metric's reader.

Each module holds everything the benchmark knows of its architecture:

- ``check(cfg, arch)``: the configuration file's sizes against the
  program's ``ArchConfig``; raises where they differ (:func:`require`);
- ``specs(cfg)``: the stored parameters, ``{name: ... {leaf: (shape, init,
  argument)}}`` in the program's layout; the shared inits ``normal`` and
  ``ones`` are ``reference/params.py``'s, any other is the module's own, in
  ``INITS``: ``{kind: fn(key, shape, argument, cfg)}``;
- ``loss(params, tokens, labels, cfg, precision)``: the plain float32
  reference's mean next-token cross-entropy;
- ``forward_flops(cfg, seq)``: the forward FLOPs of one sequence, as
  ``flops.py`` counts them;
- ``SCOPES``: the named scopes the program's layers of this architecture
  run under, each mapped to the scope that holds it, or ``None`` for an
  outer one (a layer kind).

A new architecture joins the benchmark with a new module here and nothing
else changed.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

# where modules are looked for, in order
DIRS: List[Path] = [Path(__file__).resolve().parent]

_loaded: Dict[Path, ModuleType] = {}


def _path(name: str) -> Path:
    for d in DIRS:
        p = Path(d) / f"{name}.py"
        if p.is_file():
            return p
    raise ValueError(f"no architecture module {name!r} in {[str(d) for d in DIRS]}")


def load(name: str) -> ModuleType:
    """The module of architecture ``name``, loaded once per file."""
    path = _path(name)
    if path not in _loaded:
        spec = importlib.util.spec_from_file_location(f"chipbench_arch_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _loaded[path] = module
    return _loaded[path]


def of(cfg: dict) -> ModuleType:
    """The module of a configuration file's architecture."""
    return load(cfg["architecture"])


def names() -> List[str]:
    """Every architecture in ``DIRS``, the first directory's first."""
    out = []
    for d in DIRS:
        for p in sorted(Path(d).glob("*.py")):
            if not p.name.startswith("_") and p.stem not in out:
                out.append(p.stem)
    return out


def scopes() -> Dict[str, Optional[str]]:
    """The union of every architecture's ``SCOPES``: each scope and the
    scope that holds it (``None`` for a layer kind)."""
    out: Dict[str, Optional[str]] = {}
    for name in names():
        for scope, outer in load(name).SCOPES.items():
            if out.get(scope, outer) != outer:
                raise ValueError(f"{name}: scope {scope!r} is held by {outer!r} here, by {out[scope]!r} elsewhere")
            out[scope] = outer
    return out


def require(cfg: dict, pairs: dict) -> None:
    """Raise unless each ``{key: (program, file)}`` pair agrees."""
    bad = {k: v for k, v in pairs.items() if v[0] != v[1]}
    if bad:
        raise ValueError(f"{cfg['name']}: program config differs from the file (program, file): {bad}")
