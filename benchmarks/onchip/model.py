"""A configuration file and the architecture module it names.

``configs/<name>.json`` holds the published config.json keys as they are
run, the ``program`` block (what the keys do not say: family, norm, MLP,
dtype), the ``serving`` block (slots, pages, pool), the ``check`` block
(the comparison's limits) and ``"arch"``: the name of the file
``archs/<arch>.py`` that knows the architecture.  There is no default:
a configuration without ``"arch"``, or one naming no such file, is
refused.  Each architecture module gives:

* ``load_spec(config: dict) -> spec``: the one view of the configuration
  that the rest are built from.  The harness itself reads only the
  spec's ``name``, ``vocab``, ``layers``, ``max_slots``, ``block_size``,
  ``max_seq_len`` and ``num_blocks``;
* ``program_config(spec)``: the system under test's ``ModelConfig``;
* ``make_params(spec, seed)``: the weights tree in the program's layout,
  made on the device from ``seed`` (``weights.on_device``);
* ``Reference(spec, params, control=False)``, whose ``gaps(prompt,
  served)`` gives ``(gaps, control_gaps)`` (``reference.py``);
* ``decode_step(spec, ctx)`` and ``prefill(spec, n)``: (FLOPs, bytes),
  the least work of a step (the rule is in ``work.py``).

A module may give more for the per-layer readers of its own cells (the
dense module's ``paged_attention``).
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, Tuple

ARCHS = Path(__file__).resolve().parent / "archs"
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
_LOADED: Dict[Path, ModuleType] = {}


def _found(archs: Path) -> list:
    return sorted(p.stem for p in archs.glob("*.py"))


def load_arch(name: str, archs: Path = ARCHS) -> ModuleType:
    """The module ``archs/<name>.py`` (loaded once per file), or
    SystemExit naming the modules there."""
    path = archs / f"{name}.py"
    if not (_NAME.fullmatch(name) and path.is_file()):
        raise SystemExit(f"no architecture module {name!r} ({path}); "
                         f"modules in {archs}: {_found(archs)}")
    path = path.resolve()
    if path not in _LOADED:
        # registered before it runs: a dataclass looks up its module
        mod_name = f"onchip_arch_{len(_LOADED)}_{name}"
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def load_config(path: Path, archs: Path = ARCHS
                ) -> Tuple[dict, ModuleType]:
    """The configuration file and the architecture module it names, or
    SystemExit where it names none or one that is not in ``archs``."""
    with open(path) as f:
        config = json.load(f)
    if not isinstance(config.get("arch"), str):
        raise SystemExit(f"{path} names no architecture (its \"arch\" key); "
                         f"modules in {archs}: {_found(archs)}")
    return config, load_arch(config["arch"], archs)


def load_spec(path: Path):
    """The spec of a configuration file, made by its module."""
    config, arch = load_config(path)
    return arch.load_spec(config)


def program_config(spec):
    """The ``ModelConfig`` of a spec, from the module that made it."""
    return sys.modules[type(spec).__module__].program_config(spec)
