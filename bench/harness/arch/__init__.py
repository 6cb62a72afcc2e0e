"""What the benchmark knows of each model architecture, one module per
architecture: ``bench/harness/arch/<name>.py``, loaded by path, so that
adding an architecture adds a file and edits none.

A tier entry of a configuration file names its module with
``"arch": "<name>"``; without the key the module is ``dense_gqa``.  An
unknown name is an error.  A module provides:

    HF_KEYS          published ``config.json`` key -> how the tier's
                     ``ModelConfig`` states it (``cells.tier_config``
                     checks every key the file gives that is named here)
    layout(cfg)      ``{path: (shape, init)}`` of the tier's params, with
                     init ``"ones"``, ``"normal:<std>"`` or ``"fan_in"``
                     (``weights.make``)
    logits_at(params, cfg, tokens, rows, precision)
                     the float32 forward pass: logits ``[len(rows), V]``
                     at positions ``rows`` of ``tokens``; every matmul
                     weight goes through ``reference._weight(w, precision,
                     in_axis)``, which gives the fp8 control
    matmul_weights(cfg)
                     weights one token multiplies through in its layers
                     (the output projection is counted apart)
    pair_flops(cfg)  attention FLOPs of one query-key pair, all layers
    kv_bytes_per_position(cfg, dtype_bytes)
                     cache bytes of one position, all layers
    attention_bytes(cfg, work, dtype_bytes)
                     bytes the attention kernel moves in one launch
    weight_bytes_read(cfg, work, dtype_bytes)
                     weight bytes one launch reads

``work`` is a ragged ``launch`` span's args, handed in whole
(``roofline.WORK`` and whatever else the program puts there).
"""
from __future__ import annotations

import hashlib
import importlib.util
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
DEFAULT = "dense_gqa"
_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")

# copies of one file (a test's root holds copies) share one module, and
# so one set of jitted programs
_LOADED: dict = {}
# served name -> module, written by ``cells.tier_config`` beside the
# program's own registry of configs by name
_BOUND: dict = {}


def load(name: str, root: Path = ROOT):
    """The module ``bench/harness/arch/<name>.py`` under ``root``."""
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"architecture name {name!r} is not a module name")
    d = root / "bench" / "harness" / "arch"
    path = d / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in d.glob("*.py") if p.stem != "__init__")
        raise KeyError(f"no architecture {name!r} in {d}; known: {known}")
    code = path.read_bytes()
    key = hashlib.sha256(code).hexdigest()
    if key not in _LOADED:
        spec = importlib.util.spec_from_file_location(f"bench_arch_{name}",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[key] = mod
    return _LOADED[key]


def bind(cfg, module) -> None:
    _BOUND[cfg.name] = module


def of(cfg):
    """The module of a tier's ``ModelConfig``: the one its tier entry
    named, for a config ``cells.tier_config`` made; otherwise, as for a
    tier entry without ``"arch"``, ``dense_gqa``."""
    return _BOUND.get(cfg.name) or load(DEFAULT)
