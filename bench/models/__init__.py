"""Plain reference models, one module per family.

A configuration file names its family in the ``reference`` key, and every
caller reaches the model through ``of(cfg)``, which imports
``bench.models.<reference>``.  A module gives exactly what the harness
needs, and imports nothing of the program:

* ``check_config(cfg)``: raise ``ValueError`` for a key of the file (other
  than those in ``META``) that the model does not implement;
* ``param_shapes(cfg)``: the weight layout it reads, a tree of shape tuples
  that the program's layout must equal; ``bench/weights.py`` makes the
  weights from it;
* ``loss(cfg, params, tokens)``: the mean next-token cross-entropy of
  ``(B, S)`` tokens, ``params`` in float32;
* ``flops_per_token(cfg, seq_len)``: forward plus backward FLOPs per
  trained token, counted by the rules in ``bench/counts.py``.

A new family is a new module here; no other file changes for it.
"""

from __future__ import annotations

import importlib
import pkgutil

# keys of a configuration file that describe it rather than the model
META = ("name", "program_config", "source", "reduced", "assumed",
        "deployment", "reference")
INTERFACE = ("check_config", "param_shapes", "loss", "flops_per_token")


def known() -> list[str]:
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def of(cfg: dict):
    """The reference model module that ``cfg["reference"]`` names."""
    name = cfg.get("reference")
    if name not in known():
        raise ValueError(f"a configuration names its reference model in "
                         f"'reference', one of {known()}; got {name!r}")
    module = importlib.import_module(f"{__name__}.{name}")
    missing = [f for f in INTERFACE if not callable(getattr(module, f, None))]
    if missing:
        raise ValueError(f"bench.models.{name} lacks {missing}")
    return module
