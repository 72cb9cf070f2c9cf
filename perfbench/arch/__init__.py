"""Architecture modules: everything the harness does that depends on a
model's architecture, one module per ``model_type`` that a configuration
file names (``arch/<model_type>.py``).  ``load(cfg)`` is the harness's
only way to reach one.

A module provides, for a configuration file ``cfg``:

* ``shapes(cfg)``: the float32 parameter tree as leaf shapes, in the
  layout the program's ``prepare_params`` takes, and ``leaf(key, names,
  shape)``, each leaf's initial values (``weights.make`` calls both);
* ``program_config(cfg)``: the program's ``ArchConfig`` (``serving``);
* ``prepared_spec(cfg, qcfg)``: shapes of the served tree, for the AOT
  compile (``compile_v5e``);
* ``Model(cfg, weights, bits=8)``: the plain reference forward, a
  ``reference.Decoder`` (``check``);
* ``projections(cfg)``: one layer's serving projections in the order the
  decode step calls them, each ``(name, members, K, N)`` with ``members``
  the ``(group, leaf)`` of the float tree merged into it, in column order
  (``qdot_check``, ``metrics/step_qdot_roofline``);
* ``layer_macs(cfg)``: multiply-accumulates of one layer's projections
  for one token (``roofline``);
* ``head_bytes(cfg)``: bytes of one element of the output head's table
  as served (``metrics/unembed_roofline``).

A new ``model_type`` joins by adding its module here and naming it in its
configuration file; no other file of the harness changes.
"""
from __future__ import annotations

import importlib
import pkgutil
import re


def load(cfg: dict):
    """The architecture module of a configuration file; LookupError, with
    the file looked for and the modules present, where there is none."""
    name = cfg.get("model_type")
    if isinstance(name, str) and re.fullmatch(r"[A-Za-z0-9_]+", name):
        try:
            return importlib.import_module(f"{__name__}.{name}")
        except ModuleNotFoundError as e:
            if e.name != f"{__name__}.{name}":
                raise
    present = sorted(f"{m.name}.py" for m in pkgutil.iter_modules(__path__))
    raise LookupError(
        f"configuration {cfg.get('name')!r} has model_type {name!r}: no "
        f"architecture module {name}.py in {', '.join(__path__)}; "
        f"present: {present}")
