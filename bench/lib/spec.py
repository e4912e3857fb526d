"""Model configurations: the JSON file under ``bench/configs/`` and the
architecture it names, the module ``bench/arch/<architecture>.py``.

An architecture module holds everything the benchmark knows of a model's
shape and mathematics, and nothing else:

* ``Dims`` and ``dims(spec)``: the plain dimensions read from the file.
  Every ``Dims`` has ``n_layers``, ``vocab`` and ``param_dtype``, which
  the drivers and the weight generator read;
* ``program_config(dm, name, *, remat, attention_impl)``: the program's
  ``ModelConfig`` for them;
* the weight table: ``shapes(dm)`` (name -> (shape, init scale)),
  ``PATHS`` (name -> path in the program's parameter tree), ``LAYERED``
  (the leaves stacked over layers) and ``F32`` (the leaves the program
  keeps in float32);
* the plain float32 reference, built on ``reference``'s primitives:
  ``embed(w, tokens, dm)``, ``hidden(w, tokens, dm, mode, *, lo, hi, x,
  pos)``, ``head(w, x, dm, mode)`` and ``loss(w, tokens, dm, mode,
  fault)``;
* the counts the FLOP formulas need: ``active_params(dm, name, size)``,
  ``matmul_params_per_token(dm)`` and ``attention_flops(dm, context)``.

A ``Dims`` belongs to the architecture module that defines its class
(``arch_of``), so code handed one finds the rest by it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _arch_path(name: str, base: str) -> str:
    return os.path.join(base, "arch", f"{name}.py")


def load(name: str, base: str = BENCH) -> dict:
    path = os.path.join(base, "configs", f"{name}.json")
    with open(path) as f:
        spec = json.load(f)
    if spec.get("name") != name:
        raise ValueError(f"{path} names itself {spec.get('name')!r}")
    arch = spec.get("architecture")
    if not isinstance(arch, str) or not os.path.isfile(_arch_path(arch, base)):
        raise ValueError(f"{path} names the architecture {arch!r}, which "
                         f"is no file under {os.path.join(base, 'arch')}")
    return spec


def architecture(name: str, base: str = BENCH):
    """The module ``bench/arch/<name>.py`` under ``base``, loaded once."""
    path = _arch_path(name, base)
    modname = "bench_arch_" + name.replace("-", "_").replace(".", "_")
    mod = sys.modules.get(modname)
    if mod is None or mod.__file__ != path:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        # registered before it runs, so that its classes name a module
        # that ``arch_of`` finds
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
    return mod


def dims(spec: dict, base: str = BENCH):
    """A configuration's dimensions, as its architecture reads them."""
    return architecture(spec["architecture"], base).dims(spec)


def arch_of(dm):
    """The architecture module that defines ``dm``'s class."""
    return sys.modules[type(dm).__module__]


def shrink(dm, **kw):
    """A smaller copy for the CPU tests (never used on the chip)."""
    return dataclasses.replace(dm, **kw)
