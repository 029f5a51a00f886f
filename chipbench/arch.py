"""The architecture of a configuration, found by its ``model_type``.

Each architecture is two modules, found by name and listed nowhere:

- ``reference/<model_type>.py``: its leaves (``shapes``, ``init``), the
  plain forward (``Ref``) and its operation counts (``matmul_params``,
  ``attention_flops``).  It never imports the program.
- ``programs/<model_type>.py``: the program's config of the file
  (``program_config``), the benchmark's leaves in the program's tree
  (``program_tree``) and back (``leaves``).

Code that does not depend on the architecture reaches it only through
these two functions.
"""
from __future__ import annotations

import importlib


def reference(c: dict):
    """The reference module of configuration ``c``."""
    return _find("reference", c["model_type"])


def program(c: dict):
    """The program module of configuration ``c``."""
    return _find("programs", c["model_type"])


def _find(package: str, model_type: str):
    name = f"{package}.{model_type}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name not in (name, package):
            raise            # the module exists; something it imports does not
        raise LookupError(
            f"no architecture for model_type {model_type!r}: looked for "
            f"chipbench/reference/{model_type}.py and "
            f"chipbench/programs/{model_type}.py") from None
