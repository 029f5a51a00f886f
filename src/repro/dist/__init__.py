"""Distribution layer: activation constraints + parameter partition rules.

``constraints`` supplies mesh-aware ``with_sharding_constraint`` tags
that are exact no-ops when no mesh is active, so every model file can be
written once and run identically on a laptop (1 device, no mesh) and on
a pod mesh.  ``sharding`` holds the path-based parameter partition rules
and the pytree-level spec builders the pjit call sites consume.
"""
from repro.dist import constraints, sharding
from repro.dist.constraints import constrain, constrain_qkv

__all__ = ["constraints", "sharding", "constrain", "constrain_qkv"]
