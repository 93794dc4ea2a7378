"""Production mesh definitions.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count locks on first backend init — the dry-run
sets XLA_FLAGS before any jax import).

Mesh shapes (TPU v5e pods):
  single-pod: (16, 16)    axes (data, model)   = 256 chips
  multi-pod:  (2, 16, 16) axes (pod, data, model) = 512 chips; the 'pod'
              axis is data-parallel over DCN (gradient all-reduce crosses
              pods once per step; everything else stays inside a pod).
"""
from __future__ import annotations

from repro.parallel.mesh import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def smoke_mesh():
    """Whatever devices exist, as a 1D 'data' mesh (tests / CPU runs)."""
    import jax

    n = len(jax.devices())
    return make_mesh((n,), ("data",))
