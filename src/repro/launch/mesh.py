"""Production mesh construction (functions only — importing this module
never touches jax device state; jax locks the device count on first init).

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis carries
data parallelism across the inter-pod DCI links (collectives on it are the
most expensive — see EXPERIMENTS §Roofline).
"""

from __future__ import annotations

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
