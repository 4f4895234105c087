"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; callers (dryrun.py)
set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_for(n_devices: Optional[int] = None, model_parallel: int = 1):
    """Elastic mesh: largest (data, model) grid for the devices we have.

    Used by the trainer on restart after losing nodes: data parallelism
    shrinks to whatever is available while model parallelism is preserved.
    """
    n = n_devices if n_devices is not None else len(jax.devices())
    assert n % model_parallel == 0, (n, model_parallel)
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"))


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    # Auto axes: the model code places activations with
    # with_sharding_constraint, which an Explicit axis (jax.make_mesh's
    # default since JAX 0.8) refuses
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_info(mesh) -> dict:
    return {"axis_names": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
            "n_devices": int(mesh.size)}
