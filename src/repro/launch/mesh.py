"""The serving device mesh.

Built by a function (never a module-level constant) so importing this
module never touches jax device state: on the CPU the device count is set
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
initializes.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_serve_device_mesh"]


def make_serve_device_mesh(data: int = 1, model: int = 1) -> Mesh:
    """("data", "model") mesh over the first ``data * model`` local devices.

    The device grid under :class:`repro.serve.mesh.ServeMesh`: the "data"
    axis shards request batches, the "model" axis (optionally) shards the
    clause pool.  Raises with a remediation hint when the process has too
    few devices — on CPU the count is set with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    initializes.
    """
    n = data * model
    have = len(jax.devices())
    if have < n:
        raise ValueError(
            f"mesh ({data} data x {model} model) needs {n} devices but the "
            f"process has {have}; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            f"jax initializes"
        )
    devs = np.array(jax.devices()[:n]).reshape(data, model)
    return Mesh(devs, ("data", "model"))
