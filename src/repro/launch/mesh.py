"""Production mesh builders.

Importing this module never touches jax device state; meshes are built by
functions only (the dry-run sets XLA_FLAGS before any jax import)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np


def _auto(n_axes: int):
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2 pods = 512
    chips (pod, data, model); the pod axis is a second (DCN) data axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model: Optional[int] = None):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = jax.device_count()
    model = model or 1
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def make_tp_mesh(tp: int):
    """(1, tp) serving mesh over the FIRST ``tp`` local devices.

    Unlike ``make_mesh``/``make_host_mesh`` this does not require the mesh
    to cover every device — a tp=2 engine on a 4-device host uses devices
    0..1 and leaves the rest free (e.g. for a second engine)."""
    n = jax.device_count()
    if tp < 1 or tp > n:
        raise ValueError(f"tp={tp} needs 1..{n} local devices")
    devs = np.asarray(jax.devices()[:tp], dtype=object).reshape(1, tp)
    return jax.sharding.Mesh(devs, ("data", "model"), axis_types=_auto(2))
