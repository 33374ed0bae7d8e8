"""Device meshes + elastic re-mesh planning.

The builders are functions: importing this module never touches jax
device state.

``plan_elastic_mesh`` supports fault tolerance: given the number of
*surviving* devices after failures, pick the largest factorizable mesh that
preserves the model axis (TP groups must stay intact — a TP group losing one
chip loses its shard of every weight), shrinking the data axis instead.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """Mesh over ``shape``/``axes``; with an explicit (possibly
    non-contiguous) device list it must supply at least prod(shape)
    devices — short lists used to reshape-crash with an opaque error."""
    import numpy as np
    need = int(np.prod(shape))
    if devices is None:
        if need > len(jax.devices()):
            raise ValueError(
                f"mesh shape {tuple(shape)} needs {need} devices; only "
                f"{len(jax.devices())} available")
        return jax.make_mesh(tuple(shape), tuple(axes))
    if len(devices) < need:
        raise ValueError(
            f"mesh shape {tuple(shape)} needs {need} devices; "
            f"got a list of {len(devices)}")
    dev = np.asarray(devices)[:need].reshape(tuple(shape))
    from jax.sharding import Mesh
    return Mesh(dev, tuple(axes))


def make_data_mesh(num_devices: Optional[int] = None,
                   devices: Optional[Sequence] = None):
    """1-D data-only mesh (axis ``"data"``) over any device count.

    It makes no tensor-parallel or pod-topology assumption: it works on
    whatever ``jax.devices()`` provides — including CPU hosts forced to N
    devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` —
    and accepts an explicit (non-contiguous, e.g. post-failure surviving)
    device list. ``num_devices=None`` uses every available device.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices) if num_devices is None else int(num_devices)
    if n <= 0:
        raise ValueError("data mesh needs at least one device")
    return make_mesh((n,), ("data",), devices=list(devices)[:n])


@dataclasses.dataclass(frozen=True)
class ElasticPlan:
    shape: Tuple[int, ...]
    axes: Tuple[str, ...]
    used_devices: int
    dropped_devices: int

    @property
    def dp_degree(self) -> int:
        mp = self.shape[-1] if self.axes[-1] == "model" else 1
        return self.used_devices // mp


def plan_elastic_mesh(surviving: int, model_parallel: int = 16,
                      pods: int = 1, data_only: bool = False) -> ElasticPlan:
    """Largest usable mesh after failures.

    TP degree is preserved (checkpoint weight shards stay valid); the data
    axis shrinks to floor(surviving / model_parallel). Remaining chips idle
    until the failed hosts are replaced (standard elastic-DP policy).

    ``data_only=True`` plans a 1-D ``("data",)`` mesh instead (the
    partitioned-graph executors in ``dist/``, which have no TP axis at
    all): every survivor is usable and logical graph shards refold onto
    the remaining devices (``shards_per_device = P // dp``). The default
    keeps the trailing ``"model"`` axis even at ``model_parallel=1``, so
    a tensor-parallel sharding can resolve specs against it by name.
    """
    if surviving < model_parallel:
        raise ValueError(
            f"fewer surviving devices ({surviving}) than one TP group "
            f"({model_parallel}); cannot form a mesh")
    dp = surviving // model_parallel
    used = dp * model_parallel
    if data_only:
        if model_parallel != 1 or pods > 1:
            raise ValueError("data_only plans have no model/pod axes")
        return ElasticPlan(shape=(dp,), axes=("data",), used_devices=dp,
                           dropped_devices=surviving - dp)
    if pods > 1 and dp % pods == 0:
        shape = (pods, dp // pods, model_parallel)
        axes = ("pod", "data", "model")
    else:
        shape = (dp, model_parallel)
        axes = ("data", "model")
    return ElasticPlan(shape=shape, axes=axes, used_devices=used,
                       dropped_devices=surviving - used)
