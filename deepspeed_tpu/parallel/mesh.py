"""Device-mesh construction for {data, model, pipe} parallelism.

This is the TPU-native heart of what the reference scattered across NCCL process-group
creation (``deepspeed/runtime/pipe/topology.py:299-364``, ``runtime/engine.py:70-86``): one
``jax.sharding.Mesh`` with named axes, over which every collective in the framework runs
(``psum`` for DP allreduce, ``psum_scatter`` for ZeRO reduce-scatter, ``all_gather`` for
param regather, ``ppermute`` for pipeline p2p).

Axis order is (pipe, data, model): pipe outermost so adjacent stages sit on contiguous
device blocks (DCN-friendly), model innermost so TP collectives ride the fastest ICI links
— the standard TPU mesh recipe.
"""

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"


def build_mesh(data: Optional[int] = None,
               model: int = 1,
               pipe: int = 1,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a (pipe, data, model) mesh over the given devices.

    ``data=None`` means "use all remaining devices" after model/pipe are placed.
    """
    explicit_devices = devices is not None
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if data is None:
        assert n % (model * pipe) == 0, f"{n} devices not divisible by model*pipe={model * pipe}"
        data = n // (model * pipe)
    total = data * model * pipe
    assert total <= n, f"mesh needs {total} devices, only {n} available"
    if not explicit_devices and total != n:
        # Never silently strand devices; a submesh must be an explicit choice.
        raise ValueError(f"mesh shape (pipe={pipe}, data={data}, model={model}) covers {total} of {n} "
                         f"devices; pass devices=... explicitly to build a submesh")
    dev_array = np.asarray(devices[:total]).reshape(pipe, data, model)
    return Mesh(dev_array, axis_names=(PIPE_AXIS, DATA_AXIS, MODEL_AXIS))


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    dev = device or jax.devices()[0]
    return build_mesh(data=1, model=1, pipe=1, devices=[dev])


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Batch-dim sharding over the data axis (leading dim)."""
    return NamedSharding(mesh, P(DATA_AXIS))

def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_from_mpu(mpu) -> Mesh:
    """Build a mesh matching an mpu/grid object's (pipe, data, model) sizes."""
    return build_mesh(data=mpu.get_data_parallel_world_size(),
                      model=mpu.get_slice_parallel_world_size(),
                      pipe=mpu.get_pipe_parallel_world_size())
