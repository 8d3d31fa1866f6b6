"""Parallelism layer: device meshes and the strategies built on them.

The reference implements exactly one parallelism primitive — data-parallel
gradient allreduce over an actor cluster (SURVEY.md §2). Here that maps to
`dp.py` over a ``jax.sharding.Mesh`` axis, and the same mesh machinery
carries the strategies a TPU-scale framework needs alongside it: tensor
parallelism (`tp.py`), sequence/context parallelism via ring attention
(`ring_attention.py`), and their composition in the training step
(models/train.py).
"""

from akka_allreduce_tpu.parallel.mesh import (
    MeshSpec,
    make_device_mesh,
    local_axis_size,
)

__all__ = ["MeshSpec", "make_device_mesh", "local_axis_size"]
