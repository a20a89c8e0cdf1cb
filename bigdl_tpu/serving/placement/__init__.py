"""bigdl_tpu.serving.placement — device topology, mesh slicing, and
replica placement for multi-chip serving.

The reference framework's core trick was mapping each physical compute
unit to a Spark task slot so one engine drove the whole cluster
(Engine.init, arXiv 1804.05839).  The TPU-native equivalent is
placement as a first-class ``NamedSharding`` parameter (GSPMD named
meshes, arXiv 2004.13336): carve the backend's devices into named
submeshes — N data-parallel replica *slots* x M-way tensor-parallel
within a slot — hand each :class:`~bigdl_tpu.serving.engine.ServingEngine`
replica its slot's :class:`MeshSlice`, and XLA inserts the collectives.

Three layers, smallest first:

- :class:`DeviceTopology` — enumerate/describe the backend's devices;
  degrades gracefully to one device (a laptop CPU serves exactly as
  before, through a 1-slot x TP1 slice).
- :class:`MeshSlicer` — carve the device set into :class:`MeshSlice`
  submeshes, reusing :mod:`bigdl_tpu.parallel.mesh` axis names (a slot's
  mesh is a 1-D ``model`` axis — tensor parallelism *within* the slot;
  data parallelism *across* slots is the ReplicaSet's dispatch).
- :class:`PlacementPolicy` — pack replicas onto slots (acquire/release
  with headroom accounting), publish ``serving/placement/*`` gauges.

Agreement is held on CPU with the 8-virtual-device fake mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``):
``tests/test_placement.py`` compares 2-slot x TP2 and 1-slot x TP4
against the unsharded oracle.  No chip has run a sharded slot.
"""
from bigdl_tpu.serving.placement.topology import DeviceTopology
from bigdl_tpu.serving.placement.slicer import (MeshSlice, MeshSlicer,
                                                PlacementError)
from bigdl_tpu.serving.placement.policy import PlacementPolicy
from bigdl_tpu.serving.placement.rules import (serving_tp_rules,
                                               shard_params_chunked)

__all__ = [
    "DeviceTopology", "MeshSlice", "MeshSlicer", "PlacementError",
    "PlacementPolicy", "serving_tp_rules", "shard_params_chunked",
]
