"""akka_allreduce_tpu — a TPU-native fault/straggler-tolerant allreduce framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of
GuixingLin/akka-allreduce (Scala/Akka): chunked, threshold-gated,
bounded-staleness data-parallel allreduce with partial-completion counts,
plus the surrounding control plane (membership, rank assignment, round
pacing, straggler catch-up).

Two planes, mirroring the reference's actor split but mapped to TPU hardware:

* **Device plane** (`ops/`, `parallel/`): the hot path. Bucketed gradients
  lower to XLA ``reduce_scatter`` + ``all_gather`` (or fused ``psum``, or
  the int8-quantized two-phase collective) over ICI via ``shard_map``;
  lossy threshold semantics become mask/count arithmetic (``psum`` of
  ``(values*valid, valid)``); Pallas kernels cover custom ring schedules
  and quantized transport. On top sits the five-axis parallel stack —
  dp / tp (Megatron) / sp (ring attention) / pp (GPipe) / ep (MoE) — over
  one ``jax.sharding.Mesh``, composed in ``models/train.py``.
* **Host control plane** (`protocol/`, `runtime/`): membership, rank
  assignment, round pacing with a ``max_lag`` staleness window, straggler
  catch-up, and completion tally — the exact observable semantics of the
  reference's AllreduceMaster/AllreduceWorker actors
  (reference: AllreduceMaster.scala:12-90, AllreduceWorker.scala:7-301),
  reproduced message-for-message and pinned by the ported test suite.

See the subpackage docstrings for the public surface of each plane.
"""

# NOTE: this module stays jax-free — the protocol plane (config, messages,
# protocol/) runs in master/worker subprocesses that never touch a device,
# and `import akka_allreduce_tpu` must not tax them with the jax import.

from akka_allreduce_tpu.config import (
    ThresholdConfig,
    DataConfig,
    WorkerConfig,
    AllreduceConfig,
)
from akka_allreduce_tpu.messages import (
    InitWorkers,
    StartAllreduce,
    ScatterBlock,
    ReduceBlock,
    CompleteAllreduce,
    AllReduceInputRequest,
    AllReduceInput,
    AllReduceOutput,
)

__version__ = "0.1.0"

__all__ = [
    "ThresholdConfig",
    "DataConfig",
    "WorkerConfig",
    "AllreduceConfig",
    "InitWorkers",
    "StartAllreduce",
    "ScatterBlock",
    "ReduceBlock",
    "CompleteAllreduce",
    "AllReduceInputRequest",
    "AllReduceInput",
    "AllReduceOutput",
]
