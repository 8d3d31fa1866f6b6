"""Model zoo: workloads that exercise the framework end-to-end.

The reference ships no model code — its benchmark workload is a synthetic
float vector (reference: AllreduceWorker.scala:325-326). A complete framework
needs real gradient producers: `mlp.py` is the minimal DP workload
(the synthetic-vector benchmark's moral successor), and `transformer.py` is
the flagship — a causal transformer LM whose training step composes every
parallelism axis: dp gradient sync through the framework's bucketed
collectives, tp-sharded projections, and ring-attention sequence parallelism
(models/train.py).
"""

from akka_allreduce_tpu.models.mlp import init_mlp, mlp_apply
from akka_allreduce_tpu.models.speculate import (
    extend,
    speculative_generate,
    speculative_sample,
)
from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
    transformer_apply,
)

__all__ = [
    "init_mlp",
    "mlp_apply",
    "TransformerConfig",
    "init_transformer",
    "transformer_apply",
    "extend",
    "speculative_generate",
    "speculative_sample",
]
