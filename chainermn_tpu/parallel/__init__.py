"""Parallelism strategies beyond data parallel: sequence/context and tensor.

The reference predates long-context techniques entirely (SURVEY.md §5
"long-context: absent — 2017-era codebase"), but its L1/L3 primitives
(`alltoall`, ring `send/recv`) are exactly the substrate they need; per the
rebuild brief these are FIRST-CLASS here, built the TPU way: ring attention
as a ``ppermute`` ring over ICI neighbors (the physical torus topology) with
online-softmax accumulation, and Ulysses-style head↔sequence swaps as one
XLA ``all_to_all``.
"""

from .ring_attention import (  # noqa: F401
    make_ring_attention,
    ring_attention,
)
from .ulysses import (  # noqa: F401
    make_ulysses_attention,
    ulysses_attention,
)
from .moe import (  # noqa: F401
    init_moe_mlp_params,
    make_moe_mlp,
    moe_mlp,
    moe_mlp_specs,
)
from .pipeline import (  # noqa: F401
    make_pipeline,
    make_pipeline_1f1b,
    pipeline_1f1b_grads,
    pipeline_apply,
    stack_stage_params,
)
from .collective_matmul import (  # noqa: F401
    all_gather_matmul,
    make_all_gather_matmul,
    make_matmul_reduce_scatter,
    matmul_reduce_scatter,
)
from .hybrid import (  # noqa: F401
    init_fsdp_params,
    init_fsdp_state,
    init_zero1_state,
    make_fsdp_train_step,
    make_hybrid_shard_map_step,
    make_hybrid_train_step,
    make_zero1_train_step,
    shard_pytree,
    state_specs_like,
    zero1_specs,
)
from .decode import (  # noqa: F401
    lm_generate,
    make_lm_generator,
)
from .transformer import (  # noqa: F401
    apply_rope,
    init_tp_transformer_lm,
    sp_block,
    sp_transformer_lm_loss,
    tp_attention,
    tp_attention_sp,
    tp_block,
    tp_block_sp,
    tp_transformer_lm_loss,
    transformer_lm_specs,
    vocab_parallel_logits_loss,
)
from .reshard import (  # noqa: F401
    make_reshard,
    reshard,
    reshard_cost,
    reshard_host,
    reshard_tree_cost,
)
from .tensor_parallel import (  # noqa: F401
    column_parallel_dense,
    init_tp_mlp_params,
    make_tensor_parallel_mlp,
    row_parallel_dense,
    gather_seq_matmul,
    matmul_scatter_seq,
    tp_mlp,
    tp_mlp_sp,
    tp_mlp_specs,
    vocab_parallel_embedding,
)

__all__ = [
    "reshard",
    "make_reshard",
    "reshard_host",
    "reshard_cost",
    "reshard_tree_cost",
    "ring_attention",
    "make_ring_attention",
    "ulysses_attention",
    "make_ulysses_attention",
    "pipeline_apply",
    "stack_stage_params",
    "make_pipeline",
    "make_pipeline_1f1b",
    "pipeline_1f1b_grads",
    "moe_mlp",
    "init_moe_mlp_params",
    "moe_mlp_specs",
    "make_moe_mlp",
    "column_parallel_dense",
    "row_parallel_dense",
    "vocab_parallel_embedding",
    "tp_mlp",
    "tp_mlp_sp",
    "gather_seq_matmul",
    "matmul_scatter_seq",
    "init_tp_mlp_params",
    "tp_mlp_specs",
    "make_tensor_parallel_mlp",
    "all_gather_matmul",
    "matmul_reduce_scatter",
    "make_all_gather_matmul",
    "make_matmul_reduce_scatter",
    "make_hybrid_train_step",
    "make_hybrid_shard_map_step",
    "make_zero1_train_step",
    "make_fsdp_train_step",
    "init_fsdp_params",
    "init_fsdp_state",
    "init_zero1_state",
    "zero1_specs",
    "shard_pytree",
    "state_specs_like",
    "apply_rope",
    "lm_generate",
    "make_lm_generator",
    "init_tp_transformer_lm",
    "sp_block",
    "sp_transformer_lm_loss",
    "tp_attention",
    "tp_attention_sp",
    "tp_block",
    "tp_block_sp",
    "tp_transformer_lm_loss",
    "transformer_lm_specs",
    "vocab_parallel_logits_loss",
]
