"""chainermn_tpu — a TPU-native distributed-training framework.

Capability parity with ChainerMN (reference: ``okuta/chainermn``; see
SURVEY.md) built idiomatically on JAX/XLA: communicators lower to XLA
collectives over ICI/DCN instead of NCCL/MPI, gradient averaging fuses into
one jitted SPMD step instead of eager bucketed allreduce, and model
parallelism is sharding + ppermute instead of MPI send/recv.  No CUDA, NCCL
or mpi4py anywhere in the import graph.
"""

from . import extensions, functions, global_except_hook, iterators, links, observability, ops, parallel, runtime, serving, training  # noqa: F401,E402
from .runtime import (FileDataset, PrefetchIterator,  # noqa: F401
                      write_file_dataset)
from .parallel import (  # noqa: F401
    column_parallel_dense,
    make_moe_mlp,
    make_pipeline,
    make_ring_attention,
    make_tensor_parallel_mlp,
    make_ulysses_attention,
    moe_mlp,
    pipeline_apply,
    ring_attention,
    row_parallel_dense,
    stack_stage_params,
    tp_mlp,
    ulysses_attention,
    vocab_parallel_embedding,
)
from .extensions import (  # noqa: F401
    AllreducePersistent,
    ObservationAggregator,
    create_multi_node_checkpointer,
    multi_node_snapshot,
)
from .iterators import (  # noqa: F401
    SerialIterator,
    create_multi_node_iterator,
    create_synchronized_iterator,
)
from .datasets import (  # noqa: F401
    ScatteredDataset,
    SubDataset,
    create_empty_dataset,
    scatter_dataset,
    scatter_index,
)
from .evaluators import (  # noqa: F401
    accuracy_evaluator,
    bleu_evaluator,
    corpus_bleu,
    create_multi_node_evaluator,
)
from .optimizers import (  # noqa: F401
    ErrorFeedbackState,
    compressed_mean,
    create_multi_node_optimizer,
    error_feedback_layout,
    fold_error_feedback,
    gradient_average,
    hierarchical_gradient_average,
    opt_state_partition_specs,
)
from .train import (  # noqa: F401
    make_flax_train_step,
    make_train_step,
    replicate,
    shard_batch,
    shard_batch_local,
)
from .communicators import (  # noqa: F401
    CommunicatorBase,
    NaiveCommunicator,
    XlaCommunicator,
    create_communicator,
)
from .topology import (  # noqa: F401
    DEFAULT_AXIS_NAME,
    Topology,
    init_distributed,
    make_mesh,
    make_multislice_mesh,
    make_nd_mesh,
)

__version__ = "0.1.0"
