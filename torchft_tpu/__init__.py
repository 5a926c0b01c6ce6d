"""torchft_tpu: per-step fault tolerance for TPU (JAX/XLA) training.

A TPU-native framework with the capabilities of torchft (reference
torchft/__init__.py:13-20): replicated training keeps making progress when
replica groups (TPU slices) die or rejoin — membership is recomputed at
training-step granularity, recovering replicas fetch live weights from a
healthy peer, and every step ends in a distributed commit vote.
"""

from torchft_tpu import startup  # first: its import is the record's stamp

from torchft_tpu._native import (
    LeaseClient,
    Lighthouse,
    ManagerClient,
    QuorumResult,
    RegionLighthouse,
    Store,
    StoreClient,
    WireCorruption,
)
from torchft_tpu.chaos import ChaosInjector, FaultEvent, FaultPlan
from torchft_tpu.checkpointing import CheckpointServer, CheckpointTransport
from torchft_tpu.collectives import (
    Collectives,
    DummyCollectives,
    HostCollectives,
    ReduceOp,
    TreeShard,
    Work,
)
from torchft_tpu.data import DistributedSampler, StatefulDataLoader
from torchft_tpu.durable import (
    CheckpointStore,
    DurableCheckpointer,
    LocalDirStore,
    ManifestLog,
)
from torchft_tpu.isolated_xla import (
    ChildStalledError,
    IsolatedXLACollectives,
)
from torchft_tpu.ddp import (
    AdaptiveDDP,
    DistributedDataParallel,
    PipelinedDDP,
    ShardedDDP,
)
from torchft_tpu.local_sgd import AsyncDiLoCo, DiLoCo, LocalSGD
from torchft_tpu.manager import Manager, WorldSizeMode
from torchft_tpu.optim import OptimizerWrapper as Optimizer
from torchft_tpu.optim import OptimizerWrapper, ShardedOptimizerWrapper
from torchft_tpu.policy import CostKnobs, PolicyEngine, StrategySpec
from torchft_tpu.serving import (
    StaleWeightsError,
    WeightPublisher,
    WeightRelay,
    WeightSubscriber,
    publish_on_commit,
)
from torchft_tpu.pipeline import pipeline_blocks, stack_blocks
from torchft_tpu.profiling import Profiler
from torchft_tpu.train_state import FTTrainState
from torchft_tpu.xla_collectives import XLACollectives

startup.listen()  # a trainer that holds jax by now; nobody else (startup.py)

__all__ = [
    "AdaptiveDDP",
    "ChaosInjector",
    "ChildStalledError",
    "FaultEvent",
    "FaultPlan",
    "WireCorruption",
    "AsyncDiLoCo",
    "CheckpointServer",
    "CheckpointTransport",
    "Collectives",
    "DiLoCo",
    "DistributedDataParallel",
    "DistributedSampler",
    "DummyCollectives",
    "DurableCheckpointer",
    "CheckpointStore",
    "LocalDirStore",
    "ManifestLog",
    "LocalSGD",
    "HostCollectives",
    "IsolatedXLACollectives",
    "LeaseClient",
    "Lighthouse",
    "RegionLighthouse",
    "FTTrainState",
    "Manager",
    "ManagerClient",
    "Optimizer",
    "OptimizerWrapper",
    "PipelinedDDP",
    "ShardedDDP",
    "ShardedOptimizerWrapper",
    "PolicyEngine",
    "CostKnobs",
    "StrategySpec",
    "Profiler",
    "QuorumResult",
    "pipeline_blocks",
    "stack_blocks",
    "ReduceOp",
    "StaleWeightsError",
    "StatefulDataLoader",
    "Store",
    "WeightPublisher",
    "WeightRelay",
    "WeightSubscriber",
    "publish_on_commit",
    "StoreClient",
    "TreeShard",
    "Work",
    "WorldSizeMode",
    "XLACollectives",
]
