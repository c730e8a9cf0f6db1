"""Host-side data staging: padding, bucketing, epoch buffers."""

from relayrl_tpu.data.batching import (
    BatchStaging,
    PaddedTrajectory,
    TrajectoryBatch,
    pad_trajectory,
    pick_bucket,
    stack_trajectories,
)
from relayrl_tpu.data.replay_buffer import DEFAULT_BUCKETS, EpochBuffer
from relayrl_tpu.data.step_buffer import StepReplayBuffer

__all__ = [
    "BatchStaging",
    "StepReplayBuffer",
    "PaddedTrajectory",
    "TrajectoryBatch",
    "pad_trajectory",
    "pick_bucket",
    "stack_trajectories",
    "EpochBuffer",
    "DEFAULT_BUCKETS",
]
