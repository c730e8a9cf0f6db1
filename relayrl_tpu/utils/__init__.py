"""Observability + misc utilities (ref layer L8, SURVEY.md §1)."""

from relayrl_tpu.utils.logger import (
    EpochLogger,
    Logger,
    colorize,
    setup_logger_kwargs,
    statistics_scalar,
)
from relayrl_tpu.utils.profiling import (
    timed,
    trace,
)

__all__ = [
    "EpochLogger",
    "Logger",
    "colorize",
    "setup_logger_kwargs",
    "statistics_scalar",
    "timed",
    "trace",
]
