"""Metrics, checkpointing, and small helpers."""

from .checkpoint import (load_shard, restore_train_state, save_shard,
                         save_train_state, save_train_state_async)
from .compile_cache import enable_compile_cache
from .metrics import LatencyHistogram, PipelineMetrics
from .profile import (annotate, counters, phase, phases, step_annotate,
                      trace)

__all__ = ["LatencyHistogram", "PipelineMetrics", "save_train_state",
           "save_train_state_async",
           "restore_train_state", "save_shard", "load_shard",
           "trace", "annotate", "step_annotate", "phase", "phases",
           "counters", "enable_compile_cache"]
