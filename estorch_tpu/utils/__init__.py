from ..obs.trace import annotate, trace
from .backend import (compile_event_counts, enable_compilation_cache,
                      enable_cpu_gloo_collectives, force_cpu_backend,
                      install_compile_event_counters,
                      scoped_compilation_cache)
from .checkpoint import (PeriodicCheckpointer, latest_checkpoint,
                         restore_checkpoint, save_checkpoint)
from .fault import mask_and_renormalize, rank_weights_with_failures, valid_mask
from .metrics import JsonlWriter, MultiWriter, TensorBoardWriter

__all__ = [
    "compile_event_counts",
    "enable_compilation_cache",
    "enable_cpu_gloo_collectives",
    "force_cpu_backend",
    "install_compile_event_counters",
    "scoped_compilation_cache",
    "PeriodicCheckpointer",
    "latest_checkpoint",
    "restore_checkpoint",
    "save_checkpoint",
    "mask_and_renormalize",
    "rank_weights_with_failures",
    "valid_mask",
    "JsonlWriter",
    "MultiWriter",
    "TensorBoardWriter",
    "annotate",
    "trace",
]
