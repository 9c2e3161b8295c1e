"""Workload utilities: data, checkpointing, tree math."""

from dcos_commons_tpu.utils.data import synthetic_tokens, synthetic_mnist
from dcos_commons_tpu.utils.tree import param_count, param_bytes
from dcos_commons_tpu.utils.checkpoint import (
    AsyncCheckpointer,
    StaleWriterError,
    claim_incarnation,
    restore_checkpoint,
    save_checkpoint,
)
from dcos_commons_tpu.utils.compile_cache import enable_compilation_cache
from dcos_commons_tpu.utils.devices import claim_devices

__all__ = [
    "AsyncCheckpointer",
    "StaleWriterError",
    "claim_devices",
    "claim_incarnation",
    "enable_compilation_cache",
    "param_bytes",
    "param_count",
    "restore_checkpoint",
    "save_checkpoint",
    "synthetic_mnist",
    "synthetic_tokens",
]
