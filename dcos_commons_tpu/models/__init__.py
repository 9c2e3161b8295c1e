"""Workload model zoo for frameworks/jax.

The reference SDK has no data plane (SURVEY.md: "the workloads are
whatever the service YAML launches"); these are the flagship workloads
the TPU rebuild ships so a user can stand up real training pods:

- transformer.py  decoder-only LM, pure-JAX pytrees, scan-over-layers,
                  bf16 compute, RoPE + GQA + SwiGLU, pallas kernels,
                  dp/fsdp/tp/sp shardings for pjit + pp pipeline trunk
- moe.py          mixture-of-experts FFN, einsum dispatch, ep-parallel
                  all_to_all expert exchange
- mlp.py          MNIST-scale MLP (the BASELINE.json config-3 demo)
"""

from dcos_commons_tpu.models.transformer import (
    TransformerConfig,
    config_from_env,
    init_params,
    loss_fn,
    make_train_step,
    train_state_shardings,
    forward,
    pipeline_forward,
    pipeline_loss_fn,
    pipeline_param_specs,
)
from dcos_commons_tpu.models.decode import (
    decode_step,
    generate,
    init_kv_cache,
    prefill,
    sample_token,
)
from dcos_commons_tpu.models.moe import (
    MoEConfig,
    expert_shard_spec,
    init_moe_params,
    moe_ffn,
    moe_sharding_rules,
)
from dcos_commons_tpu.models.mlp import MlpConfig, mlp_forward, mlp_init, mlp_train_step
from dcos_commons_tpu.models.quantize import (
    dequantize_weight,
    quantize_params_int8,
)

__all__ = [
    "MlpConfig",
    "MoEConfig",
    "TransformerConfig",
    "config_from_env",
    "decode_step",
    "dequantize_weight",
    "expert_shard_spec",
    "forward",
    "generate",
    "init_kv_cache",
    "init_moe_params",
    "init_params",
    "prefill",
    "sample_token",
    "loss_fn",
    "make_train_step",
    "train_state_shardings",
    "mlp_forward",
    "mlp_init",
    "mlp_train_step",
    "moe_ffn",
    "moe_sharding_rules",
    "pipeline_forward",
    "pipeline_loss_fn",
    "pipeline_param_specs",
    "quantize_params_int8",
]
