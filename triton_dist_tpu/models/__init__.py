"""Flagship model family built on the fused distributed kernels.

The reference is a kernel library — its "models" are the LLaMA/Qwen-shaped
GEMM configs its perf tests sweep (test_ag_gemm.py:149-156) and the layer
compositions its tests perform inline. This package IS that composition,
shipped: a Megatron-style TP transformer (sequence-sharded residual stream,
AG-GEMM column projections, GEMM-RS row projections, vocab-parallel loss)
with dense and MoE blocks, differentiable end-to-end through the fused
kernels' custom VJPs.
"""

from triton_dist_tpu.models.decode import (
    ContinuousBatcher,
    KVCacheSpec,
    PagedKVCacheSpec,
    Request,
    StepsExhaustedError,
    decode_step,
    generate,
)
from triton_dist_tpu.models.pipeline import pipeline_apply, stage_slice
from triton_dist_tpu.models.prefix_cache import (
    PagePrefixCache,
    PrefixCacheConfig,
)
from triton_dist_tpu.models.speculative import (
    speculative_generate,
    verify_step,
)
from triton_dist_tpu.models import presets
from triton_dist_tpu.models.sp_transformer import (
    SPTransformer,
    SPTransformerConfig,
    sp_train_step,
)
from triton_dist_tpu.models.tp_transformer import (
    EPMoETransformer,
    EPMoETransformerConfig,
    MoETransformerConfig,
    TransformerConfig,
    TPMoETransformer,
    TPTransformer,
    ep_moe_param_specs,
    ep_moe_quantized_param_specs,
    init_moe_params,
    init_params,
    moe_param_specs,
    moe_quantized_param_specs,
    opt_state_specs,
    pack_gate_up,
    param_specs,
    quantize_moe_serving_params,
    specs_for,
    train_step,
    unpack_gate_up,
)

__all__ = [
    "ContinuousBatcher",
    "KVCacheSpec",
    "PagePrefixCache",
    "PagedKVCacheSpec",
    "PrefixCacheConfig",
    "Request",
    "StepsExhaustedError",
    "presets",
    "pipeline_apply",
    "stage_slice",
    "SPTransformer",
    "SPTransformerConfig",
    "sp_train_step",
    "decode_step",
    "generate",
    "speculative_generate",
    "verify_step",
    "EPMoETransformer",
    "EPMoETransformerConfig",
    "MoETransformerConfig",
    "TransformerConfig",
    "TPMoETransformer",
    "TPTransformer",
    "ep_moe_param_specs",
    "ep_moe_quantized_param_specs",
    "init_moe_params",
    "init_params",
    "moe_param_specs",
    "moe_quantized_param_specs",
    "opt_state_specs",
    "pack_gate_up",
    "param_specs",
    "quantize_moe_serving_params",
    "specs_for",
    "train_step",
    "unpack_gate_up",
]
