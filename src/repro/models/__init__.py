"""Architecture zoo: dense GQA transformers, MLA, MoE, Mamba-1/2, hybrids,
and stub multimodal frontends — every assigned architecture family."""

from repro.models.config import (
    FrontendConfig, HybridConfig, MLAConfig, MoEConfig, ModelConfig, SSMConfig,
    YarnConfig, reduced,
)
from repro.models.model import (
    CallConfig, decode_step, decode_step_ragged, forward, init_cache,
    init_params, loss_fn, prefill,
)
from repro.models.registry import ARCHS, count_params, get

__all__ = [
    "ARCHS", "CallConfig", "FrontendConfig", "HybridConfig", "MLAConfig",
    "MoEConfig", "ModelConfig", "SSMConfig", "count_params", "decode_step",
    "decode_step_ragged",
    "forward", "get", "init_cache", "init_params", "loss_fn", "prefill",
    "YarnConfig", "reduced",
]
