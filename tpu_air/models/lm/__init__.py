from .config import LMConfig
from .generate import generate, make_lm_generate_fn
from .modeling import (
    CausalLM,
    head_weight,
    lm_chunked_loss_with_targets,
    lm_loss,
    lm_loss_with_targets,
)

__all__ = [
    "LMConfig",
    "generate",
    "make_lm_generate_fn",
    "CausalLM",
    "head_weight",
    "lm_chunked_loss_with_targets",
    "lm_loss",
    "lm_loss_with_targets",
]
