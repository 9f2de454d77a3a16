"""Flax T5 / FLAN-T5 model family."""

from .config import T5Config
from .generate import (
    generate,
    init_slot_state,
    make_generate_fn,
    make_t5_admit_fn,
    make_t5_slot_step_fn,
)
from .hf_import import config_from_hf, convert_t5_state_dict, load_t5_from_hf
from .modeling import (
    T5ForConditionalGeneration,
    cross_entropy_loss,
    shift_right,
)

__all__ = [
    "T5Config",
    "T5ForConditionalGeneration",
    "config_from_hf",
    "convert_t5_state_dict",
    "cross_entropy_loss",
    "generate",
    "init_slot_state",
    "load_t5_from_hf",
    "make_generate_fn",
    "make_t5_admit_fn",
    "make_t5_slot_step_fn",
    "shift_right",
]
