"""Did an edit leave the engine's compiled programs alone?  Prints the sha256
of the lowered text (``jax.jit(body).lower(...).as_text()``, on the CPU, shapes
only) of every ``InferenceEngine`` program (decode, chunk, mixed) of the tests'
tiny dense, OLMoE, Jamba and latent-attention models: run it from the root of
two trees and diff the output.

    JAX_PLATFORMS=cpu python tools/lowered_programs.py > /tmp/change.txt
    (cd <parent tree> && JAX_PLATFORMS=cpu python <this file> > /tmp/parent.txt)

A program whose line is the same in both runs the same operations in both
(PERF.md, PRs 44 and 45: the cells whose model a change does not name)."""

import hashlib
import os
import sys

sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.getcwd(), "tests"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import test_gigachat  # noqa: E402
import test_jamba  # noqa: E402
import test_olmoe  # noqa: E402
from tpu_air.models.lm import CausalLM, LMConfig, hf_import  # noqa: E402
from tpu_air.models.lm.generate import (  # noqa: E402
    init_paged_cache, make_paged_decode_body, make_paged_mixed_body,
    make_prefill_chunk_body)

S, C, L = 4, 8, 64
npg = L // C
cfgs = {"dense": LMConfig.tiny(),
        "olmoe": hf_import.lm_config_from_hf(test_olmoe.HF),
        "jamba": hf_import.lm_config_from_hf(test_jamba.TINY, max_seq_len=256),
        "gigachat": hf_import.lm_config_from_hf(test_gigachat.TINY, max_seq_len=256, experts_first=4, experts_held=8)}
for name, cfg in cfgs.items():
    model = CausalLM(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cache = jax.eval_shape(lambda: init_paged_cache(model, S, S * npg + 1, C, npg))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    slot = {"slot": i32()} if cfg.has_recurrent_layers else {}
    progs = {
        "decode": (make_paged_decode_body(model, L), (params, cache, i32(S), i32(S), i32(S, npg)), {}),
        "chunk": (make_prefill_chunk_body(model, C, L), (params, cache, i32(1, C), i32(), i32(), i32(npg)), slot),
        "mixed": (make_paged_mixed_body(model, C, L), (params, cache, i32(S), i32(S), i32(S, npg), i32(1, C), i32(), i32(), i32(npg)), slot),
    }
    for pn, (body, args, kw) in progs.items():
        text = jax.jit(body, donate_argnums=(1,)).lower(*args, **kw).as_text()
        print(name, pn, len(text), hashlib.sha256(text.encode()).hexdigest()[:16])
