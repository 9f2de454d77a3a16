"""Did an edit leave the engine's compiled programs alone?  Prints the sha256
of the lowered text (``jax.jit(body).lower(...).as_text()``, on the CPU, shapes
only) of every ``InferenceEngine`` program (decode, chunk, mixed) of the tests'
tiny dense, OLMoE, Jamba, latent-attention and Nemotron-H models (and, in a
tree that has it, the one with window layers: PR 60): run it from the root of
two trees and diff the output.  After them the T5 programs: the
engine's two admits and three steps over its eight slots, and ``generate``'s
``while`` and ``scan``, at widths of whole tiles.

    JAX_PLATFORMS=cpu python tools/lowered_programs.py > /tmp/change.txt
    (cd <parent tree> && JAX_PLATFORMS=cpu python <this file> > /tmp/parent.txt)

A program whose line is the same in both runs the same operations in both
(PERF.md, PRs 44 and 45: the cells whose model a change does not name).

``--as-tpu`` answers for the programs a TPU traces: ``jax.default_backend`` is
patched to say so, so the trace-time rules (``latent_pages_read_in_place``,
``state_rows_move_in_place``) pick what they pick on the chip, and the hash is
of the traced jaxpr (a Mosaic kernel does not lower for the CPU).  The
Nemotron-H model is there at a state of whole tiles, which the second rule
wants (PR 48).  Of the T5 programs the engine's are the same text either way
(a ring of positions has no written prefix: the flat read on every backend)
and ``generate``'s two loops differ on a TPU alone (PR 59:
``prefix_append_decode_attention`` under ``prefix_slabs_read_in_place``)."""

import hashlib
import os
import sys

sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.getcwd(), "tests"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import test_gigachat  # noqa: E402
import test_jamba  # noqa: E402
import test_nemotron_h  # noqa: E402
import test_olmoe  # noqa: E402
from tpu_air.models.lm import CausalLM, LMConfig, hf_import  # noqa: E402
from tpu_air.models.lm.generate import (  # noqa: E402
    init_paged_cache, make_paged_decode_body, make_paged_mixed_body,
    make_prefill_chunk_body)
from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration  # noqa: E402
from tpu_air.models.t5.generate import (  # noqa: E402
    init_slot_state, make_generate_fn, make_t5_admit_fn, make_t5_slot_step_fn)

AS_TPU = "--as-tpu" in sys.argv[1:]
if AS_TPU:
    jax.default_backend = lambda: "tpu"
S, C, L = 4, 8, 64
i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
npg = L // C
# a Mamba-2 state [heads, 8, 128]: whole float32 tiles
nemotron = {**test_nemotron_h.TINY, "ssm_state_size": 128} if AS_TPU else test_nemotron_h.TINY
cfgs = {"dense": LMConfig.tiny(),
        "olmoe": hf_import.lm_config_from_hf(test_olmoe.HF),
        "jamba": hf_import.lm_config_from_hf(test_jamba.TINY, max_seq_len=256),
        "gigachat": hf_import.lm_config_from_hf(test_gigachat.TINY, max_seq_len=256, experts_first=4, experts_held=8),
        "nemotron": hf_import.lm_config_from_hf(nemotron, max_seq_len=256, experts_first=2, experts_held=4)}
try:    # a tree from before PR 60 has no such model: its lines are the others'
    import test_laguna  # noqa: E402

    cfgs["laguna"] = hf_import.lm_config_from_hf(test_laguna.TINY, max_seq_len=256)
except ImportError:
    pass
for name, cfg in cfgs.items():
    model = CausalLM(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cache = jax.eval_shape(lambda: init_paged_cache(model, S, S * npg + 1, C, npg))
    slot = {"slot": i32()} if getattr(cfg, "keeps_slot_rows", cfg.has_recurrent_layers) else {}
    progs = {
        "decode": (make_paged_decode_body(model, L), (params, cache, i32(S), i32(S), i32(S, npg)), {}),
        "chunk": (make_prefill_chunk_body(model, C, L), (params, cache, i32(1, C), i32(), i32(), i32(npg)), slot),
        "mixed": (make_paged_mixed_body(model, C, L), (params, cache, i32(S), i32(S), i32(S, npg), i32(1, C), i32(), i32(), i32(npg)), slot),
    }
    for pn, (body, args, kw) in progs.items():
        if AS_TPU:
            text = str(jax.make_jaxpr(lambda a, k: body(*a, **k))(args, kw))
        else:
            text = jax.jit(body, donate_argnums=(1,)).lower(*args, **kw).as_text()
        print(name, pn, len(text), hashlib.sha256(text.encode()).hexdigest()[:16])

# T5 at 4 heads of 32 and 8 rows: a position [8, 128] is one float32 tile
t5 = T5ForConditionalGeneration(T5Config(
    vocab_size=384, d_model=64, d_kv=32, d_ff=128, num_layers=2, num_heads=4,
    dropout_rate=0.0))
params = jax.eval_shape(lambda: t5.init(jax.random.PRNGKey(0), *[jnp.ones((1, 8), jnp.int32)] * 3)["params"])
state, tok = jax.eval_shape(lambda p: init_slot_state(t5, p, 8, 17, 16), params)
progs = {f"admit{n}": (make_t5_admit_fn(t5, n), (params, state, tok, i32(1, n + 2))) for n in (8, 16)}
progs.update({f"step{n}": (make_t5_slot_step_fn(t5, n), (params, state, tok)) for n in (2, 4, 8)})
progs.update({kind: (make_generate_fn(t5, 16, early_stop=kind == "while"),
                     (params, i32(8, 16), i32(8, 16), jax.ShapeDtypeStruct((2,), jnp.uint32)))
              for kind in ("while", "scan")})
for pn, (fn, args) in progs.items():
    text = str(jax.make_jaxpr(fn)(*args)) if AS_TPU else fn.lower(*args).as_text()
    print("t5", pn, len(text), hashlib.sha256(text.encode()).hexdigest()[:16])
