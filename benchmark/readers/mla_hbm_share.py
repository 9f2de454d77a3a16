"""Share of the HBM roofline one paged decode step of a latent-attention
sparse-expert rank reaches, in percent: the bytes the step streams
(``costs_mla.decode_step_bytes``: the held weights but the embedding, the
routed experts the steps touched and the latent at the positions the engine
counted live, a step: both counts are the kind's facts) at the peak
bandwidth, over the median device time of one execution of the step's
program (found on the capture's ``XLA Modules`` line as ``module_hbm_share``
finds it).

``modules`` names the programs a step can be (the decode program alone, the
mixed step that carries a prefill chunk beside it): the one the capture
holds MOST executions of is read, with the experts ITS steps streamed (the
fact is by program; a chunk's rows touch experts of their own).  The bytes
are a floor for either: the mixed step streams them too, and computes more.
Not this family's configuration, no such line, none of the programs or no
counts for it (an older tree): nothing to read."""

from benchmark import costs_mla, spans, stats
from benchmark.readers.module_hbm_share import module_durations


def most_run(runs):
    """Of ``{module: executions}``, the module with the most (the first
    named wins a tie) and its executions; (None, []) where none ran."""
    module = max(runs, key=lambda m: len(runs[m]), default=None)
    return (module, runs[module]) if module and runs[module] else (None, [])


def read(rc, modules):
    if rc.trace is None or rc.peak is None or "kv_lora_rank" not in rc.cfg:
        return None
    path = spans.newest_xplane()
    if path is None:
        return None
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    module, xs = most_run({m: module_durations(data, m) for m in modules})
    live = rc.facts.get("latent_positions_live_per_step")
    streamed = (rc.facts.get("moe_held_experts_streamed_per_step")
                or {}).get(module)
    if live is None or streamed is None:
        return None
    need = costs_mla.decode_step_bytes(rc.cfg, live, streamed)
    floor_s = need["total_bytes"] / rc.peak["hbm_bytes_per_s"]
    return 100.0 * floor_s / stats.percentile(xs, 0.5)
