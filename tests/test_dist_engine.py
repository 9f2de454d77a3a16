"""tpu_air.engine.dist tests — sharded decode over a CPU mesh and
prefill/decode disaggregation (the PR 8 acceptance surface).

Host-side pool/admission logic is tested jax-free; sharded parity runs
both in-process (the forced-8-device conftest environment) and through a
jax-clean subprocess rig (tests/_mesh_parity_driver.py); the
disaggregated path runs against the shared ``air`` runtime with REAL
PrefillWorker actor replicas and the shm object store between them.
"""

import os
import subprocess
import sys
import time

import pytest

import jax
import jax.numpy as jnp

import tpu_air
from tpu_air.engine import (
    DisaggRouter,
    EngineConfig,
    InferenceEngine,
    MeshEngine,
    PrefillWorker,
    ShardedPagedPool,
)
from tpu_air.models.lm import CausalLM, LMConfig
from tpu_air.observability import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def lm():
    cfg = LMConfig.tiny()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, model, params


@pytest.fixture(scope="module")
def ckpt(lm):
    from tpu_air.train import Checkpoint

    cfg, _model, params = lm
    return Checkpoint.from_model(model_config=cfg, params=params)


def _drain(engine, limit=500):
    steps = 0
    while not engine.idle():
        engine.step()
        steps += 1
        assert steps < limit, "engine failed to drain"
    return steps


# ---------------------------------------------------------------------------
# ShardedPagedPool host bookkeeping (jax-free)
# ---------------------------------------------------------------------------


class TestShardedPagedPool:
    def _pool(self, dp=2, ppr=9, page_len=8, slots=4, ppslot=4):
        return ShardedPagedPool(dp, ppr, page_len, slots, ppslot)

    def test_slot_routing_and_null_pages(self):
        pool = self._pool()
        assert [pool.replica_of(s) for s in range(4)] == [0, 0, 1, 1]
        # each slot's null page is ITS replica's page 0, globally offset
        assert pool.null_page_of(0) == 0
        assert pool.null_page_of(1) == 0
        assert pool.null_page_of(2) == 9
        assert pool.null_page_of(3) == 9

    def test_global_block_table_offsets(self):
        pool = self._pool()
        pool.admit(0, list(range(1, 17)), 4)   # replica 0, 2 pages
        pool.admit(2, list(range(1, 17)), 4)   # replica 1, same prompt
        table = pool.block_table
        r0 = [p for p in table[0] if p != 0]
        r1 = [p for p in table[2] if p != 9]
        assert r0 and r1
        # replica-1 pages live in the second global page range, and the
        # LOCAL layout is identical (independent per-replica allocators)
        assert all(0 < p < 9 for p in r0)
        assert all(9 < p < 18 for p in r1)
        assert [p - 9 for p in r1] == r0

    def test_chunk_row_and_prompt_ids_offset(self):
        pool = self._pool()
        prompt = list(range(1, 17))
        pool.admit(3, prompt, 4)  # replica 1
        row = pool.chunk_row(3, 0, null_target=False)
        assert all(p >= 9 for p in row)  # null entries -> replica-1 null
        ids = pool.prompt_page_ids(3, len(prompt))
        assert len(ids) == 2 and all(9 < p < 18 for p in ids)

    def test_capacity_is_per_replica(self):
        pool = self._pool()
        assert pool.replica_capacity(0) == pool.replicas[0].capacity()
        assert pool.capacity() == sum(p.capacity() for p in pool.replicas)
        # filling replica 0 leaves replica 1's capacity untouched
        pool.admit(0, list(range(1, 17)), 4)
        pool.admit(1, list(range(17, 33)), 4)
        assert pool.replica_capacity(1) == pool.replicas[1].capacity()
        assert pool.replica_capacity(0) < pool.replica_capacity(1)

    def test_stats_aggregate(self):
        pool = self._pool()
        pool.admit(0, list(range(1, 17)), 4)
        st = pool.stats()
        assert st["dp_replicas"] == 2
        # pages_total excludes each replica's pinned null page: 2 x (9-1)
        assert st["pages_total"] == 16
        assert st["pages_used"] == sum(
            p.stats()["pages_used"] for p in pool.replicas)

    def test_rejects_indivisible_slots(self):
        with pytest.raises(ValueError):
            ShardedPagedPool(3, 9, 8, 4, 4)


# ---------------------------------------------------------------------------
# MeshEngine: sharded decode parity + admission
# ---------------------------------------------------------------------------


def _offline(model, params, prompt, max_new, eos):
    import numpy as np

    from tpu_air.models.lm.generate import generate

    out = np.asarray(generate(model, params, [prompt], max_new_tokens=max_new,
                              eos_token_id=eos))[0].tolist()
    if eos is not None and eos in out:
        out = out[: out.index(eos) + 1]
    return out


def test_mesh_engine_requires_divisible_slots(lm):
    _cfg, model, params = lm
    with pytest.raises(ValueError, match="not divisible by dp"):
        MeshEngine(model, params, EngineConfig(num_slots=3), dp=2, tp=1,
                   auto_start=False)


def test_mesh_engine_per_replica_admission(lm):
    """A prompt that fits replica 1 must not be blocked by a full replica
    0 — and a prompt that fits NO single replica defers even though the
    aggregate pool could cover it."""
    _cfg, model, params = lm
    # 2 replicas x (2 slots * 2 pages + 1 null) = 5 pages each
    ecfg = EngineConfig(num_slots=4, slot_len=32, max_new_tokens=4,
                        page_len=16, reorder_window=2, prefix_cache=False)
    eng = MeshEngine(model, params, ecfg, dp=2, tp=1, auto_start=False,
                     name="mesh-admission")
    try:
        streams = [eng.submit([i + 1] * 20, 4) for i in range(6)]
        _drain(eng)
        outs = [s.result(5.0) for s in streams]
        assert all(len(o) >= 1 for o in outs)
        # all six ran though only 4 slots / 2-per-replica fit at once
        assert eng.metrics.snapshot()["requests_completed"] == 6
    finally:
        eng.close()


def test_mesh_parity_subprocess():
    """The CPU-mesh rig: a jax-clean subprocess forces 8 host devices and
    proves MeshEngine (dp=2,tp=2 / 4x2 / 1x8) token-identical to the
    single-chip paged engine and offline generate."""
    env = dict(os.environ)
    for k in ("TPU_AIR_COORDINATOR", "TPU_AIR_NUM_PROCESSES",
              "TPU_AIR_PROCESS_ID", "TPU_AIR_NUM_CHIPS",
              "TPU_AIR_CHIPS_PER_HOST", "XLA_FLAGS"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "_mesh_parity_driver.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}")
    assert "MESH-PARITY-OK" in proc.stdout


# ---------------------------------------------------------------------------
# Disaggregated prefill/decode (real actors, shm store, tracing)
# ---------------------------------------------------------------------------


@pytest.fixture
def _clean_tracing():
    tracing.disable()
    tracing.recorder().clear()
    yield
    tracing.disable()
    tracing.recorder().clear()


def test_disagg_end_to_end_trace_and_parity(air, lm, ckpt, _clean_tracing):
    """The acceptance trace: a shared-prefix arrival completes with
    prefill and decode on DISTINCT replicas, KV pages through the shm
    object store, and ONE trace id spanning queue_wait -> prefill ->
    kv_transfer -> decode."""
    cfg, model, params = lm
    eos = cfg.eos_token_id
    max_new = 6
    prompts = [[7, 8, 9, 10, 11, 12, 13, 14],          # one full page
               [7, 8, 9, 10, 11, 12, 13, 14, 3, 4],    # shared prefix
               [101, 102, 103]]
    want = [_offline(model, params, p, max_new, eos) for p in prompts]

    tracing.enable()
    router = DisaggRouter(
        ckpt,
        EngineConfig(num_slots=4, slot_len=64, max_new_tokens=max_new,
                     page_len=8),
        prefill_replicas=2, name="disagg-e2e")
    try:
        got = []
        trace_ids = []
        for p in prompts:
            with tracing.span("client.request") as root:
                trace_ids.append(root.trace_id)
                got.append(router.submit(p).result(120.0))
        assert got == want, f"disagg parity\nwant={want}\ngot={got}"

        # worker spans ship back on the done message — give them a beat
        deadline = time.monotonic() + 20.0
        needed = {"engine.queue_wait", "engine.prefill",
                  "engine.kv_transfer", "engine.request", "engine.decode"}
        by_trace = {}
        while time.monotonic() < deadline:
            spans = tracing.recorder().recent(limit=0)
            by_trace = {}
            for sp in spans:
                by_trace.setdefault(sp.trace_id, []).append(sp)
            if all(needed <= {s.name for s in by_trace.get(t, [])}
                   for t in trace_ids):
                break
            time.sleep(0.25)
        driver_pid = os.getpid()
        for tid in trace_ids:
            names = {s.name for s in by_trace.get(tid, [])}
            assert needed <= names, f"trace {tid} spans: {sorted(names)}"
            # prefill ran in ANOTHER process than decode
            prefill_pids = {s.pid for s in by_trace[tid]
                            if s.name == "engine.prefill"}
            decode_pids = {s.pid for s in by_trace[tid]
                           if s.name == "engine.decode"}
            assert decode_pids == {driver_pid}
            assert prefill_pids and driver_pid not in prefill_pids
        assert router.handoffs == len(prompts)
        assert router.fallbacks == 0
        # distinct actor replicas both took work (least-loaded spread)
        st = router.stats()
        assert all(w.get("prefills", 0) >= 1 for w in st["workers"])
        assert st["engine"]["topology"]["prefill_replicas"] == 2
    finally:
        router.close()


def test_submit_prefilled_defers_on_pool_exhaustion(air, lm, ckpt):
    """A handoff that does not fit the decode pool DEFERS in the
    admission queue (and is admitted once pages free) — never dropped."""
    cfg, model, params = lm
    eos = cfg.eos_token_id
    max_new = 4
    # num_pages=5 -> 4 obtainable after the null page; one worst-case
    # admit (prompt 16 + budget 4 -> 3 pages) fits, two would need 6:
    # exactly one handoff admits per round, the rest defer in the queue
    ecfg = EngineConfig(num_slots=2, slot_len=32, max_new_tokens=max_new,
                        page_len=8, num_pages=5, prefix_cache=False,
                        reorder_window=0)
    engine = InferenceEngine(model, params, ecfg, auto_start=False,
                             name="disagg-exhaustion")
    worker = PrefillWorker(ckpt, page_len=8, slot_len=32,
                           name="exhaustion-worker")
    try:
        prompts = [[i + 1] * 16 for i in range(3)]
        handoffs = [worker.prefill(p) for p in prompts]
        streams = []
        for p, h in zip(prompts, handoffs):
            payload = tpu_air.get(h["kv"])
            streams.append(engine.submit_prefilled(
                p, h["first_token"], payload, max_new))
        # after one step only ONE fits; the others sit in the queue
        engine.step()
        snap = engine.metrics.snapshot()
        assert snap["slot_occupancy"] == 1
        assert snap["queue_depth"] == 2
        _drain(engine)
        outs = [s.result(5.0) for s in streams]
        want = [_offline(model, params, p, max_new, eos) for p in prompts]
        assert outs == want  # deferred handoffs completed token-identical
    finally:
        engine.close()


def test_prefill_replica_death_reroutes_then_falls_back(air, lm, ckpt):
    """Killing a prefill replica re-routes new submits to the survivor;
    killing ALL replicas falls back to local prefill on the decode
    engine.  In-flight decode streams keep their tokens throughout."""
    cfg, model, params = lm
    eos = cfg.eos_token_id
    max_new = 6
    router = DisaggRouter(
        ckpt,
        EngineConfig(num_slots=4, slot_len=64, max_new_tokens=max_new,
                     page_len=8),
        prefill_replicas=2, prefill_timeout=60.0, name="disagg-death")
    try:
        # a long-budget request in flight before any failure
        inflight_prompt = [41, 42, 43, 44, 45]
        inflight = router.submit(inflight_prompt)

        tpu_air.kill(router._workers[0])
        p1 = [51, 52, 53, 54]
        out1 = router.submit(p1).result(120.0)
        assert out1 == _offline(model, params, p1, max_new, eos)
        assert router.live_prefill_replicas() == 1
        assert router.reroutes >= 1
        assert router.fallbacks == 0

        tpu_air.kill(router._workers[1])
        p2 = [61, 62, 63]
        out2 = router.submit(p2).result(120.0)
        assert out2 == _offline(model, params, p2, max_new, eos)
        assert router.live_prefill_replicas() == 0
        assert router.fallbacks >= 1

        # the pre-failure stream was never dropped
        assert inflight.result(120.0) == _offline(
            model, params, inflight_prompt, max_new, eos)
    finally:
        router.close()


# ---------------------------------------------------------------------------
# serve integration: mesh config on the engine deployment
# ---------------------------------------------------------------------------


def test_engine_server_mesh_path(lm, ckpt):
    from tpu_air.serve.engine_deployment import _EngineServer

    cfg, model, params = lm
    eos = cfg.eos_token_id
    server = _EngineServer(
        ckpt,
        EngineConfig(num_slots=4, slot_len=64, max_new_tokens=4, page_len=8),
        engine_name="serve-mesh", mesh=(2, 2),
    )
    assert server.stats() == {}  # scrape before build stays lazy
    out = server({"prompts": [[5, 6, 7, 8], [9, 10, 11, 12]],
                  "max_new_tokens": 4})
    assert len(out["results"]) == 2
    for r, p in zip(out["results"], [[5, 6, 7, 8], [9, 10, 11, 12]]):
        assert r["tokens"] == _offline(model, params, p, 4, eos)
    snap = server.stats()
    assert snap["topology"]["mesh"] == "2x2"
    # under the full suite the session runtime is live and the engine takes
    # a real chip lease; standalone it falls back to visible devices
    lease = snap["topology"]["lease"]
    assert lease == "local" or lease.startswith("chips:")
    assert snap["topology"]["decode_replicas"] == 2
    server._engine.close()


def test_topology_in_metrics_export(lm):
    """/metrics surfaces lease id, mesh shape and replica-count gauges
    through the registry's prometheus rendering."""
    from tpu_air.engine.metrics import prometheus_lines

    _cfg, model, params = lm
    eng = MeshEngine(model, params,
                     EngineConfig(num_slots=2, slot_len=32, page_len=8),
                     dp=2, tp=1, auto_start=False, name="topo-export")
    try:
        lines = prometheus_lines({"topo-export": eng.metrics.snapshot()})
        info = [l for l in lines
                if l.startswith("tpu_air_engine_topology_info")]
        assert len(info) == 1
        assert 'mesh="2x1"' in info[0]
        assert 'lease="local"' in info[0] or 'lease="chips:' in info[0]
        assert 'role="decode"' in info[0]
        gauges = [l for l in lines if
                  l.startswith("tpu_air_engine_topology_decode_replicas")]
        assert gauges and gauges[0].endswith(" 2")
    finally:
        eng.close()
