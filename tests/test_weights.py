"""Live weight hot-swap, canary gate, and multi-tenant adapters.

Layers under test:
  * WeightStore — versioned publish/restore over the shm object store:
    manifest-written-last atomicity, per-tensor crc32 validation on EVERY
    restore read, retain-N GC, adapter versions;
  * torn/corrupt publish chaos — a ``weights.publish`` kill never goes
    live (no manifest), a corrupt shard is caught at restore, and a
    value-corrupting fault (valid checksums, wrong values) is caught by
    the canary probe gate and AUTO-ROLLED-BACK with zero non-200s;
  * engine hot swap — ``swap_params`` between decode steps: same-weights
    swap is token-invisible to in-flight streams, swap under streaming
    load drops nothing, rollback restores the prior device tree;
  * multi-tenant LoRA adapters — per-request ``adapter_id`` gathered
    per-slot inside the jitted decode step; mixed-tenant batch output is
    token-identical to per-tenant offline greedy decodes;
  * trainer handoff — ``CheckpointConfig.publish_weights_to`` publishes
    every retained checkpoint and GCs the store;
  * the serve-plane controller — canary → probe → soak → fleet promote,
    surfaced in ``/-/stats`` and ``tpu_air_weights_*`` metrics.
"""

import json
import os
import tempfile
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_air
from tpu_air import faults
from tpu_air.engine import EngineConfig, InferenceEngine
from tpu_air.faults import FaultPlan, FaultSpec
from tpu_air.models.lm import CausalLM, LMConfig
from tpu_air.serve.weights import (
    TornPublishError,
    WeightsIntegrityError,
    WeightStore,
    compute_probe,
    offline_greedy,
)

PORT = 8243


@pytest.fixture(scope="module")
def lm():
    cfg = LMConfig.tiny()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, model, params


@pytest.fixture
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _prompts(seed, n, lo=3, hi=12, vocab=384):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(1, vocab, size=rng.randint(lo, hi))))
            for _ in range(n)]


def _tree_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# WeightStore: versioned publish / checksummed restore / GC
# ---------------------------------------------------------------------------


def test_store_roundtrip_versions_and_gc(lm):
    cfg, model, params = lm
    ws = WeightStore(tempfile.mkdtemp(prefix="wstore-"))
    assert ws.latest_version() is None
    v1 = ws.publish(params, metadata={"iteration": 1})
    assert ws.versions() == [v1] and v1 == 1
    assert _tree_equal(ws.load(), params)
    man = ws.manifest(v1)
    assert man["kind"] == "full" and man["metadata"]["iteration"] == 1
    # monotone ids; retain-N drops the oldest FULL versions
    v2, v3 = ws.publish(params), ws.publish(params)
    doomed = ws.gc(keep=2)
    assert doomed == [v1]
    assert ws.versions() == [v2, v3]
    with pytest.raises(KeyError):
        ws.manifest(v1)
    # GC'd shards are really gone from the object store
    with pytest.raises(KeyError):
        ws.load(v1)


def test_store_adapter_roundtrip_and_gc_exemption(lm):
    cfg, model, params = lm
    ws = WeightStore(tempfile.mkdtemp(prefix="wstore-"))
    ws.publish(params)
    a = np.random.RandomState(0).randn(cfg.d_model, 4).astype(np.float32)
    b = np.random.RandomState(1).randn(4, cfg.vocab_size).astype(np.float32)
    va = ws.publish_adapter("tenant-a", a, b)
    name, la, lb = ws.load_adapter(va)
    assert name == "tenant-a"
    assert np.array_equal(la, a) and np.array_equal(lb, b)
    with pytest.raises(ValueError):
        ws.load_adapter(1)  # version 1 is kind="full"
    # adapter versions are controller-evicted, never retention-GC'd
    ws.publish(params), ws.publish(params)
    ws.gc(keep=1)
    assert va in ws.versions()


def test_torn_publish_never_goes_live(_clean_faults):
    """A publisher killed mid-publish (``weights.publish`` kill) leaves
    orphan shards and NO manifest; the store's latest version is
    unchanged, and a retried publish reuses the version number and
    overwrites the orphans (delete-then-put: objects are immutable)."""
    params = {"a": np.arange(6, dtype=np.float32),
              "b": np.ones((2, 3), np.float32)}
    ws = WeightStore(tempfile.mkdtemp(prefix="wstore-"))
    v1 = ws.publish(params)
    faults.install(FaultPlan(specs=[
        FaultSpec("weights.publish", "kill", at=2)]))
    with pytest.raises(TornPublishError):
        ws.publish(params)
    faults.clear()
    assert ws.latest_version() == v1  # torn version does not exist
    assert _tree_equal(ws.load(), params)
    # retry (no faults): same number, clean shards — even over the orphans
    v2 = ws.publish({"a": params["a"] * 2, "b": params["b"] * 2})
    assert v2 == v1 + 1
    assert np.array_equal(ws.load(v2)["a"], params["a"] * 2)


def test_restore_rejects_corrupt_and_missing_shards():
    params = {"a": np.arange(6, dtype=np.float32),
              "b": np.ones((2, 3), np.float32)}
    ws = WeightStore(tempfile.mkdtemp(prefix="wstore-"))
    v = ws.publish(params)
    oid = ws.manifest(v)["tensors"][0]["object_id"]
    # bit-rot stand-in: same shape/dtype, different bytes under the same id
    ws._store.delete(oid)
    ws._store.put(np.arange(6, dtype=np.float32) + 99.0, oid)
    with pytest.raises(WeightsIntegrityError, match="crc32"):
        ws.load(v)
    ws2 = WeightStore(tempfile.mkdtemp(prefix="wstore-"))
    v2 = ws2.publish(params)
    ws2._store.delete(ws2.manifest(v2)["tensors"][1]["object_id"])
    with pytest.raises(WeightsIntegrityError, match="missing"):
        ws2.load(v2)


def test_corrupt_publish_fault_passes_checksums(_clean_faults):
    """The ``corrupt`` action is the canary gate's quarry: values flip
    BEFORE checksumming, so the restore path loads it cleanly — only the
    probe gate can catch it."""
    params = {"a": np.arange(6, dtype=np.float32),
              "b": np.ones((2, 3), np.float32)}
    ws = WeightStore(tempfile.mkdtemp(prefix="wstore-"))
    faults.install(FaultPlan(specs=[
        FaultSpec("weights.publish", "corrupt", at=1)]))
    v = ws.publish(params)
    faults.clear()
    bad = ws.load(v)  # no WeightsIntegrityError: checksums are valid
    assert not np.array_equal(bad["a"], params["a"])
    assert np.array_equal(bad["b"], params["b"])


def test_generated_plan_covers_weight_sites():
    sites = ["weights.publish", "weights.swap"]
    p = FaultPlan.generate(seed=41, sites=sites)
    assert p.to_json() == FaultPlan.generate(seed=41, sites=sites).to_json()
    by_site = {s.site: s for s in p.specs}
    assert by_site["weights.publish"].action == "corrupt"
    assert by_site["weights.swap"].action == "delay"


# ---------------------------------------------------------------------------
# engine hot swap: parity, no dropped streams, rollback
# ---------------------------------------------------------------------------


def test_same_weights_swap_midstream_is_token_invisible(lm):
    """The tentpole parity gate: a swap to byte-identical weights between
    decode steps must be a NO-OP for in-flight streams — same tokens as
    an engine that never swapped, and nothing dropped."""
    cfg, model, params = lm
    max_new = 10
    prompts = _prompts(seed=21, n=4)
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=max_new),
        auto_start=False,
    )
    streams = [engine.submit(p) for p in prompts]
    engine.step()
    engine.step()  # in-flight: slots mid-decode, queue non-empty
    stall_ms = engine.swap_params(params, version=2)
    assert stall_ms >= 0.0 and engine.weights_version() == 2
    n = 0
    while not engine.idle():
        engine.step()
        n += 1
        assert n < 500, "engine failed to drain after swap"
    for p, s in zip(prompts, streams):
        assert s.result(5.0) == offline_greedy(model, params, p, max_new)
    snap = engine.metrics.snapshot()
    assert snap["requests_completed"] == len(prompts)
    assert snap["weights"]["swaps"] == 1
    assert snap["weights"]["last_stall_ms"] == pytest.approx(stall_ms)
    engine.close()


def test_swap_under_load_zero_dropped_streams(lm):
    """A REAL weight change mid-stream under threaded load: every stream
    completes with its full budget (zero drops, zero errors) while the
    serving version flips underneath."""
    cfg, model, params = lm
    new_params = jax.tree_util.tree_map(
        lambda x: np.asarray(x) * 0.5, params)
    max_new = 12
    prompts = _prompts(seed=31, n=6)
    with InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=max_new),
    ) as engine:
        results, errors = [None] * len(prompts), []

        def consume(i, p):
            try:
                results[i] = list(engine.submit(p))
            except Exception as e:  # noqa: BLE001 — recorded, asserted empty
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=consume, args=(i, p), daemon=True)
                   for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        time.sleep(0.05)  # let streams admit and decode a few steps
        engine.swap_params(new_params, version=2)
        for t in threads:
            t.join(timeout=120.0)
            assert not t.is_alive()
        assert errors == []
        assert all(r is not None and len(r) == max_new for r in results)
        assert engine.weights_version() == 2
        # post-drain traffic decodes under the NEW weights
        fresh = _prompts(seed=32, n=1)[0]
        assert list(engine.submit(fresh)) == offline_greedy(
            model, new_params, fresh, max_new)


def test_rollback_restores_prior_device_tree(lm):
    cfg, model, params = lm
    bad = jax.tree_util.tree_map(lambda x: np.asarray(x) * -1 + 1, params)
    max_new = 8
    prompt = _prompts(seed=41, n=1)[0]
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=max_new),
        auto_start=False,
    )
    engine.swap_params(bad, version=2)
    with pytest.raises(ValueError):
        engine.swap_params({"nope": np.zeros(3)})  # structure mismatch
    engine.rollback_params()
    assert engine.weights_version() is None or engine.weights_version() != 2
    s = engine.submit(prompt)
    while not engine.idle():
        engine.step()
    assert s.result(5.0) == offline_greedy(model, params, prompt, max_new)
    snap = engine.metrics.snapshot()["weights"]
    assert snap["swaps"] == 2 and snap["rollbacks"] == 1
    with pytest.raises(RuntimeError):
        engine.rollback_params()  # only ONE prior tree is retained
    engine.close()


# ---------------------------------------------------------------------------
# multi-tenant adapters
# ---------------------------------------------------------------------------


def test_adapter_parity_mixed_tenants_vs_offline(lm):
    """A mixed-tenant batch (base + two adapters decoding CONCURRENTLY in
    the same slot pool) is token-identical to each tenant's offline
    greedy decode — the per-slot bank gather changes nothing else."""
    cfg, model, params = lm
    max_new = 8
    rng = np.random.RandomState(5)
    a1 = (rng.randn(cfg.d_model, 4) * 0.5).astype(np.float32)
    b1 = (rng.randn(4, cfg.vocab_size) * 0.5).astype(np.float32)
    a2 = (rng.randn(cfg.d_model, 2) * 0.5).astype(np.float32)
    b2 = (rng.randn(2, cfg.vocab_size) * 0.5).astype(np.float32)
    prompts = _prompts(seed=51, n=3)
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=3, slot_len=64, max_new_tokens=max_new,
                     adapter_slots=2, adapter_rank=4),
        auto_start=False,
    )
    assert engine.load_adapter("tenant-a", a1, b1) == 1
    # rank-2 adapter zero-pads into the rank-4 bank
    assert engine.load_adapter("tenant-b", a2, b2) == 2
    assert engine.adapters() == {"tenant-a": 1, "tenant-b": 2}
    streams = [
        engine.submit(prompts[0]),                            # base
        engine.submit(prompts[1], adapter_id="tenant-a"),
        engine.submit(prompts[2], adapter_id="tenant-b"),
    ]
    while not engine.idle():
        engine.step()
    assert streams[0].result(5.0) == offline_greedy(
        model, params, prompts[0], max_new)
    assert streams[1].result(5.0) == offline_greedy(
        model, params, prompts[1], max_new, adapter_a=a1, adapter_b=b1)
    assert streams[2].result(5.0) == offline_greedy(
        model, params, prompts[2], max_new, adapter_a=a2, adapter_b=b2)
    # at least one adapter stream must actually DIFFER from base decode,
    # or the gather proves nothing
    assert streams[1].result(0.1) != offline_greedy(
        model, params, prompts[1], max_new)
    # both tenants' prompts rode a decode step of the base request: their
    # first tokens came from the mixed program's head, under their adapters
    assert engine.metrics.snapshot()["chunks_fused"] == 2
    engine.close()


def test_adapter_lifecycle_guards(lm):
    cfg, model, params = lm
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=4,
                     adapter_slots=1, adapter_rank=4),
        auto_start=False,
    )
    a = np.zeros((cfg.d_model, 4), np.float32)
    b = np.zeros((4, cfg.vocab_size), np.float32)
    with pytest.raises(ValueError):
        engine.submit([1, 2, 3], adapter_id="ghost")  # unknown tenant
    with pytest.raises(ValueError):
        engine.load_adapter("fat", np.zeros((cfg.d_model, 8), np.float32),
                            np.zeros((8, cfg.vocab_size), np.float32))
    engine.load_adapter("a", a, b)
    with pytest.raises(ValueError):
        engine.load_adapter("b", a, b)  # bank full (adapter_slots=1)
    # reload-in-place keeps the row
    assert engine.load_adapter("a", a, b) == 1
    s = engine.submit([1, 2, 3], adapter_id="a")
    engine.step()
    with pytest.raises(RuntimeError):
        engine.unload_adapter("a")  # active slot holds the row
    while not engine.idle():
        engine.step()
    s.result(5.0)
    assert engine.unload_adapter("a") is True
    assert engine.unload_adapter("a") is False  # already gone
    assert engine.adapters() == {}
    engine.close()


def test_adapters_rejected_on_mesh(lm):
    from tpu_air.engine import MeshEngine

    cfg, model, params = lm
    with pytest.raises(ValueError, match="single-chip"):
        MeshEngine(
            model, params,
            EngineConfig(num_slots=2, slot_len=32, adapter_slots=1),
            dp=1, tp=1, auto_start=False)


# ---------------------------------------------------------------------------
# trainer handoff: publish-on-retain
# ---------------------------------------------------------------------------


def test_session_publishes_retained_checkpoints(lm):
    from tpu_air.train import Checkpoint
    from tpu_air.train.config import CheckpointConfig
    from tpu_air.train.session import Session

    cfg, model, params = lm
    wroot = tempfile.mkdtemp(prefix="wstore-")
    sess = Session(tempfile.mkdtemp(),
                   CheckpointConfig(num_to_keep=2,
                                    publish_weights_to=wroot))
    for it in range(3):
        sess.report({"loss": 1.0 / (it + 1)},
                    Checkpoint.from_model(model_config=cfg, params=params))
    ws = WeightStore(wroot)
    assert len(ws.versions()) == 2  # GC'd to num_to_keep
    man = ws.manifest(ws.latest_version())
    assert man["metadata"]["iteration"] == 3
    assert man["metadata"]["metrics"]["loss"] == pytest.approx(1.0 / 3)
    assert _tree_equal(ws.load(), params)
    # a checkpoint WITHOUT params (metrics-only) publishes nothing and
    # does not kill the loop
    sess.report({"loss": 0.1}, Checkpoint.from_model(metrics={"e": 1}))
    assert len(ws.versions()) == 2


# ---------------------------------------------------------------------------
# serve plane: canary gate, fleet promote, rollback observability
# ---------------------------------------------------------------------------


def _post(path, payload, headers=None, port=PORT):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


class _StreamClient(threading.Thread):
    """Submit one stream, then poll (pinned) to completion, recording any
    non-200 seen AFTER admission."""

    def __init__(self, path, prompt, max_new):
        super().__init__(daemon=True)
        self.path = path
        self.prompt = prompt
        self.max_new = max_new
        self.admitted = threading.Event()
        self.tokens = None
        self.bad_status = []

    def run(self):
        status, out, hdrs = _post(self.path, {
            "action": "submit", "prompt": self.prompt,
            "max_new_tokens": self.max_new})
        if status != 200:
            self.bad_status.append(("submit", status, out))
            return
        self.admitted.set()
        rid = out["request_id"]
        pin = {"x-tpu-air-replica": hdrs.get("x-tpu-air-replica", "")}
        cursor, toks = 0, []
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            status, out, _ = _post(self.path, {
                "action": "poll", "request_id": rid, "cursor": cursor,
            }, headers=pin)
            if status != 200:
                self.bad_status.append(("poll", status, out))
                return
            got = out.get("tokens") or []
            toks += got
            cursor += len(got)
            if out.get("done"):
                self.tokens = toks
                return
            time.sleep(0.01)


def test_canary_promote_fleet_with_inflight_parity(lm, air):
    """The end-to-end acceptance: the trainer publishes, the canary gate
    passes (pinned probe fingerprint), the whole fleet promotes — while
    in-flight streams keep decoding token-identically (same weights, so
    the swap must be invisible) — and the promotion is observable in
    ``/-/stats`` and the merged ``tpu_air_weights_*`` metrics."""
    from tpu_air import serve
    from tpu_air.engine.metrics import merge_snapshots, prometheus_lines
    from tpu_air.serve import EngineDeployment, attach_weights
    from tpu_air.serve.proxy import serve_control_stats
    from tpu_air.train import Checkpoint

    cfg, model, params = lm
    ckpt = Checkpoint.from_model(model_config=cfg, params=params)
    max_new = 16
    prompts = _prompts(seed=61, n=3)
    probe_prompts = _prompts(seed=62, n=2)
    try:
        h = serve.run(
            EngineDeployment.options(
                name="lm-weights", route_prefix="/weights", num_replicas=2,
            ).bind(ckpt, EngineConfig(num_slots=4, slot_len=64,
                                      max_new_tokens=max_new)),
            port=PORT,
        )
        root = tempfile.mkdtemp(prefix="wstore-")
        store = WeightStore(root)
        probe = compute_probe(model, params, probe_prompts, max_new=4)
        v = store.publish(params, metadata={"iteration": 1}, probe=probe)
        ctl = attach_weights("/weights", root,
                             probe_prompts=probe_prompts, probe_max_new=4,
                             soak_s=0.2)
        clients = [_StreamClient("/weights", p, max_new) for p in prompts]
        for c in clients:
            c.start()
        for c in clients:
            assert c.admitted.wait(timeout=120.0), c.bad_status
        out = ctl.promote()
        assert out["promoted"] and out["version"] == v
        assert out["max_stall_ms"] >= 0.0
        for c in clients:
            c.join(timeout=180.0)
            assert not c.is_alive()
        for c, p in zip(clients, prompts):
            assert c.bad_status == [], c.bad_status
            assert c.tokens == offline_greedy(model, params, p, max_new)
        # observable: /-/stats weights section...
        st = serve_control_stats()["weights"]["/weights"]
        assert st["state"] == "serving"
        assert st["current_version"] == v and st["promotions"] == 1
        # ...and the merged fleet metrics + prometheus families
        snaps = {f"r{i}": tpu_air.get(r.handle.remote("stats", (), {}))
                 for i, r in enumerate(h._replicas)}
        merged = merge_snapshots(snaps)
        assert merged["weights"]["version"] == v
        assert merged["weights"]["swaps"] == 2  # canary + 1 fleet replica
        text = "\n".join(prometheus_lines({"lm-weights": merged}))
        assert f'tpu_air_weights_version{{engine="lm-weights"}} {v}' in text
        assert 'tpu_air_weights_swaps{engine="lm-weights"} 2' in text
    finally:
        serve.shutdown()


@pytest.mark.chaos
def test_bad_weight_publish_rolls_back_zero_non200(lm, air, _clean_faults):
    """ISSUE acceptance: a seeded ``weights.publish`` corrupt fault ships
    bad values with VALID checksums; the canary swap succeeds, the probe
    fingerprint mismatches, and the controller auto-rolls the canary back
    — within one soak window, with zero non-200s for admitted streams,
    the rollback visible in ``/-/stats`` and ``tpu_air_weights_*``."""
    from tpu_air import serve
    from tpu_air.engine.metrics import merge_snapshots, prometheus_lines
    from tpu_air.serve import EngineDeployment, attach_weights
    from tpu_air.serve.proxy import serve_control_stats
    from tpu_air.train import Checkpoint

    seed = int(os.environ.get("TPU_AIR_FAULT_SEED", "41"))
    plan = FaultPlan.generate(seed, sites=["weights.publish",
                                           "weights.swap"])
    assert plan.to_json() == FaultPlan.generate(
        seed, sites=["weights.publish", "weights.swap"]).to_json()

    cfg, model, params = lm
    ckpt = Checkpoint.from_model(model_config=cfg, params=params)
    max_new = 16
    prompts = _prompts(seed=71, n=4)
    probe_prompts = _prompts(seed=72, n=2)
    try:
        h = serve.run(
            EngineDeployment.options(
                name="lm-badw", route_prefix="/badw", num_replicas=2,
            ).bind(ckpt, EngineConfig(num_slots=4, slot_len=64,
                                      max_new_tokens=max_new)),
            port=PORT,
            fault_plan=plan,
        )
        root = tempfile.mkdtemp(prefix="wstore-")
        store = WeightStore(root)
        probe = compute_probe(model, params, probe_prompts, max_new=4)
        # the template corrupts shard rng∈[1,6]; the tiny LM has more
        # tensors than that, so the publish ALWAYS ships bad values
        assert len(jax.tree_util.tree_leaves(params)) > 6
        v_bad = store.publish(params, probe=probe)
        bad = store.load(v_bad)  # valid checksums — restore can't catch it
        assert not _tree_equal(bad, params)

        ctl = attach_weights("/badw", root,
                             probe_prompts=probe_prompts, probe_max_new=4,
                             soak_s=0.2)
        clients = [_StreamClient("/badw", p, max_new) for p in prompts]
        for c in clients:
            c.start()
        for c in clients:
            assert c.admitted.wait(timeout=120.0), c.bad_status
        out = ctl.promote()
        assert not out["promoted"], out
        assert "fingerprint" in out["reason"]
        for c in clients:
            c.join(timeout=180.0)
            assert not c.is_alive()
        for c in clients:
            assert c.bad_status == [], c.bad_status
            assert c.tokens is not None and len(c.tokens) == max_new
        # rollback surfaced: controller stats via /-/stats...
        st = serve_control_stats()["weights"]["/badw"]
        assert st["rollbacks"] == 1
        assert st["gate_failures"].get("probe") == 1
        assert st["current_version"] is None  # nothing ever promoted
        # ...and engine metrics: exactly one swap + one rollback, on the
        # canary only — the fleet never saw the bad version
        snaps = {f"r{i}": tpu_air.get(r.handle.remote("stats", (), {}))
                 for i, r in enumerate(h._replicas)}
        merged = merge_snapshots(snaps)
        assert merged["weights"]["rollbacks"] == 1
        assert merged["weights"]["swaps"] == 2  # bad swap + rollback swap
        text = "\n".join(prometheus_lines({"lm-badw": merged}))
        assert 'tpu_air_weights_rollbacks{engine="lm-badw"} 1' in text
        # post-rollback: the fleet serves the ORIGINAL weights
        p = probe_prompts[0]
        status, body, _ = _post("/badw", {"prompts": [p],
                                          "max_new_tokens": 4})
        assert status == 200
        assert body["results"][0]["tokens"] == offline_greedy(
            model, params, p, 4)
    finally:
        serve.shutdown()
        faults.clear()


def test_adapter_promotion_and_eviction_through_gate(lm, air):
    """Adapter versions ride the same canary gate as full swaps: probe
    runs UNDER the tenant's adapter, fleet load on pass, and eviction
    unloads fleet-wide."""
    from tpu_air import serve
    from tpu_air.serve import EngineDeployment, WeightsController
    from tpu_air.train import Checkpoint

    cfg, model, params = lm
    ckpt = Checkpoint.from_model(model_config=cfg, params=params)
    rng = np.random.RandomState(9)
    a = (rng.randn(cfg.d_model, 4) * 0.5).astype(np.float32)
    b = (rng.randn(4, cfg.vocab_size) * 0.5).astype(np.float32)
    probe_prompts = _prompts(seed=81, n=2)
    try:
        h = serve.run(
            EngineDeployment.options(
                name="lm-adpt", route_prefix="/adpt", num_replicas=2,
            ).bind(ckpt, EngineConfig(num_slots=2, slot_len=64,
                                      max_new_tokens=8, adapter_slots=2)),
            port=PORT,
        )
        root = tempfile.mkdtemp(prefix="wstore-")
        store = WeightStore(root)
        probe = compute_probe(model, params, probe_prompts, max_new=4,
                              adapter_a=a, adapter_b=b)
        va = store.publish_adapter("tenant-a", a, b, probe=probe)
        ctl = WeightsController(h, root, probe_prompts=probe_prompts,
                                probe_max_new=4, soak_s=0.1)
        out = ctl.promote(va)
        assert out["promoted"] and out["adapter"] == "tenant-a"
        # every replica serves the tenant: routed requests decode under
        # the adapter regardless of which replica they land on
        p = probe_prompts[0]
        want = offline_greedy(model, params, p, 4, adapter_a=a, adapter_b=b)
        for _ in range(4):
            status, body, _ = _post("/adpt", {
                "prompts": [p], "max_new_tokens": 4,
                "adapter_id": "tenant-a"})
            assert status == 200
            assert body["results"][0]["tokens"] == want
        # unknown tenant is a clean 400, not a 500
        status, body, _ = _post("/adpt", {"prompts": [p],
                                          "adapter_id": "ghost"})
        assert status == 400
        assert ctl.evict_adapter("tenant-a") == 2
        status, body, _ = _post("/adpt", {"prompts": [p],
                                          "adapter_id": "tenant-a"})
        assert status == 400  # evicted everywhere
    finally:
        serve.shutdown()
