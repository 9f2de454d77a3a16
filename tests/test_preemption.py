"""Preemption-tolerant serving + elastic train (PR 15).

Layers under test:
  * ChipLease revocation plumbing — notice delivery, late-callback
    immediacy, idempotence, expiry windows, pickling degrade;
  * the ``runtime.lease`` fault site's ``revoke``/``notice`` actions —
    no chip leak on cold revocation, deterministic schedules including
    the notice fields' JSON round-trip;
  * kv_transfer payload integrity — round-trip equality plus the typed
    :class:`KVTransferError` taxonomy (missing layer/half, truncation,
    page geometry, lossy dtype) with lossless widening accepted;
  * engine drain-and-migrate — preempt() sheds new submits but keeps the
    backlog queued; migrate_out()/submit_migrated() continues streams
    token-identically with ZERO re-run prefill chunks;
  * per-tenant quotas — in-flight caps shed with QuotaExceededError
    proxy-side and 429 + Retry-After over HTTP, released on completion;
  * journal cap eviction — done entries evicted first, forced live
    evictions counted (``journal_evicted_live``);
  * chaos (``-m chaos``): a lease revoked WITH notice mid-decode under
    live streaming load migrates live KV pages to the survivor (zero
    non-200 after admission, token-identical, zero re-prefill); a
    zero-notice revocation exercises the journal-replay fallback;
  * elastic train (subprocess): a revoked SPMD lease mid-trial shrinks
    the data-parallel width and resumes from the retained checkpoint
    without spending ``max_failures``.
"""

import json
import os
import pickle
import subprocess
import sys
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
from tpu_air.core.runtime import ChipLease, get_runtime
from tpu_air.engine import EngineConfig, InferenceEngine
from tpu_air.engine.types import EngineDrainingError
from tpu_air.faults import FaultPlan, FaultSpec, LeaseRevokedError
from tpu_air.models.lm import CausalLM, LMConfig
from tpu_air.models.lm.generate import generate as lm_generate

PORT = 8147
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def _offline(model, params, prompt, max_new):
    return np.asarray(lm_generate(
        model, params, [prompt], max_new_tokens=max_new,
        eos_token_id=None))[0].tolist()


# ---------------------------------------------------------------------------
# ChipLease: revocation plumbing
# ---------------------------------------------------------------------------


def test_lease_is_a_list_and_fires_callbacks():
    lease = ChipLease([0, 1])
    assert lease == [0, 1] and lease.chip_ids == [0, 1]
    assert not lease.revoking and lease.notice_s is None
    got = []
    lease.on_revoke(got.append)
    lease.deliver_notice(4.5)
    assert got == [4.5]
    assert lease.revoking and lease.notice_s == 4.5
    # a callback registered AFTER the notice fires immediately — no
    # lost-wakeup window between engine build and watcher registration
    late = []
    lease.on_revoke(late.append)
    assert late == [4.5]


def test_lease_notice_is_idempotent_and_expires():
    lease = ChipLease([3])
    lease.deliver_notice(0.05)
    lease.deliver_notice(9.0)  # second delivery must not extend the window
    assert lease.notice_s == 0.05
    assert lease.wait_expired(5.0) and lease.expired


def test_lease_zero_notice_expires_immediately():
    lease = ChipLease([3])
    assert not lease.expired
    lease.deliver_notice(0.0)
    assert lease.expired and lease.notice_s == 0.0


def test_lease_broken_callback_does_not_mask_notice():
    lease = ChipLease([1])
    lease.on_revoke(lambda n: (_ for _ in ()).throw(RuntimeError("boom")))
    got = []
    lease.on_revoke(got.append)
    lease.deliver_notice(1.0)
    assert got == [1.0]


def test_lease_pickles_down_to_chip_ids():
    # spmd closures ship leases to host agents: the revocation plumbing
    # (lock, timer, callbacks) must degrade to the plain id list
    out = pickle.loads(pickle.dumps(ChipLease([2, 5])))
    assert type(out) is list and out == [2, 5]


# ---------------------------------------------------------------------------
# runtime.lease fault site: revoke / notice actions
# ---------------------------------------------------------------------------


def test_notice_spec_validation_and_determinism():
    with pytest.raises(ValueError):
        FaultSpec("runtime.lease", "notice", notice_s=-1.0)
    a = FaultPlan.generate(seed=15, sites=["runtime.lease"])
    b = FaultPlan.generate(seed=15, sites=["runtime.lease"])
    assert a.to_json() == b.to_json()
    # the notice fields survive the env-var round-trip workers re-parse
    rt = FaultPlan.from_json(a.to_json())
    assert rt.to_json() == a.to_json()
    assert all(s.notice_s >= 0.0 for s in rt.specs)


def test_cold_revoke_does_not_leak_chips(air, _clean_faults):
    rt = get_runtime()
    faults.install(FaultPlan(seed=2, specs=[
        FaultSpec("runtime.lease", "revoke", at=1)]))
    with pytest.raises(LeaseRevokedError):
        rt.lease_chips(2, timeout=30.0)
    faults.clear()
    # the revoked claim was handed back: the same shape leases cleanly
    lease = rt.lease_chips(2, timeout=30.0)
    try:
        assert len(lease) == 2
    finally:
        rt.release_chips(lease)


def test_notice_action_grants_then_revokes_with_warning(air, _clean_faults):
    rt = get_runtime()
    faults.install(FaultPlan(seed=3, specs=[
        FaultSpec("runtime.lease", "notice", at=1, delay_s=0.05,
                  notice_s=30.0)]))
    lease = rt.lease_chips(1, timeout=30.0)
    try:
        got = []
        lease.on_revoke(got.append)
        deadline = time.monotonic() + 10.0
        while not lease.revoking and time.monotonic() < deadline:
            time.sleep(0.01)
        assert lease.revoking and got == [30.0]
        assert not lease.expired  # the 30s window is still open
    finally:
        faults.clear()
        rt.release_chips(lease)


# ---------------------------------------------------------------------------
# kv_transfer: payload integrity
# ---------------------------------------------------------------------------


def _toy_cache(pages=6, page_len=4, d=8, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)

    def leaf():
        return jnp.asarray(rng.randn(pages, page_len, d), dtype)

    return {"decoder": {
        "layers_0": {"cached_key": leaf(), "cached_value": leaf()},
        "layers_1": {"cached_key": leaf(), "cached_value": leaf()},
    }}


def test_kv_payload_roundtrip_and_error_taxonomy():
    from tpu_air.engine.dist.kv_transfer import (
        KVTransferError,
        extract_kv_pages,
        insert_kv_pages,
        payload_nbytes,
        payload_pages,
        validate_kv_payload,
    )

    src = _toy_cache(seed=1)
    payload = extract_kv_pages(src, [1, 3, 4])
    assert payload_pages(payload) == 3 and payload_nbytes(payload) > 0
    # round trip into DIFFERENT ids of a same-geometry destination pool
    dst = _toy_cache(seed=2)
    out = insert_kv_pages(dst, [0, 2, 5], payload)
    np.testing.assert_array_equal(
        np.asarray(out["decoder"]["layers_0"]["cached_key"])[[0, 2, 5]],
        payload["decoder/layers_0"]["k"])

    broken = {k: v for k, v in payload.items() if not k.endswith("layers_1")}
    with pytest.raises(KVTransferError, match="missing layer"):
        validate_kv_payload(dst, [0, 2, 5], broken)

    broken = dict(payload)
    broken["decoder/layers_1"] = {"k": payload["decoder/layers_1"]["k"]}
    with pytest.raises(KVTransferError, match="missing 'v'"):
        validate_kv_payload(dst, [0, 2, 5], broken)

    with pytest.raises(KVTransferError, match="truncated"):
        validate_kv_payload(dst, [0, 2, 5, 1], payload)  # 4 ids, 3 pages

    with pytest.raises(KVTransferError, match="page shape mismatch"):
        validate_kv_payload(_toy_cache(page_len=8), [0, 2, 5], payload)

    # narrowing float32 pages into a float16 pool is LOSSY: refused
    f16 = _toy_cache(dtype=jnp.float16, seed=3)
    with pytest.raises(KVTransferError, match="dtype mismatch"):
        validate_kv_payload(f16, [0, 2, 5], payload)
    # widening float16 pages into a float32 pool is lossless: accepted
    narrow = extract_kv_pages(f16, [1, 3, 4])
    validate_kv_payload(dst, [0, 2, 5], narrow)


# ---------------------------------------------------------------------------
# engine: preemption drain + live migration (manual stepping)
# ---------------------------------------------------------------------------


def test_engine_preempt_sheds_submits_keeps_backlog(lm):
    cfg, model, params = lm
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=1, slot_len=64, max_new_tokens=8),
        auto_start=False,
    )
    for p in _prompts(seed=5, n=3):
        engine.submit(p)
    engine.step()  # one admitted; two queued behind the single slot
    engine.preempt()
    assert engine.preempting
    with pytest.raises(EngineDrainingError):
        engine.submit([1, 2, 3])
    # unlike a rollout drain the backlog STAYS queued — prefilling it
    # would burn the notice window on work this replica cannot finish
    assert engine.scheduler.depth() == 2
    engine.close()


def test_migration_token_identical_with_zero_reprefill(lm):
    cfg, model, params = lm
    ecfg = EngineConfig(num_slots=2, slot_len=64, max_new_tokens=16,
                        page_len=8)
    src = InferenceEngine(model, params, ecfg, auto_start=False)
    dst = InferenceEngine(model, params, ecfg, auto_start=False)
    prompts = _prompts(seed=21, n=2)
    streams = [src.submit(p) for p in prompts]
    for _ in range(200):
        src.step()
        if all(len(s.tokens_so_far()) >= 4 for s in streams):
            break
    assert all(4 <= len(s.tokens_so_far()) < 16 for s in streams)

    payloads = src.migrate_out()
    assert src.preempting and len(payloads) == 2
    for pl in payloads:
        assert pl["streamed"] and pl["pages"]
        assert pl["pos"] == len(pl["prompt"]) + len(pl["streamed"]) - 1
    assert src.metrics.snapshot()["migrations"]["out"] == 2

    landed = [dst.submit_migrated(pl) for pl in payloads]
    steps = 0
    while not dst.idle():
        dst.step()
        steps += 1
        assert steps < 500, "destination failed to drain"
    for pl, s in zip(payloads, landed):
        assert s.result(5.0) == _offline(model, params, pl["prompt"], 16)
    mg = dst.metrics.snapshot()["migrations"]
    assert mg["in"] == 2 and mg["in_pages"] >= 2
    assert mg["in_reprefill_chunks"] == 0  # zero prefill re-run
    src.close()
    dst.close()


def test_submit_migrated_rejects_inconsistent_payloads(lm):
    from tpu_air.engine.types import RequestValidationError

    cfg, model, params = lm
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=1, slot_len=64, max_new_tokens=8,
                     page_len=8),
        auto_start=False,
    )
    with pytest.raises(RequestValidationError, match="inconsistent"):
        engine.submit_migrated({
            "request_id": 1, "prompt": [1, 2, 3], "streamed": [4],
            "pos": 9, "budget_left": 2, "priority": "interactive",
            "deadline_ms": None, "adapter_id": None, "pages": {},
        })
    engine.close()


# ---------------------------------------------------------------------------
# admission: per-tenant quotas (pure units, fake handle)
# ---------------------------------------------------------------------------


class _QuotaHandle:
    def __init__(self, replicas=1):
        self._n = replicas

    def num_replicas(self):
        return self._n

    def engine_stats(self, timeout=10.0):
        return {}


def test_tenant_quota_caps_inflight_and_releases():
    from tpu_air.serve.admission import (
        AdmissionController,
        AdmissionPolicy,
        QuotaExceededError,
    )

    c = AdmissionController(_QuotaHandle(), AdmissionPolicy(
        queue_hard=1.0, tenant_queue_shares={"t-a": 0.5},
        retry_after_s=3.0))
    c.admit("interactive", adapter_id="t-a")  # cap = max(1, .5*1*1) = 1
    with pytest.raises(QuotaExceededError) as ei:
        c.admit("interactive", adapter_id="t-a")
    assert ei.value.retry_after_s == 3.0 and ei.value.adapter_id == "t-a"
    # unmetered traffic is unaffected by the hot tenant
    c.admit("interactive")
    c.admit("interactive", adapter_id="t-other")
    # releasing the unit re-opens the share; release is idempotent-safe
    c.release("t-a")
    c.release("t-a")
    c.admit("interactive", adapter_id="t-a")
    st = c.stats()
    assert st["quota_shed"]["interactive"] == 1
    assert st["tenant_inflight"]["t-a"] == 1
    assert st["policy"]["tenant_queue_shares"] == {"t-a": 0.5}


def test_tenant_token_budget_min_composes():
    from tpu_air.serve.admission import AdmissionPolicy

    p = AdmissionPolicy(token_budgets={"interactive": 256},
                        tenant_token_budgets={"t-a": 64})
    assert p.clamp_budget("interactive", 4096, adapter_id="t-a") == 64
    assert p.clamp_budget("interactive", 32, adapter_id="t-a") == 32
    # unlike the class budget, a tenant budget also caps UNSET asks — a
    # metered tenant must not inherit the engine default
    assert p.clamp_budget("interactive", None, adapter_id="t-a") == 64
    assert p.clamp_budget("interactive", None) is None
    assert p.clamp_budget("interactive", 4096) == 256


# ---------------------------------------------------------------------------
# journal: cap eviction prefers finished entries
# ---------------------------------------------------------------------------


def test_journal_cap_eviction_prefers_done_counts_live():
    from tpu_air.serve.supervisor import RequestJournal

    def rec(j, rid):
        j.record_submit("/x", "r0", rid, prompt=[1, 2],
                        max_new_tokens=4, priority="interactive",
                        deadline_ms=None)

    j = RequestJournal(cap=2)
    rec(j, 1)
    rec(j, 2)
    j.record_progress(j.lookup("/x", "r0", 1), [7, 8, 9, 9], done=True)
    rec(j, 3)  # evicts the DONE entry 1, not live entry 2
    assert j.lookup("/x", "r0", 1) is None
    assert j.lookup("/x", "r0", 2) is not None
    assert j.lookup("/x", "r0", 3) is not None
    assert j.stats()["journal_evicted_live"] == 0
    rec(j, 4)  # every entry live: the forced eviction is COUNTED
    assert j.stats()["journal_evicted_live"] == 1
    assert j.lookup("/x", "r0", 2) is None  # oldest live went


# ---------------------------------------------------------------------------
# serve plane over HTTP
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


def _poll_to_done(path, rid, pin, timeout=120.0):
    cursor, toks = 0, []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        status, out, _ = _post(path, {
            "action": "poll", "request_id": rid, "cursor": cursor,
        }, headers=pin)
        assert status == 200, out
        got = out.get("tokens") or []
        toks += got
        cursor += len(got)
        if out.get("done"):
            return toks
        time.sleep(0.01)
    raise AssertionError("stream did not finish in time")


def test_http_tenant_quota_429_with_retry_after(lm, air):
    """One tenant at its queue share: the next submit is a 429 with
    Retry-After, base traffic still admits, and finishing the stream
    returns the unit.  The shed surfaces in the merged metrics as
    ``priority.<class>.quota_shed``."""
    from tpu_air import serve
    from tpu_air.engine.metrics import merge_snapshots, prometheus_lines
    from tpu_air.serve import EngineDeployment
    from tpu_air.serve.admission import AdmissionPolicy
    from tpu_air.serve.proxy import replica_engine_stats
    from tpu_air.train import Checkpoint

    cfg, model, params = lm
    ckpt = Checkpoint.from_model(model_config=cfg, params=params)
    rng = np.random.RandomState(9)
    a = (rng.randn(cfg.d_model, 4) * 0.5).astype(np.float32)
    b = (rng.randn(4, cfg.vocab_size) * 0.5).astype(np.float32)
    prompt = _prompts(seed=31, n=1)[0]
    try:
        h = serve.run(
            EngineDeployment.options(
                name="lm-quota", route_prefix="/quota", num_replicas=1,
            ).bind(ckpt, EngineConfig(num_slots=2, slot_len=64,
                                      max_new_tokens=24, adapter_slots=2)),
            port=PORT,
            admission_policy=AdmissionPolicy(
                queue_hard=1.0, tenant_queue_shares={"tenant-a": 0.2},
                retry_after_s=2.0),
        )
        for r in h._replicas:
            tpu_air.get(r.handle.remote("weights_load_adapter",
                                        ("tenant-a", a, b), {}))
        # in-flight 1/1 for tenant-a (the hold lives until its poller
        # observes done, so this is deterministic even if decode races)
        status, out1, hdrs1 = _post("/quota", {
            "action": "submit", "prompt": prompt, "max_new_tokens": 24,
            "adapter_id": "tenant-a"})
        assert status == 200, out1
        pin1 = {"x-tpu-air-replica": hdrs1.get("x-tpu-air-replica", "")}

        status, out, hdrs = _post("/quota", {
            "action": "submit", "prompt": prompt, "max_new_tokens": 4,
            "adapter_id": "tenant-a"})
        assert status == 429, out
        assert "QuotaExceededError" in out["error"]
        assert float(hdrs["Retry-After"]) == 2.0

        # base (unmetered) traffic rides through the hot tenant's shed
        status, out2, hdrs2 = _post("/quota", {
            "action": "submit", "prompt": prompt, "max_new_tokens": 4})
        assert status == 200, out2
        _poll_to_done("/quota", out2["request_id"],
                      {"x-tpu-air-replica":
                       hdrs2.get("x-tpu-air-replica", "")})

        # draining the tenant stream returns the unit: admit again
        _poll_to_done("/quota", out1["request_id"], pin1)
        status, out3, hdrs3 = _post("/quota", {
            "action": "submit", "prompt": prompt, "max_new_tokens": 4,
            "adapter_id": "tenant-a"})
        assert status == 200, out3
        _poll_to_done("/quota", out3["request_id"],
                      {"x-tpu-air-replica":
                       hdrs3.get("x-tpu-air-replica", "")})

        merged = merge_snapshots(replica_engine_stats())
        assert merged["priority"]["interactive"]["quota_shed"] >= 1
        fam = [ln for ln in prometheus_lines(replica_engine_stats())
               if "tpu_air_engine_priority_quota_shed" in ln]
        assert any(not ln.startswith("#") for ln in fam)
    finally:
        serve.shutdown()


class _FeedClient(threading.Thread):
    """One lane of continuous streaming load: submits a fresh stream as
    soon as the previous one finishes, until told to stop.  Pre-admission
    sheds (429/503 during a drain window) back off and retry — only a
    non-200 AFTER admission is a failure."""

    def __init__(self, path, prompts, max_new):
        super().__init__(daemon=True)
        self.path = path
        self.prompts = prompts
        self.max_new = max_new
        self.stop = threading.Event()
        self.finished = []  # (prompt, tokens) per completed stream
        self.bad = []

    def run(self):
        for prompt in self.prompts:
            if self.stop.is_set():
                return
            status, out, hdrs = _post(self.path, {
                "action": "submit", "prompt": prompt,
                "max_new_tokens": self.max_new})
            if status != 200:
                time.sleep(0.05)  # shed pre-admission: legal, try again
                continue
            rid = out["request_id"]
            pin = {"x-tpu-air-replica": hdrs.get("x-tpu-air-replica", "")}
            cursor, toks = 0, []
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                status, out, _ = _post(self.path, {
                    "action": "poll", "request_id": rid, "cursor": cursor,
                }, headers=pin)
                if status != 200:
                    self.bad.append((prompt, status, out))
                    return
                got = out.get("tokens") or []
                toks += got
                cursor += len(got)
                if out.get("done"):
                    self.finished.append((prompt, toks))
                    break
                time.sleep(0.01)


def _drive_until(clients, cond, timeout=150.0):
    """Run the feed clients until ``cond()`` is true, then stop them and
    let in-flight streams finish."""
    deadline = time.monotonic() + timeout
    ok = False
    while time.monotonic() < deadline:
        if cond():
            ok = True
            break
        if not any(c.is_alive() for c in clients):
            break
        time.sleep(0.25)
    for c in clients:
        c.stop.set()
    for c in clients:
        c.join(timeout=180.0)
        assert not c.is_alive()
    return ok


@pytest.mark.chaos
def test_lease_notice_migrates_live_streams_token_identical(
        lm, air, _clean_faults):
    """The tentpole acceptance: a seeded plan revokes one replica's chip
    lease WITH notice mid-decode under live streaming load.  The watcher
    migrates the live KV pages to the survivor: zero non-200 after
    admission, every finished stream token-identical to offline greedy,
    and zero prefill chunks re-run for the migrated slots."""
    from tpu_air import serve
    from tpu_air.engine.metrics import merge_snapshots
    from tpu_air.serve import EngineDeployment
    from tpu_air.serve.proxy import replica_engine_stats, serve_control_stats
    from tpu_air.train import Checkpoint

    cfg, model, params = lm
    ckpt = Checkpoint.from_model(model_config=cfg, params=params)
    # the notice timer arms at the chip-1 replica's engine build (its
    # attach consults the fault site keyed "chips=1") — per-process hit
    # counters make `match` the ONLY way to preempt one replica, not both
    plan = FaultSpec("runtime.lease", "notice", at=1, match="chips=1",
                     delay_s=1.5, notice_s=60.0)
    # seed pinned by the workflow matrix (TPU_AIR_FAULT_SEED) so a red CI
    # run replays locally with the identical schedule
    plan = FaultPlan(seed=int(os.environ.get("TPU_AIR_FAULT_SEED", "19")),
                     specs=[plan])
    assert plan.to_json() == FaultPlan.from_json(plan.to_json()).to_json()
    max_new = 48
    try:
        serve.run(
            EngineDeployment.options(
                name="lm-mig", route_prefix="/mig", num_replicas=2,
                num_chips=1,
            ).bind(ckpt, EngineConfig(num_slots=4, slot_len=96,
                                      max_new_tokens=max_new,
                                      page_len=16)),
            port=PORT,
            fault_plan=plan,
        )
        clients = [_FeedClient("/mig", _prompts(seed=40 + i, n=40),
                               max_new) for i in range(4)]
        for c in clients:
            c.start()

        def migrated():
            rec = serve_control_stats()["recovery"]
            return rec.get("migrations", 0) >= 1

        assert _drive_until(clients, migrated), (
            "no migration observed", serve_control_stats()["recovery"])

        for c in clients:
            assert c.bad == [], c.bad
            for prompt, toks in c.finished:
                assert toks == _offline(model, params, prompt, max_new)
        assert sum(len(c.finished) for c in clients) >= 4

        rec = serve_control_stats()["recovery"]
        assert rec["preemptions"] >= 1
        assert rec["migrations"] >= 1 and rec["migrated_pages"] >= 1
        merged = merge_snapshots(replica_engine_stats())
        mg = merged.get("migrations") or {}
        assert mg.get("in", 0) >= 1
        # ZERO re-prefill: migrated slots continue from their exact cursor
        assert mg.get("in_reprefill_chunks", 0) == 0
    finally:
        serve.shutdown()
        faults.clear()


@pytest.mark.chaos
def test_zero_notice_revocation_falls_back_to_replay(lm, air, _clean_faults):
    """A lease revoked with NO warning cannot migrate (the window is
    gone): the watcher counts the fallback and the journal replays the
    orphaned streams on the survivor — still zero non-200 after
    admission, still token-identical."""
    from tpu_air import serve
    from tpu_air.serve import EngineDeployment
    from tpu_air.serve.proxy import serve_control_stats
    from tpu_air.train import Checkpoint

    cfg, model, params = lm
    ckpt = Checkpoint.from_model(model_config=cfg, params=params)
    plan = FaultPlan(seed=int(os.environ.get("TPU_AIR_FAULT_SEED", "23")),
                     specs=[
        FaultSpec("runtime.lease", "notice", at=1, match="chips=1",
                  delay_s=1.5, notice_s=0.0)])
    max_new = 48
    try:
        serve.run(
            EngineDeployment.options(
                name="lm-fb", route_prefix="/fb", num_replicas=2,
                num_chips=1,
            ).bind(ckpt, EngineConfig(num_slots=4, slot_len=96,
                                      max_new_tokens=max_new,
                                      page_len=16)),
            port=PORT,
            fault_plan=plan,
        )
        clients = [_FeedClient("/fb", _prompts(seed=60 + i, n=40),
                               max_new) for i in range(4)]
        for c in clients:
            c.start()

        def fell_back():
            rec = serve_control_stats()["recovery"]
            return (rec.get("migration_fallbacks", 0) >= 1
                    and rec.get("replays", 0) >= 1)

        assert _drive_until(clients, fell_back), (
            "no replay fallback observed", serve_control_stats()["recovery"])

        for c in clients:
            assert c.bad == [], c.bad
            for prompt, toks in c.finished:
                assert toks == _offline(model, params, prompt, max_new)
        rec = serve_control_stats()["recovery"]
        assert rec["preemptions"] >= 1
        assert rec["migration_fallbacks"] >= 1
        assert rec["replays"] >= 1 and rec["replay_failures"] == 0
    finally:
        serve.shutdown()
        faults.clear()


# ---------------------------------------------------------------------------
# elastic train: revoked SPMD lease -> shrink + resume (subprocess)
# ---------------------------------------------------------------------------


def test_elastic_preemption_shrinks_and_resumes():
    """A 2-host x 4-chip virtual cluster; a seeded notice revokes the
    8-chip SPMD lease mid-trial.  The run must retain its newest
    checkpoint, halve the data-parallel width (landing on the single-
    actor path), and RESUME — with max_failures=0, proving the
    preemption budget is separate from the crash budget."""
    env = dict(os.environ)
    for k in ("TPU_AIR_COORDINATOR", "TPU_AIR_NUM_PROCESSES",
              "TPU_AIR_PROCESS_ID", "TPU_AIR_NUM_CHIPS",
              "TPU_AIR_CHIPS_PER_HOST", "TPU_AIR_FAULT_PLAN"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests",
                                      "_elastic_train_driver.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    )
    assert "ELASTIC-PREEMPT-OK" in proc.stdout
    assert "ELASTIC-TRAIN-OK" in proc.stdout
