"""SLO-aware serve plane: priority admission, autoscaling, rollout.

Layers under test:
  * AdmissionPolicy / AdmissionController pure decision logic (class-aware
    queue/shed thresholds, token-budget clamping);
  * engine-level priority semantics: class-aware queue caps shed tail
    classes first, reserved interactive slots + priority scheduling keep
    interactive p99 TTFT flat under a synthetic batch flood (ISSUE
    acceptance: <= 1.2x unloaded, with a CPU-noise floor);
  * Autoscaler.decide / tick units against a fake handle + injected
    gauges (scale-up on queue depth and TTFT budget, timid scale-down);
  * DeploymentHandle least-loaded replica choice with round-robin
    fallback on stale gauges, and pin resolution;
  * zero-downtime rollout under live streaming load over the real HTTP
    proxy: zero lost streams, zero non-200 for admitted requests.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_air
from tpu_air.engine import (
    EngineConfig,
    EngineOverloadedError,
    InferenceEngine,
)
from tpu_air.engine.types import EngineDrainingError
from tpu_air.models.lm import CausalLM, LMConfig
from tpu_air.models.lm.generate import generate as lm_generate
from tpu_air.serve.admission import (
    AdmissionController,
    AdmissionPolicy,
    AdmissionShedError,
)
from tpu_air.serve.autoscaler import Autoscaler, AutoscalerConfig
from tpu_air.serve.deployment import DeploymentHandle, ReplicaGoneError

PORT = 8131


@pytest.fixture(scope="module")
def lm():
    cfg = LMConfig.tiny()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _prompts(seed, n, lo=3, hi=12, vocab=384):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(1, vocab, size=rng.randint(lo, hi))))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# admission controller: pure policy units
# ---------------------------------------------------------------------------


def _controller(**policy_kw):
    # the handle is only touched by gauge scrapes; passing explicit gauges
    # to decide() keeps these units handle-free
    return AdmissionController(object(), AdmissionPolicy(**policy_kw))


def test_admission_decide_class_thresholds():
    c = _controller(queue_soft=4.0, queue_high=12.0, queue_hard=32.0)

    def g(depth):
        return {"depth_per_replica": depth}

    # interactive admits at ANY depth this controller sees
    for depth in (0, 5, 15, 100):
        assert c.decide("interactive", g(depth)) == "admit"
    # best_effort degrades first: queue at soft, shed at high
    assert c.decide("best_effort", g(3)) == "admit"
    assert c.decide("best_effort", g(4)) == "queue"
    assert c.decide("best_effort", g(12)) == "shed"
    # batch holds on longer: queue at high, shed at hard
    assert c.decide("batch", g(11)) == "admit"
    assert c.decide("batch", g(12)) == "queue"
    assert c.decide("batch", g(32)) == "shed"
    with pytest.raises(ValueError):
        c.decide("platinum", g(0))


def test_admission_queue_times_out_to_shed():
    c = _controller(queue_soft=0.0, queue_high=100.0,
                    queue_timeout_s={"interactive": 0.0, "batch": 0.0,
                                     "best_effort": 0.1},
                    queue_poll_s=0.02, retry_after_s=7.0)
    # pin the scraped gauges at a depth that queues best_effort forever
    c._gauges = {"depth_per_replica": 50.0}
    c._gauges_at = time.monotonic() + 3600.0
    t0 = time.monotonic()
    with pytest.raises(AdmissionShedError) as ei:
        c.admit("best_effort")
    assert time.monotonic() - t0 >= 0.1  # waited its class timeout first
    assert ei.value.retry_after_s == 7.0
    assert c.queued["best_effort"] == 1 and c.shed["best_effort"] == 1


def test_admission_token_budget_clamp_by_class():
    p = AdmissionPolicy(token_budgets={"interactive": 256, "batch": 1024,
                                       "best_effort": 512})
    assert p.clamp_budget("best_effort", 4096) == 512
    assert p.clamp_budget("interactive", 64) == 64
    # an INTERACTIVE unset ask stays unset — the engine config's own
    # default governs (it is sized to the engine's slots; inventing a
    # budget here can exceed them).  The TAIL classes get the class
    # budget applied even to unset asks: a batch flood that omits
    # max_new_tokens must not default to the engine max
    assert p.clamp_budget("interactive", None) is None
    assert p.clamp_budget("batch", None) == 1024
    assert p.clamp_budget("best_effort", None) == 512


# ---------------------------------------------------------------------------
# engine-level priority semantics (manual stepping: deterministic)
# ---------------------------------------------------------------------------


def test_class_queue_caps_shed_tail_first(lm):
    cfg, model, params = lm
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=1, slot_len=64, max_new_tokens=4, max_queue=4),
        auto_start=False,
    )
    prompts = _prompts(seed=3, n=12)
    # best_effort cap = int(4 * 0.5) = 2: the third sheds while batch
    # (cap 3) and interactive (cap 4) still admit
    engine.submit(prompts[0], priority="best_effort")
    engine.submit(prompts[1], priority="best_effort")
    with pytest.raises(EngineOverloadedError):
        engine.submit(prompts[2], priority="best_effort")
    engine.submit(prompts[3], priority="batch")
    with pytest.raises(EngineOverloadedError):
        engine.submit(prompts[4], priority="batch")
    engine.submit(prompts[5], priority="interactive")
    with pytest.raises(EngineOverloadedError):
        engine.submit(prompts[6], priority="interactive")
    snap = engine.metrics.snapshot()
    assert snap["priority"]["best_effort"]["shed"] == 1
    assert snap["priority"]["batch"]["shed"] == 1
    assert snap["priority"]["interactive"]["shed"] == 1
    # one step refreshes the per-class queue gauges AND shows strict
    # priority: the single slot goes to interactive, not the earlier
    # best_effort arrivals
    engine.step()
    by_class = engine.metrics.snapshot()["priority"]
    assert by_class["interactive"]["queue_depth"] == 0
    assert by_class["best_effort"]["queue_depth"] == 2


def test_drain_refuses_new_work_then_drains(lm):
    cfg, model, params = lm
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=4),
        auto_start=False,
    )
    s = engine.submit(_prompts(seed=4, n=1)[0])
    engine.drain()
    assert engine.draining and not engine.drained()
    with pytest.raises(EngineDrainingError):
        engine.submit(_prompts(seed=5, n=1)[0])
    while not engine.idle():
        engine.step()
    assert engine.drained()
    assert s.done and len(s.tokens_so_far()) > 0
    # drain is idempotent
    engine.drain()
    assert engine.drained()


def test_interactive_ttft_flat_under_batch_flood(lm):
    """The SLO acceptance gate: a batch flood deep enough to shed must not
    move interactive p99 TTFT past 1.2x its unloaded baseline (CPU-noise
    floor 50ms).  Also asserted structurally: steps-to-first-token stays
    bounded, which is the device-independent form of the same claim."""
    cfg, model, params = lm
    econf = EngineConfig(num_slots=4, slot_len=64, max_new_tokens=8,
                         max_queue=16, reserved_interactive_slots=1)
    prompts = _prompts(seed=7, n=40)

    def steps_to_first_token(engine, stream):
        n = 0
        while not stream.tokens_so_far():
            assert engine.step(), "engine went idle before first token"
            n += 1
            assert n < 50
        return n

    # unloaded baseline: interactive alone, one at a time
    engine = InferenceEngine(model, params, econf, auto_start=False)
    base_steps = []
    for p in prompts[:6]:
        s = engine.submit(p, priority="interactive")
        base_steps.append(steps_to_first_token(engine, s))
        while not engine.idle():
            engine.step()
    under = engine.metrics.snapshot()["priority"]["interactive"]["ttft_s"]

    # synthetic overload: flood batch to the queue cap (some shed), then
    # interactive arrivals must still reach a slot immediately
    engine2 = InferenceEngine(model, params, econf, auto_start=False)
    flood = 0
    for p in prompts[6:30]:
        try:
            engine2.submit(p, priority="batch")
            flood += 1
        except EngineOverloadedError:
            break
    assert flood >= 10  # the flood really is deeper than the slot pool
    engine2.step()  # let batch occupy its (non-reserved) slots
    over_steps = []
    for p in prompts[30:36]:
        s = engine2.submit(p, priority="interactive")
        over_steps.append(steps_to_first_token(engine2, s))
    while not engine2.idle():
        engine2.step()
    over = engine2.metrics.snapshot()["priority"]["interactive"]["ttft_s"]

    # structural: first token within a bounded number of steps even with a
    # deep batch backlog.  The reserved slot + strict-priority admission
    # bound the delay by the IN-FLIGHT prefill backlog (at most one chunk
    # per already-admitted non-reserved slot, prefill_chunks_per_step=1),
    # NOT by the flooded queue depth — without the reservation, interactive
    # would wait for a batch slot to decode its full budget and retire.
    # One iteration more: the engine reads a decode step one iteration after
    # it issued it, so a slot whose stream ended is free one iteration later.
    chunk_backlog = econf.num_slots - econf.reserved_interactive_slots
    assert max(over_steps) <= max(base_steps) + chunk_backlog + 1, (
        base_steps, over_steps)
    # the acceptance criterion as written, wall-clock with CPU-noise floor
    floor = 0.05
    assert max(over["p99"], floor) <= 1.2 * max(under["p99"], floor), (
        under, over)
    # and nothing interactive was shed on the way
    snap = engine2.metrics.snapshot()["priority"]
    assert snap["interactive"]["shed"] == 0
    assert snap["batch"]["shed"] >= 1


# ---------------------------------------------------------------------------
# autoscaler units (fake handle + injected gauges)
# ---------------------------------------------------------------------------


class _FakeHandle:
    deployment_name = "fake"

    def __init__(self, replicas=1):
        self.replicas = replicas
        self.ups = 0
        self.downs = 0

    def num_replicas(self):
        return self.replicas

    def scale_up(self, timeout=120.0):
        self.replicas += 1
        self.ups += 1
        return True

    def scale_down(self, timeout=120.0):
        if self.replicas <= 1:
            return False
        self.replicas -= 1
        self.downs += 1
        return True

    def engine_stats(self, timeout=10.0):
        return {}


def _snap(depth=0, occupancy=0, i_p99=None):
    s = {"queue_depth": depth, "slot_occupancy": occupancy}
    if i_p99 is not None:
        s["priority"] = {"interactive": {
            "ttft_s": {"count": 8, "p50": i_p99 / 2, "p99": i_p99}}}
    return s


def test_autoscaler_decide_signals():
    a = Autoscaler(_FakeHandle(), AutoscalerConfig(
        min_replicas=1, max_replicas=4, scale_up_queue_depth=8.0,
        ttft_budget_s=0.5))
    # queue pressure is per live replica
    assert a.decide({"r0": _snap(depth=8)}, replicas=1) == "up"
    assert a.decide({"r0": _snap(depth=8)}, replicas=2) == "hold"
    assert a.decide({"r0": _snap(depth=8), "r1": _snap(depth=8)},
                    replicas=2) == "up"
    # TTFT budget trips even with shallow queues
    assert a.decide({"r0": _snap(i_p99=0.9)}, replicas=1) == "up"
    assert a.decide({"r0": _snap(i_p99=0.1)}, replicas=1) == "hold"
    # idle above min looks like "down"; at max, no more ups
    assert a.decide({"r0": _snap()}, replicas=2) == "down"
    assert a.decide({"r0": _snap()}, replicas=1) == "hold"
    assert a.decide({"r0": _snap(depth=100)}, replicas=4) == "hold"
    # below min always comes back up
    assert a.decide({}, replicas=0) == "up"


def test_autoscaler_tick_idle_streak_and_cooldown():
    h = _FakeHandle(replicas=2)
    gauges = {"value": {"r0": _snap(depth=20)}}
    a = Autoscaler(h, AutoscalerConfig(
        min_replicas=1, max_replicas=3, scale_up_queue_depth=8.0,
        scale_down_idle_ticks=3, cooldown_s=0.0),
        gauge_source=lambda: gauges["value"])
    assert a.tick() == "up" and h.replicas == 3
    # idle ticks must run the FULL streak before a scale-down
    gauges["value"] = {"r0": _snap()}
    assert a.tick() == "hold"
    assert a.tick() == "hold"
    assert a.tick() == "down" and h.replicas == 2
    # a non-idle tick resets the streak
    assert a.tick() == "hold"
    gauges["value"] = {"r0": _snap(depth=1)}
    assert a.tick() == "hold"
    gauges["value"] = {"r0": _snap()}
    assert a.tick() == "hold"  # streak restarted at 1, not 2


def test_autoscaler_cooldown_spaces_actions():
    h = _FakeHandle(replicas=1)
    a = Autoscaler(h, AutoscalerConfig(
        min_replicas=1, max_replicas=4, scale_up_queue_depth=1.0,
        cooldown_s=30.0),
        gauge_source=lambda: {"r0": _snap(depth=50)})
    assert a.tick() == "up" and h.replicas == 2
    # pressure persists but the cooldown holds the next action
    assert a.tick() == "hold" and h.replicas == 2
    assert a.stats()["scale_ups"] == 1


def test_autoscaler_config_validation():
    with pytest.raises(ValueError):
        Autoscaler(_FakeHandle(), AutoscalerConfig(min_replicas=0))
    with pytest.raises(ValueError):
        Autoscaler(_FakeHandle(),
                   AutoscalerConfig(min_replicas=3, max_replicas=2))


# ---------------------------------------------------------------------------
# least-loaded replica choice (handle unit, no actors)
# ---------------------------------------------------------------------------


class _Rep:
    def __init__(self, actor_id):
        self._actor_id = actor_id


def _bare_handle(replicas, loads=None, fresh=True, inflight=None):
    h = object.__new__(DeploymentHandle)
    h.deployment_name = "unit"
    h._replicas = list(replicas)
    h._draining = []
    h._rr = 0
    h._lock = threading.Lock()
    h._inflight = dict(inflight or {})
    h._loads = dict(loads or {})
    h._loads_at = time.monotonic() if fresh else 0.0
    h._loads_ttl = 3.0
    return h


def test_next_replica_least_loaded_with_fresh_gauges():
    a, b, c = _Rep("a"), _Rep("b"), _Rep("c")
    h = _bare_handle([a, b, c], loads={"a": 5.0, "b": 0.0, "c": 2.0})
    assert h._next_replica() is b
    # the handle's own in-flight calls count on top of scraped load
    h._inflight["b"] = 3
    assert h._next_replica() is c


def test_next_replica_round_robin_on_stale_gauges():
    a, b = _Rep("a"), _Rep("b")
    h = _bare_handle([a, b], loads={"a": 5.0, "b": 0.0}, fresh=False)
    picks = [h._next_replica() for _ in range(4)]
    assert picks == [b, a, b, a]  # load signal ignored: alternates


def test_next_replica_pin_reaches_draining_and_raises_when_gone():
    a, b = _Rep("a"), _Rep("b")
    h = _bare_handle([a], loads={})
    h._draining = [b]
    assert h._next_replica(pin="b") is b  # out of rotation, still pinned
    with pytest.raises(ReplicaGoneError):
        h._next_replica(pin="zz")


# ---------------------------------------------------------------------------
# rollout under live streaming load (real proxy, real replicas)
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

    def __init__(self, prompt, max_new):
        super().__init__(daemon=True)
        self.prompt = prompt
        self.max_new = max_new
        self.admitted = threading.Event()
        self.tokens = None
        self.bad_status = []

    def run(self):
        status, out, hdrs = _post("/roll", {
            "action": "submit", "prompt": self.prompt,
            "max_new_tokens": self.max_new,
        })
        if status != 200:
            self.bad_status.append(("submit", status, out))
            return
        self.admitted.set()
        rid = out["request_id"]
        pin = {"x-tpu-air-replica": hdrs.get("x-tpu-air-replica", "")}
        cursor, toks = 0, []
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            status, out, _ = _post("/roll", {
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


@pytest.mark.slow
def test_rollout_under_load_loses_zero_streams(lm, air):
    from tpu_air import serve
    from tpu_air.serve import EngineDeployment
    from tpu_air.train import Checkpoint

    cfg, model, params = lm
    ckpt = Checkpoint.from_model(model_config=cfg, params=params)
    prompts = _prompts(seed=21, n=6)
    max_new = 48  # long enough that streams straddle the rollout
    try:
        handle = serve.run(
            EngineDeployment.options(
                name="lm-roll", route_prefix="/roll", num_replicas=2,
            ).bind(ckpt, EngineConfig(num_slots=4, slot_len=64,
                                      max_new_tokens=max_new)),
            port=PORT,
        )
        with handle._lock:
            old_ids = {r._actor_id for r in handle._replicas}

        clients = [_StreamClient(p, max_new) for p in prompts]
        for c in clients:
            c.start()
        for c in clients:
            assert c.admitted.wait(timeout=120.0), c.bad_status
        # all streams admitted and mid-flight: swap every replica
        swapped = serve.rollout("/roll", timeout=120.0)
        assert swapped == 2
        for c in clients:
            c.join(timeout=180.0)
            assert not c.is_alive()

        # zero lost streams, zero non-200 for admitted requests, and every
        # stream token-identical to offline greedy (nothing truncated)
        for c, p in zip(clients, prompts):
            assert c.bad_status == []
            want = np.asarray(lm_generate(
                model, params, [p], max_new_tokens=max_new,
                eos_token_id=None))[0].tolist()
            assert c.tokens == want

        # the rotation is entirely fresh replicas, old ones fully retired
        with handle._lock:
            new_ids = {r._actor_id for r in handle._replicas}
            assert len(handle._draining) == 0
        assert new_ids and new_ids.isdisjoint(old_ids)
        # and the fresh replicas serve: a blocking generate round-trips
        status, out, _ = _post("/roll", {"prompt": prompts[0],
                                         "max_new_tokens": 4})
        assert status == 200 and len(out["results"]) == 1
    finally:
        serve.shutdown()
