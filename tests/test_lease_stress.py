"""Cross-host lease scheduler under stress.

Asymmetric/fragmented lease shapes on a 4-host x 2-chip virtual cluster:
requests that don't tile the free topology, queueing under contention, a
shape-blocked queue head that must not stall satisfiable requests behind
it, and host-agent / worker-process death mid-lease (the lease must
release and waiters must not hang).  docs/MULTIHOST.md §2;
tpu_air/core/runtime.py `_claim_chips` / `_claim_queued_actors`.
"""

import os
import subprocess
import sys
import threading
import time

import pytest

import tpu_air

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def air4x2():
    """8 chips as a 4-host x 2-chip virtual cluster."""
    if tpu_air.is_initialized():  # a prior test's auto-init would shadow
        tpu_air.shutdown()        # the topology env this fixture sets
    os.environ["TPU_AIR_CHIPS_PER_HOST"] = "2"
    try:
        tpu_air.init(num_cpus=10, num_chips=8)
        yield tpu_air
    finally:
        tpu_air.shutdown()
        os.environ.pop("TPU_AIR_CHIPS_PER_HOST", None)


def _bare_runtime(num_chips, chips_per_host, free=None):
    """Shape/queue logic only — no processes (test_core.py pattern)."""
    from tpu_air.core.runtime import Runtime

    rt = Runtime.__new__(Runtime)
    rt.num_chips = num_chips
    rt.chips_per_host = chips_per_host
    rt.free_chips = list(range(num_chips)) if free is None else list(free)
    rt.avail = {"cpu": 100.0, "chip": float(len(rt.free_chips))}
    rt.lock = threading.RLock()
    rt.actor_queue = []
    rt._to_spawn = []
    rt._placement_event = threading.Event()
    return rt


def _rec(name, nchips):
    return {
        "actor_id": name,
        "ready_id": f"{name}-ready",
        "payload": None,
        "payload_ref": None,
        "resources": {"chip": float(nchips), "cpu": 0.0},
        "name": name,
    }


def test_shape_blocked_head_does_not_stall_queue():
    """4 free chips as 1+1+2 across hosts cannot serve a 4-chip lease
    (whole-host spans) — but requests queued BEHIND that head which don't
    touch its reserved hosts must still place (ADVICE r4: fragmentation
    must not stall unrelated work)."""
    # hosts: 0 -> {1 free}, 1 -> {3 free}, 2 -> busy, 3 -> {6, 7 free}
    rt = _bare_runtime(8, 2, free=[1, 3, 6, 7])
    rt.actor_queue = [_rec("big", 4), _rec("small", 2), _rec("one", 1)]
    rt._claim_queued_actors()
    spawned = [rec["name"] for rec, _ in rt._to_spawn]
    # big reserves whole host3; small (2 co-located) is then blocked too
    # and reserves host0; one places on the remaining fragment (host1)
    assert spawned == ["one"], spawned
    assert [r["name"] for r in rt.actor_queue] == ["big", "small"]
    one_ids = dict((rec["name"], ids) for rec, ids in rt._to_spawn)["one"]
    assert one_ids == [3], one_ids

    # chips recombine into a feasible shape (fragment holders and "one"
    # release): the skipped head claims FIRST, then small takes host3
    # (which the reservation protected from "one")
    rt.free_chips.extend([0, 2] + one_ids)  # hosts 0 and 1 now whole
    rt.avail["chip"] += 2.0 + len(one_ids)
    rt._to_spawn.clear()
    rt._claim_queued_actors()
    spawned = [rec["name"] for rec, _ in rt._to_spawn]
    assert spawned == ["big", "small"], spawned
    by_name = dict((rec["name"], ids) for rec, ids in rt._to_spawn)
    assert sorted(by_name["big"]) == [0, 1, 2, 3]
    assert sorted(by_name["small"]) == [6, 7]
    assert rt.actor_queue == []


def test_reserved_hosts_cannot_be_nibbled_by_small_leases():
    """The code-review starvation scenario: a 4-chip span head with one
    whole host free must not lose that host to a 2-chip lease behind it —
    reservation keeps small leases off the head's recombination capacity,
    and the head claims the moment a second host drains."""
    # hosts: 0 -> whole {0,1}; 1 -> {3}; 2 -> {5}; 3 -> busy
    rt = _bare_runtime(8, 2, free=[0, 1, 3, 5])
    rt.actor_queue = [_rec("span", 4), _rec("pair", 2), _rec("uno", 1)]
    rt._claim_queued_actors()
    spawned = [rec["name"] for rec, _ in rt._to_spawn]
    # span reserves host0 (the whole one); pair is blocked off it and
    # reserves host1; uno places on host2's fragment
    assert spawned == ["uno"], spawned
    uno_ids = dict((rec["name"], ids) for rec, ids in rt._to_spawn)["uno"]
    assert uno_ids == [5], uno_ids
    assert [r["name"] for r in rt.actor_queue] == ["span", "pair"]

    # host1's busy chip drains -> host1 whole: span (FIFO head) must claim
    # hosts 0+1 before pair can touch either
    rt.free_chips.append(2)
    rt.avail["chip"] += 1.0
    rt._to_spawn.clear()
    rt._claim_queued_actors()
    spawned = [rec["name"] for rec, _ in rt._to_spawn]
    assert spawned == ["span"], spawned
    assert sorted(rt._to_spawn[0][1]) == [0, 1, 2, 3]
    # pair still queued (span took everything whole); uno's fragment host
    # remains the only free capacity
    assert [r["name"] for r in rt.actor_queue] == ["pair"]


def test_count_blocked_head_still_fifo_blocks():
    """A head whose chip COUNT doesn't fit blocks the queue (strict FIFO):
    big leases must not be starved by a stream of small ones."""
    rt = _bare_runtime(8, 2, free=[0, 1, 2, 3])
    rt.avail["chip"] = 4.0
    rt.actor_queue = [_rec("big", 6), _rec("small", 1)]
    rt._claim_queued_actors()
    assert rt._to_spawn == []
    assert [r["name"] for r in rt.actor_queue] == ["big", "small"]


def test_nontiling_requests_queue_and_complete_under_contention(air4x2):
    """Integration on the real actor path: fragment the 4x2 cluster, queue
    a shape-blocked whole-host-span lease plus requests behind it under
    contention.  Reservation semantics: a fragment-sized request jumps the
    blocked head (fragmentation must not stall unrelated work), but a
    whole-host request behind it WAITS — the head's reserved host cannot
    be nibbled (FIFO fairness).  Then free feasible shapes and verify
    everyone lands with a correctly-shaped lease."""
    rt = tpu_air.core.runtime.get_runtime()
    assert rt.chips_per_host == 2

    @tpu_air.remote(num_chips=1, num_cpus=0)
    class Holder:
        def chips(self):
            return os.environ["TPU_AIR_CHIP_IDS"]

    # 6 single-chip holders pack hosts (best-fit) leaving one whole host
    holders = [Holder.remote() for _ in range(6)]
    owned = [int(tpu_air.get(h.chips.remote())) for h in holders]
    by_host = {}
    for h, c in zip(holders, owned):
        by_host.setdefault(c // 2, []).append((h, c))
    full_hosts = sorted(h for h, v in by_host.items() if len(v) == 2)
    free_hosts = sorted(set(range(4)) - set(by_host))
    assert len(full_hosts) == 3 and len(free_hosts) == 1, (by_host.keys())

    # break up two of the full hosts -> free = 1 + 1 + 2 (asymmetric)
    frag_a, frag_b = full_hosts[0], full_hosts[1]
    tpu_air.kill(by_host[frag_a][0][0])
    tpu_air.kill(by_host[frag_b][0][0])

    @tpu_air.remote(num_chips=4, num_cpus=0)
    class Span:
        def chips(self):
            return os.environ["TPU_AIR_CHIP_IDS"]

    @tpu_air.remote(num_chips=2, num_cpus=0)
    class Pair:
        def chips(self):
            return os.environ["TPU_AIR_CHIP_IDS"]

    @tpu_air.remote(num_chips=1, num_cpus=0)
    class Uno:
        def chips(self):
            return os.environ["TPU_AIR_CHIP_IDS"]

    span = Span.remote()          # 4 chips = 2 whole hosts: shape-blocked,
    span_ref = span.chips.remote()  # reserves the one whole free host
    pair = Pair.remote()          # 2 chips co-located: must WAIT (the only
    pair_ref = pair.chips.remote()  # whole host is reserved for span)
    uno = Uno.remote()            # 1 chip: jumps both onto a fragment
    uno_chip = int(tpu_air.get(uno.chips.remote()))
    assert uno_chip // 2 in (frag_a, frag_b), uno_chip
    # span and pair are still queued (counts fit, shapes don't)
    time.sleep(0.3)
    assert span._actor_id in rt.pending_actors
    assert pair._actor_id in rt.pending_actors

    # free the two fragmented hosts' remaining holders: together with the
    # reserved whole host there are now 2+ whole free hosts -> span places
    tpu_air.kill(by_host[frag_a][1][0])
    tpu_air.kill(by_host[frag_b][1][0])
    span_chips = sorted(int(c) for c in tpu_air.get(span_ref).split(","))
    assert len(span_chips) == 4
    span_hosts = sorted({c // 2 for c in span_chips})
    assert len(span_hosts) == 2
    assert all(len([c for c in span_chips if c // 2 == h]) == 2
               for h in span_hosts)          # whole-host spans
    assert uno_chip not in span_chips        # uno's lease survived

    # drain the last packed host -> a whole host frees -> pair places
    for h, c in by_host.get(full_hosts[2], []):
        tpu_air.kill(h)
    pair_chips = sorted(int(c) for c in tpu_air.get(pair_ref).split(","))
    assert len(pair_chips) == 2
    assert len({c // 2 for c in pair_chips}) == 1  # co-located
    assert not set(pair_chips) & set(span_chips)

    tpu_air.kill(span)
    tpu_air.kill(pair)
    tpu_air.kill(uno)
    deadline = time.time() + 10
    while time.time() < deadline and rt.avail["chip"] != float(rt.num_chips):
        time.sleep(0.05)
    assert sorted(rt.free_chips) == list(range(8))


def test_worker_death_mid_lease_releases_and_unblocks_waiters(air4x2):
    """A worker process holding a cross-host lease dies outright (SIGKILL
    class): its chips must return and a queued same-shape waiter must place
    — not hang."""
    rt = tpu_air.core.runtime.get_runtime()

    @tpu_air.remote(num_chips=4, num_cpus=0)
    class Span:
        def ping(self):
            return "pong"

        def die(self):
            os._exit(37)

    a = Span.remote()
    b = Span.remote()
    assert tpu_air.get(a.ping.remote()) == "pong"
    assert tpu_air.get(b.ping.remote()) == "pong"
    c = Span.remote()  # queued: all 8 chips leased
    c_ref = c.ping.remote()
    with pytest.raises(tpu_air.TpuAirError):
        tpu_air.get(a.die.remote(), timeout=30)
    # the dead actor's lease must recycle into c's placement
    assert tpu_air.get(c_ref, timeout=30) == "pong"
    tpu_air.kill(b)
    tpu_air.kill(c)
    deadline = time.time() + 10
    while time.time() < deadline and rt.avail["chip"] != float(rt.num_chips):
        time.sleep(0.05)
    assert rt.avail["chip"] == float(rt.num_chips)
    assert sorted(rt.free_chips) == list(range(8))


def test_host_agent_death_mid_run_raises_not_hangs():
    """HostAgentServer.run with a dead agent must raise (EOF/broken pipe),
    never block forever — the trainer's finally-release then frees the
    lease (trainer.py _run_spmd_multihost)."""
    from tpu_air.parallel.distributed import HostAgentServer, agent_loop

    os.environ.setdefault("TPU_AIR_AUTHKEY", "cafe" * 8)
    server = HostAgentServer(3)
    host, port = server.address
    agents = []
    code = (
        "import os\n"
        "os.environ['TPU_AIR_AUTHKEY'] = %r\n"
        "from tpu_air.parallel.distributed import agent_loop\n"
        "agent_loop((%r, %d), int(os.environ['PID']))\n"
        % (os.environ["TPU_AIR_AUTHKEY"], host, port)
    )
    for pid in (1, 2):
        env = dict(os.environ, PID=str(pid))
        agents.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env, cwd=REPO,
        ))
    try:
        server.wait_for_agents(timeout=60)
        assert server.run(lambda: 7) == [7, 7, 7]

        # one agent dies mid-lease; the next broadcast must raise promptly
        def die_if_agent():
            if int(os.environ.get("PID", "0")) == 1:
                os._exit(41)
            return "ok"

        t0 = time.monotonic()
        with pytest.raises((RuntimeError, EOFError, OSError)):
            server.run(die_if_agent)
        assert time.monotonic() - t0 < 60
    finally:
        server.shutdown()
        for p in agents:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_spmd_lease_released_when_cluster_run_fails(air4x2, monkeypatch):
    """_run_spmd_multihost must release its chip lease when the leased run
    raises (infra failure path) — a waiter's lease_chips then succeeds."""
    from tpu_air.train.trainer import BaseTrainer
    from tpu_air.train.config import RunConfig, ScalingConfig

    rt = tpu_air.core.runtime.get_runtime()

    class T(BaseTrainer):
        def _training_fn(self):
            def fn(config):
                return None

            return fn

    tr = T.__new__(T)
    tr.scaling_config = ScalingConfig(num_workers=4)
    tr.run_config = RunConfig()

    def boom(*a, **k):
        raise RuntimeError("host agent died")

    monkeypatch.setattr(tr, "_run_spmd_leased", boom)
    with pytest.raises(RuntimeError, match="host agent died"):
        tr._run_spmd_multihost({}, "/tmp/unused", {}, object(), rt, None)
    assert rt.avail["chip"] == float(rt.num_chips)
    assert sorted(rt.free_chips) == list(range(rt.num_chips))


def test_driver_lease_honors_queue_reservations():
    """lease_chips (the driver/SPMD-trainer path) must not nibble hosts
    reserved for a shape-blocked queued actor request, nor outrace a
    feasible queue head (code-review r5): with a 4-chip span queued and
    one whole host free, a 2-chip driver lease gets nothing; once the
    span's shape exists, its chips stay reserved for the head and the
    driver claims only what's left over."""
    rt = _bare_runtime(8, 2, free=[0, 1, 3, 5])
    rt.actor_queue = [_rec("span", 4)]
    # hosts: 0 whole {0,1}; 1 -> {3}; 2 -> {5}; 3 busy.  span reserves
    # host0; the driver pair must NOT get it (fragments don't fit a pair)
    assert rt._claim_chips(2, frozenset(rt._queued_reservations())) is None
    # a 1-chip driver lease may take a fragment, never the reserved host
    one = rt._claim_chips(1, frozenset(rt._queued_reservations()))
    assert one is not None and one[0] in (3, 5), one
    rt.free_chips.extend(one)

    # host1 drains -> span's 2-host shape exists; the simulation claims it
    # for the head, so the driver STILL cannot take hosts 0/1
    rt.free_chips.append(2)
    rt.avail["chip"] += 1.0
    reserved = rt._queued_reservations()
    assert reserved == {0, 1}, reserved
    assert rt._claim_chips(2, frozenset(reserved)) is None
    # free list must be restored by the simulation
    assert sorted(rt.free_chips) == [0, 1, 2, 3, 5]
