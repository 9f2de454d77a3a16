"""Serve-layer tests — W8 online serving (Introduction_to_Ray_AI_Runtime
.ipynb:cc-70-79): deployments, replica load-balancing, HTTP proxy + JSON
adapter, PredictorDeployment over a Checkpoint."""

import json
import urllib.request

import numpy as np
import pandas as pd
import pytest

from tpu_air import serve
from tpu_air.serve import PredictorDeployment, pandas_read_json

PORT = 8123


def _post(path, payload, port=PORT):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, json.loads(resp.read())


@pytest.fixture(autouse=True)
def _teardown(air):
    yield
    serve.shutdown()


def test_deployment_options_and_bind(air):
    @serve.deployment
    class Echo:
        def __call__(self, payload):
            return payload

    d = Echo.options(name="echo", num_replicas=3, route_prefix="/echo")
    assert d.name == "echo" and d.num_replicas == 3
    app = d.bind()
    assert app.deployment.route_prefix == "/echo"


def test_http_round_trip_json(air):
    @serve.deployment
    class Doubler:
        def __call__(self, payload):
            return {"doubled": [2 * x for x in payload["values"]]}

    serve.run(
        Doubler.options(name="doubler", num_replicas=2, route_prefix="/double").bind(),
        port=PORT,
    )
    status, out = _post("/double", {"values": [1, 2, 3]})
    assert status == 200
    assert out == {"doubled": [2, 4, 6]}


def test_routes_and_404(air):
    @serve.deployment
    class Ok:
        def __call__(self, payload):
            return "ok"

    serve.run(Ok.options(name="ok", route_prefix="/ok").bind(), port=PORT)
    status, routes = _post("/-/routes", {})
    assert status == 200 and "/ok" in routes
    try:
        status, _ = _post("/nope", {})
    except urllib.error.HTTPError as e:
        status = e.code
    assert status == 404


def test_replica_load_balancing(air):
    import os

    @serve.deployment
    class WhoAmI:
        def __init__(self):
            self.pid = os.getpid()

        def __call__(self, payload):
            return {"pid": self.pid}

    h = serve.run(
        WhoAmI.options(name="who", num_replicas=2, route_prefix="/who").bind(),
        port=PORT,
    )
    assert h.num_replicas() == 2
    pids = {_post("/who", {})[1]["pid"] for _ in range(6)}
    assert len(pids) == 2  # round-robin reaches both replicas


def _kill_replica_process(replica):
    """Simulate a crash: SIGKILL the replica actor's worker process."""
    from tpu_air.core import runtime as rt_mod

    rt = rt_mod.get_runtime()
    with rt.lock:
        st = rt.actors[replica._actor_id]
        proc = st.worker.proc
    proc.kill()
    proc.join(timeout=10)


def test_replica_crash_failover_and_restart(air):
    """Requests keep succeeding after one replica dies
    mid-traffic; the controller respawns it back to num_replicas."""
    import os
    import time

    @serve.deployment
    class WhoAmI:
        def __init__(self):
            self.pid = os.getpid()

        def __call__(self, payload):
            return {"pid": self.pid}

    h = serve.run(
        WhoAmI.options(name="who2", num_replicas=2, route_prefix="/who2").bind(),
        port=PORT,
    )
    assert _post("/who2", {})[0] == 200
    _kill_replica_process(h._replicas[0])
    # mid-traffic: every request must still succeed (failover to the live
    # replica, or transparently to the respawned one)
    for _ in range(6):
        status, out = _post("/who2", {})
        assert status == 200 and "pid" in out
    # the restart controller brings the group back to size
    deadline = time.time() + 30
    while time.time() < deadline and h.live_replicas() < 2:
        time.sleep(0.2)
    assert h.live_replicas() == 2, "dead replica was not respawned"
    pids = {_post("/who2", {})[1]["pid"] for _ in range(8)}
    assert len(pids) == 2  # both (incl. the new) replicas serve


def test_all_replicas_dead_gives_503(air):
    """With restarts disabled, a fully-dead deployment returns 503 (not a
    hang, not a 500) and /-/healthz reports degraded."""
    @serve.deployment
    class Solo:
        def __call__(self, payload):
            return "ok"

    h = serve.run(
        Solo.options(
            name="solo", num_replicas=1, route_prefix="/solo", max_restarts=0
        ).bind(),
        port=PORT,
    )
    assert _post("/solo", {})[0] == 200
    _kill_replica_process(h._replicas[0])
    # healthz FIRST: liveness must be observable without routing a request
    # through the dead replica (load balancers poll health, not traffic)
    try:
        status, health = _post("/-/healthz", {})
    except urllib.error.HTTPError as e:
        status, health = e.code, json.loads(e.read())
    assert status == 503 and health["status"] == "degraded"
    assert health["deployments"]["/solo"]["live_replicas"] == 0
    try:
        status, out = _post("/solo", {})
    except urllib.error.HTTPError as e:
        status, out = e.code, json.loads(e.read())
    assert status == 503, out


def test_application_errors_are_500_not_failover(air):
    """An exception raised by the deployment's own code must surface as 500
    — never mark the replica dead or burn restart budget."""
    @serve.deployment
    class Flaky:
        def __call__(self, payload):
            raise ValueError("bad payload")

    h = serve.run(
        Flaky.options(name="flaky", num_replicas=1, route_prefix="/flaky").bind(),
        port=PORT,
    )
    for _ in range(3):
        try:
            status, out = _post("/flaky", {})
        except urllib.error.HTTPError as e:
            status, out = e.code, json.loads(e.read())
        assert status == 500 and "ValueError" in out["error"]
    assert h.num_replicas() == 1  # still in rotation


def test_predictor_deployment_over_checkpoint(air):
    """serve.run(PredictorDeployment...bind(PredictorCls, ckpt,
    http_adapter=pandas_read_json)) — the cc-71 call shape."""
    from tpu_air.predict import Predictor
    from tpu_air.train import Checkpoint

    class LinearPredictor(Predictor):
        def __init__(self, w, b, preprocessor=None):
            super().__init__(preprocessor)
            self.w, self.b = w, b

        @classmethod
        def from_checkpoint(cls, checkpoint, **kw):
            d = checkpoint.to_dict()
            return cls(d["w"], d["b"], preprocessor=checkpoint.get_preprocessor())

        def _predict_pandas(self, df: pd.DataFrame, **kw) -> pd.DataFrame:
            x = df[["x"]].to_numpy(dtype=float)
            return pd.DataFrame({"predictions": (x * self.w + self.b).ravel()})

    ckpt = Checkpoint.from_dict({"w": 2.0, "b": 1.0})
    serve.run(
        PredictorDeployment.options(
            name="LinearService", num_replicas=2, route_prefix="/linear"
        ).bind(LinearPredictor, ckpt, http_adapter=pandas_read_json),
        port=PORT,
    )
    status, out = _post("/linear", [{"x": 1.0}, {"x": 3.0}])
    assert status == 200
    assert [r["predictions"] for r in out] == [3.0, 7.0]
    st = serve.status()
    assert st["deployments"]["/linear"]["num_replicas"] == 2


def test_serve_lm_generative_checkpoint(air):
    """An LMTrainer-style checkpoint serves generation over HTTP through
    PredictorDeployment — the W8 serve arc on the LM family."""
    import jax
    import jax.numpy as jnp

    from tpu_air.models.lm import CausalLM, LMConfig
    from tpu_air.predict import LMGenerativePredictor
    from tpu_air.train import Checkpoint

    cfg = LMConfig.tiny()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    ckpt = Checkpoint.from_model(model_config=cfg, params=params)

    serve.run(
        PredictorDeployment.options(
            name="LMService", num_replicas=1, route_prefix="/lm"
        ).bind(LMGenerativePredictor, ckpt,
               predict_kwargs={"max_new_tokens": 4}),
        port=PORT,
    )
    status, out = _post("/lm", [{"input_ids": [5, 6, 7, 8]},
                                {"input_ids": [9, 10, 11, 12]}])
    assert status == 200, out
    assert len(out) == 2 and all(r["generated_output"] for r in out)
