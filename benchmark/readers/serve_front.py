"""What the serve plane adds in front of the engine, in ms: the client's
median time to first token less the engine's own (submit to first token
inside the replica) — proxy, admission, the actor hop and the poll."""

from benchmark import stats


def read(rc):
    client = stats.percentile(rc.facts.get("client_ttft_ms") or [], 0.5)
    engine = rc.facts.get("engine_ttft_ms_p50")
    if client is None or engine is None:
        return None
    return client - engine
