"""Driver for tests/test_runtime_processes.py: a runtime of its own (the
test suite's session runtime cannot be shut down under the other tests).

``shutdown``: an actor starts a child process of its own; after
``tpu_air.shutdown()`` nothing below this driver is alive.  ``orphan``: an
actor that computes without ever returning to its message loop; the driver
prints the worker's pid (and its control-plane daemon's, and its store's
directory) and waits to be killed."""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tpu_air  # noqa: E402
from tpu_air.core.runtime import (  # noqa: E402
    _descendants, _proc_stat, get_runtime)


class Spawner:
    def spawn(self):
        child = subprocess.Popen([sys.executable, "-c",
                                  "import time; time.sleep(600)"])
        return {"worker": os.getpid(), "child": child.pid}

    def spin(self, seconds):
        # never back at conn.recv() while the driver dies
        end = time.monotonic() + seconds
        while time.monotonic() < end:
            sum(range(10000))
        return os.getpid()


def main(mode: str) -> int:
    from tpu_air.control import ensure_gcs_binary

    ensure_gcs_binary()     # built already: init() starts the daemon in line
    tpu_air.init(num_cpus=2)
    actor = tpu_air.remote(Spawner).remote()
    pids = tpu_air.get(actor.spawn.remote())
    if mode == "orphan":
        actor.spin.remote(600)
        rt = get_runtime()
        gcs = rt._gcs_proc.pid if rt._gcs_proc is not None else None
        print(json.dumps({**pids, "gcs": gcs, "store": rt.store_root}),
              flush=True)
        time.sleep(600)
        return 1
    assert _proc_stat(pids["child"]) is not None
    tpu_air.shutdown()
    left = [p for p, _ in _descendants(os.getpid())]
    alive = {k: (_proc_stat(p) or (0, "gone", ""))[1]
             for k, p in pids.items()}
    print(json.dumps({"left": left, "state": alive}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
