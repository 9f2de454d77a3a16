#!/usr/bin/env python3
"""One run of one benchmark cell:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This file only records when the process started and puts the checkout on the
import path (for this process and the workers it will spawn); everything else
is ``benchmark/harness.py``.
"""

import os
import sys
import time

STARTED_AT = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    # `python benchmark/run.py` puts benchmark/ itself first on the path; the
    # package is imported from the checkout's root instead, here and in the
    # worker processes (which inherit PYTHONPATH)
    sys.path[0] = ROOT
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
                  if p and p != ROOT])
    if not os.path.isdir(os.path.join(ROOT, "tpu_air")):
        print("benchmark: no tpu_air/ beside benchmark/ — nothing to measure",
              file=sys.stderr)
        sys.exit(2)
    from benchmark import harness

    sys.exit(harness.main(sys.argv[1:], started_at=STARTED_AT))
