"""Share of the HBM roofline the one-token state-space update reaches, in
percent: the float32 state of every slot and Mamba layer read once and
written once (``2 x costs_ssm.state_bytes(..., tail_el=0)``: the bf16
convolution tail is read and written under ``ssm_conv``, not under the
update's scope, and is left out) at the peak bandwidth, over the device
time, an execution of the program ``module``, of the operations traced
under ``scope`` (``benchmark/scopes.py``: the ``jax.named_scope`` path in the
capture's event metadata; by scope and not by kernel name, so it reads the
same work whatever implements it).  An execution's time is the union of the
intervals of its operations in the scope; the median over the executions that
lie whole inside the capture is taken.  Not this family's configuration, no
such program or no operation in the scope (an older tree): nothing to read.

NO METRIC NAMES THIS READER since PR 61: it prices every slot's state over
a MEDIAN time, where ``ssm_roofline`` (part ``state_update``) reads the rows
the captured steps advanced, mean over mean.
It stays because ``tests/test_benchmark_ssm.py`` (tier-1, not a benchmark
PR's to edit) pins it: a PR that may edit that file moves the test, and the
next benchmark PR deletes this file (PERF.md 7)."""

import re

from benchmark import costs_ssm, scopes, spans, stats, xplane
from benchmark.readers.scope_share import in_scope


def read(rc, scope, module):
    if rc.trace is None or rc.peak is None:
        return None
    if "num_slots" not in rc.facts or "mamba_d_state" not in rc.cfg:
        return None
    path = spans.newest_xplane()
    if path is None:
        return None
    planes = scopes.read(path)
    if not planes:
        return None
    plane = planes[min(planes)]
    program = plane.program_id(module)
    if program is None:
        return None
    pat = re.compile(scope)
    ops = sorted((s, e) for i, s, e in plane.of_program(program)
                 if in_scope(plane.parts(i), pat))
    if not ops:
        return None
    runs = sorted((s, e) for i, s, e in plane.modules
                  if plane.metadata[i]["name"].startswith(
                      f"jit_{module}({program})"))
    per_run, k = [], 0
    for start, end in runs:
        while k < len(ops) and ops[k][0] < start:
            k += 1
        inside = []
        while k < len(ops) and ops[k][0] < end:
            inside.append(ops[k])
            k += 1
        if inside:
            per_run.append(xplane.total(xplane.union(inside)))
    # the first and the last execution may be cut by the capture's edges
    whole = per_run[1:-1] or per_run
    if not whole:
        return None
    moved = 2 * costs_ssm.state_bytes(rc.cfg, rc.facts["num_slots"],
                                      tail_el=0)
    floor_s = moved / rc.peak["hbm_bytes_per_s"]
    return 100.0 * floor_s / stats.percentile(whole, 0.5)
