"""Share of the first device's operation time, in percent, that ran in a
part of the model: 100 x union of the intervals of the operations in the
scope / union of all operation intervals (of one program, where ``module``
names it: ``jit_<module>(<id>)`` on the capture's ``XLA Modules`` line, as
``module_hbm_share`` finds it).  Which part an operation belongs to is read
from the capture's event metadata (``benchmark/scopes.py``): its ``tf_op``,
the ``jax.named_scope`` / flax module path it was traced under, cut into
components.

* ``scope``: a regular expression searched against each component; an
  operation is in the scope when a component matches.
* ``under``: a second expression that some OTHER component must match
  (``decode_attention`` under ``cross_attn``).
* ``unscoped``: instead, the operations no word of the documented vocabulary
  and no flax module name covers (``scopes.covered``).

A fusion carries one ``tf_op``, its root's: where XLA fused across a scope
boundary the whole fusion counts for the root's scope.  No device plane, no
such program, no operation with a path, or (``scope``) no operation in the
scope, as in a program older than the scopes: nothing to read."""

import re

from benchmark import scopes, spans, xplane


def in_scope(parts, scope, under=None):
    return any(
        scope.search(c) and (under is None or any(
            under.search(d) for j, d in enumerate(parts) if j != i))
        for i, c in enumerate(parts))


def share(plane, scope=None, under=None, module=None, unscoped=False):
    """``read`` on one :class:`scopes.DevicePlane`."""
    program = None
    if module is not None:
        program = plane.program_id(module)
        if program is None:
            return None
    ops = plane.of_program(program)
    idents = {ident for ident, _, _ in ops}
    if not any(plane.metadata[i].get("tf_op") for i in idents):
        return None
    if unscoped:
        chosen = {i for i in idents if not scopes.covered(plane.parts(i))}
    else:
        scope, under = re.compile(scope), under and re.compile(under)
        chosen = {i for i in idents
                  if in_scope(plane.parts(i), scope, under)}
        if not chosen:
            return None
    return (100.0 * xplane.total(xplane.union(
        [(s, e) for i, s, e in ops if i in chosen]))
        / xplane.total(xplane.union([(s, e) for _, s, e in ops])))


def read(rc, scope=None, under=None, module=None, unscoped=False):
    if rc.trace is None:
        return None
    path = spans.newest_xplane()
    if path is None:
        return None
    planes = scopes.read(path)
    if not planes:
        return None
    return share(planes[min(planes)], scope, under, module, unscoped)
