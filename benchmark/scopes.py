"""The capture's own account of each device operation: which part of the
model it belongs to, and what the compiler says it costs.

``jax.profiler.ProfileData`` hands out a plane's events and their stats, but
not the plane's *event metadata*, and that is where an ``.xplane.pb`` keeps
what the compiler knew of an operation: ``tf_op`` (the operation's
``op_name``: the ``jax.named_scope`` / flax module path it was traced
under), ``hlo_category``, ``flops``, ``bytes_accessed`` and the
``program_id`` of the program it is part of (the number in the ``XLA
Modules`` event ``jit_<name>(<id>)``).  This module reads them with a plain
protobuf wire reader (varint and length-delimited fields; nothing imported
that the benchmark does not import already):

* of every ``/device:TPU:<n>`` plane, the ``XLA Ops`` line's events as
  ``(metadata id, start, end)`` in seconds, the ``XLA Modules`` line's the
  same way, and the plane's event metadata by id.  Events are keyed by
  METADATA ID, not by name: ``fusion.6`` of the chunk program and
  ``fusion.6`` of the decode step are two operations.
* every other plane, and every other line of a device plane, is skipped by
  its length, never descended into.
* control-flow containers (``xplane.CONTAINER``) are left out of the
  operations, as ``xplane.reduce_profile`` leaves them out.

A scope path is normalised to its components (:func:`components`), and
:func:`covered` says whether a component names a part of the model.  A
fusion carries ONE ``tf_op``, its root's: where XLA fused across a scope
boundary the whole fusion counts for the root's scope.

To be folded into ``xplane.py`` by a ``benchmark`` issue (PERF.md §7).
"""

from __future__ import annotations

import re
import struct
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

from .xplane import CONTAINER, DEVICE_PLANE, OPS_LINE, op_name

MODULES_LINE = "XLA Modules"
# the stats of an operation's metadata that are kept, under these names
KEPT = ("tf_op", "hlo_category", "program_id", "flops", "bytes_accessed")

# -- the wire format ----------------------------------------------------------
# tensorflow/tsl/profiler/protobuf/xplane.proto, by field number:
#   XSpace         1 planes*
#   XPlane         2 name, 3 lines*, 4 event_metadata (map), 5 stat_metadata (map)
#   XLine          2 name, 3 timestamp_ns, 4 events*
#   XEvent         1 metadata_id, 2 offset_ps, 3 duration_ps
#   XEventMetadata 1 id, 2 name, 5 stats*
#   XStatMetadata  1 id, 2 name
#   XStat          1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str, 6 bytes,
#                  7 ref (the id of a stat metadata whose name is the value)
#   a map entry    1 key, 2 value


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes, start: int = 0,
            end: Optional[int] = None) -> Iterator[Tuple[int, int, Any]]:
    """``(field number, wire type, value)`` of one message: a varint's value,
    a fixed field's bytes, or the ``(start, end)`` of a length-delimited
    field inside ``buf`` (so skipping one costs nothing)."""
    end = len(buf) if end is None else end
    pos = start
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif wire == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield number, wire, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span: Tuple[int, int]) -> Tuple[int, Tuple[int, int]]:
    key, value = 0, (0, 0)
    for number, _, v in _fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(buf: bytes, span: Tuple[int, int],
          names: Dict[int, str]) -> Tuple[Optional[str], Any]:
    """``(the stat's name, its value)``; a reference is the name it points to."""
    ident, value = 0, None
    for number, _, v in _fields(buf, *span):
        if number == 1:
            ident = v
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = v - (1 << 64) if v >= 1 << 63 else v
        elif number in (5, 6):
            value = _text(buf, v)
        elif number == 7:
            value = names.get(v, "")
    return names.get(ident), value


# -- what is read ---------------------------------------------------------------

Event = Tuple[int, float, float]  # metadata id, start, end (seconds)


@dataclass
class DevicePlane:
    """One ``/device:TPU:<n>`` plane."""

    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    # metadata id -> {"name", "tf_op", "hlo_category", "program_id", ...}
    metadata: Dict[int, Dict[str, Any]] = field(default_factory=dict)
    _parts: Dict[int, List[str]] = field(default_factory=dict)

    def programs(self) -> Dict[int, Tuple[str, List[float]]]:
        """Program id -> ``(jit_<name>, seconds of each execution)`` of the
        ``XLA Modules`` events ``jit_<name>(<id>)``."""
        out: Dict[int, Tuple[str, List[float]]] = {}
        for ident, start, end in self.modules:
            m = re.match(r"^(.*)\((\d+)\)$", self.metadata[ident]["name"])
            if m:
                out.setdefault(int(m.group(2)), (m.group(1), []))[1].append(
                    end - start)
        return out

    def program_id(self, module: str) -> Optional[int]:
        """The id of the program ``jit_<module>``; None: no such program."""
        for pid, (name, _) in self.programs().items():
            if name == "jit_" + module:
                return pid
        return None

    def of_program(self, program_id: Optional[int]) -> List[Event]:
        """The operations of one program (None: of all)."""
        if program_id is None:
            return self.ops
        return [ev for ev in self.ops
                if self.metadata[ev[0]].get("program_id") == program_id]

    def parts(self, ident: int) -> List[str]:
        """:func:`components` of an operation's path, cut once an operation."""
        if ident not in self._parts:
            self._parts[ident] = components(
                self.metadata[ident].get("tf_op", ""))
        return self._parts[ident]


def _line(buf: bytes, span: Tuple[int, int]) -> Tuple[str, List[Event]]:
    name, at_ns, events = "", 0, []
    for number, _, v in _fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
        elif number == 3:
            at_ns = v
        elif number == 4:
            events.append(v)
    if name not in (OPS_LINE, MODULES_LINE):
        return name, []
    out = []
    for pos, end in events:
        # an XEvent, read in place: a decode step's line holds a million
        # events, and a generator a field is most of the time it takes
        got = [0, 0, 0, 0]          # -, metadata_id, offset_ps, duration_ps
        while pos < end:
            key = buf[pos]
            if key >= 0x20 or key & 7:      # not a varint of fields 1-3
                for number, wire, v in _fields(buf, pos, end):
                    if wire == 0 and number < 4:
                        got[number] = v
                break
            value = buf[pos + 1]
            pos += 2
            if value >= 0x80:
                value &= 0x7F
                shift = 7
                while True:
                    b = buf[pos]
                    pos += 1
                    value |= (b & 0x7F) << shift
                    if b < 0x80:
                        break
                    shift += 7
            got[key >> 3] = value
        start = at_ns * 1e-9 + got[2] * 1e-12
        out.append((got[1], start, start + got[3] * 1e-12))
    return name, out


def _plane(buf: bytes, span: Tuple[int, int]) -> Optional[Tuple[int, DevicePlane]]:
    lines, event_md, stat_md, device = [], [], [], None
    for number, _, v in _fields(buf, *span):
        if number == 2:
            m = DEVICE_PLANE.match(_text(buf, v))
            if not m:
                return None
            device = int(m.group(1))
        elif number == 3:
            lines.append(v)
        elif number == 4:
            event_md.append(v)
        elif number == 5:
            stat_md.append(v)
    if device is None:
        return None
    stat_names: Dict[int, str] = {}
    for entry in stat_md:
        key, value = _map_entry(buf, entry)
        for number, _, v in _fields(buf, *value):
            if number == 2:
                stat_names[key] = _text(buf, v)
    plane = DevicePlane()
    for entry in event_md:
        key, value = _map_entry(buf, entry)
        md: Dict[str, Any] = {"name": ""}
        for number, _, v in _fields(buf, *value):
            if number == 2:
                md["name"] = _text(buf, v)
            elif number == 5:
                what, stat = _stat(buf, v, stat_names)
                if what in KEPT:
                    md[what] = stat
        plane.metadata[key] = md
    for span_ in lines:
        name, events = _line(buf, span_)
        if name == MODULES_LINE:
            plane.modules = events
        elif name == OPS_LINE:
            containers = {k for k, md in plane.metadata.items()
                          if CONTAINER.match(op_name(md["name"]))}
            plane.ops = [ev for ev in events if ev[0] not in containers]
    return device, plane


_PARSED: Dict[str, Dict[int, DevicePlane]] = {}
SECONDS: Dict[str, float] = {}      # what reading each file took


def read(path: str) -> Dict[int, DevicePlane]:
    """Device number -> :class:`DevicePlane` of an ``.xplane.pb``; a file is
    parsed once a process, whoever asks."""
    if path not in _PARSED:
        t0 = time.time()
        with open(path, "rb") as f:
            buf = f.read()
        planes = {}
        for number, wire, v in _fields(buf):
            if number == 1 and wire == 2:
                found = _plane(buf, v)
                if found is not None and found[1].ops:
                    planes[found[0]] = found[1]
        _PARSED[path] = planes
        SECONDS[path] = time.time() - t0
    return _PARSED[path]


# -- scope paths ------------------------------------------------------------------

_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap|pmap|checkpoint|remat|"
                      r"custom_jvp|custom_vjp)\((.*)\)$")
_JIT = re.compile(r"^p?jit\(.*\)$")
# the words of docs/OBSERVABILITY.md, "Model parts on the device rows"
VOCABULARY = (
    "attn_scores", "attn_softmax", "attn_context", "dropout", "loss",
    "optimizer", "kv_gather", "decode_attention", "kv_append", "moe_router",
    "moe_sort", "moe_experts", "moe_combine", "lm_head")
# what jax writes into a path for control flow and calls: no part of a model
STRUCTURE = re.compile(
    r"^(while|body|cond|scan|branch_\d+_fun|closed_call|core_call|remat\d*|"
    r"checkpoint|rematted_computation|custom_jvp_call|custom_lin|shard_map|"
    r"custom_vjp_call(_jaxpr)?|pallas_call)$")
# a flax submodule's name, as this repo writes them: lower case, no brackets
_MODULE = re.compile(r"^[a-z][a-z0-9_]*$")


def components(tf_op: str) -> List[str]:
    """``jit(loss)/transpose(jvp(T5))/T5.decode/decoder/layer_1/cross_attn/
    attn_softmax/reduce_max:`` -> ``[T5, T5.decode, decoder, layer_1,
    cross_attn, attn_softmax]``: the operation type after ``:`` and the
    trailing primitive dropped, the leading ``jit(...)`` parts dropped,
    ``jvp(X)`` / ``transpose(jvp(X))`` / ``vmap(X)`` unwrapped to ``X``
    (empty: dropped).  Of two paths joined by ``;`` (instructions XLA merged)
    the first is taken."""
    path = tf_op.split(";", 1)[0].rsplit(":", 1)[0] if tf_op else ""
    parts = []
    for part in path.split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        if part:
            parts.append(part)
    parts = parts[:-1]                      # the primitive
    while parts and _JIT.match(parts[0]):
        parts.pop(0)
    return parts


def is_part(component: str) -> bool:
    """Whether a path component names a part of the model: a word of the
    vocabulary or a flax submodule's name.  The top module is named by its
    class (``T5ForConditionalGeneration``, ``CausalLM.__call__``), which says
    "the model" and no part of it; ``jit(_where)``, an einsum's
    ``bqhd,bkhd->bhqk`` and jax's control-flow words are no parts either."""
    return (component in VOCABULARY
            or bool(_MODULE.match(component))
            and not STRUCTURE.match(component))


def covered(parts: List[str]) -> bool:
    return any(is_part(c) for c in parts)


def part_path(parts: List[str], depth: Optional[int] = None) -> str:
    """The path down to its last part, layers merged (``layer_3`` ->
    ``*``), the model's class and jax's own words left out:
    ``encoder/*/self_attn/attn_softmax``.  ``depth`` keeps that many leading
    components.  ``(unscoped)`` where no component is a part."""
    kept = [re.sub(r"^layer_\d+$", "*", c) for c in parts if is_part(c)]
    if depth is not None:
        kept = kept[:depth]
    return "/".join(kept) or "(unscoped)"
