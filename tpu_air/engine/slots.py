"""Slot table of the continuous-batching engine.

A *slot* is one row of the engine's fixed decode batch: row ``s`` of the
block table, whose pages hold the sequence's K/V (engine/kvpool/).  The
host-side :class:`SlotManager` tracks which request occupies each row and
where its context ends.

Lifecycle of a slot (docs/SERVING.md)::

    free -> [admit] prefilling (chunks run between decode steps)
         -> [final chunk] decoding(pos=len(prompt)) -> [decode steps] pos+1
         -> [EOS or budget] free again -- no page zeroing on retirement:
    stale K/V beyond the next occupant's written positions are masked by
    the per-row validity mask (arange <= index[row]) and progressively
    overwritten, so retirement is host bookkeeping only.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional

from .types import Request


@dataclass
class Slot:
    """Host bookkeeping for one block-table row."""

    index: int
    request: Optional[Request] = None
    pos: int = 0            # cache write position == tokens in context
    budget_left: int = 0    # decode steps remaining before forced retirement
    # mid-chunked-prefill flag + the pool's AdmitPlan
    # (remaining chunk starts, prefix coverage).  A prefilling slot holds
    # pages and a request but does NOT ride the decode step yet.
    prefilling: bool = False
    plan: Any = None

    @property
    def active(self) -> bool:
        return self.request is not None


class SlotManager:
    """Free-list over the ``S`` rows."""

    def __init__(self, num_slots: int):
        self.slots: List[Slot] = [Slot(i) for i in range(num_slots)]
        # pop() takes from the end: keep it ascending-last so admission
        # fills row 0 first (deterministic slot assignment for the parity
        # tests — FIFO arrival k lands in the lowest free row)
        self._free: List[int] = list(range(num_slots))[::-1]

    def free_count(self) -> int:
        return len(self._free)

    def free_indices(self) -> List[int]:
        """Free rows in ACQUIRE order (lowest first) — what an admission
        predicate that must know which row each admit will land in (the
        MeshEngine's per-replica capacity gate) simulates against."""
        return sorted(self._free)

    def active_slots(self) -> List[Slot]:
        return [s for s in self.slots if s.active]

    def occupancy(self) -> int:
        return len(self.slots) - len(self._free)

    def acquire(self) -> Slot:
        slot = self.slots[self._free.pop()]
        assert not slot.active, "acquired an occupied slot"
        return slot

    def release(self, slot: Slot) -> None:
        slot.request = None
        slot.pos = 0
        slot.budget_left = 0
        slot.prefilling = False
        slot.plan = None
        # keep the free list sorted descending so the next acquire still
        # hands out the lowest free row
        self._free.append(slot.index)
        self._free.sort(reverse=True)
