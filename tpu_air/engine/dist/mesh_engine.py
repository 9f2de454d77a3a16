"""MeshEngine — the paged engine over a leased ``(dp, tp)`` device mesh.

Same host loop, scheduler, slot table and token streams as
:class:`~tpu_air.engine.InferenceEngine`; what changes is WHERE state
lives and which jit wraps the step bodies:

* **lease** — when the tpu_air runtime is up, the engine takes a shaped
  chip lease (``Runtime.lease_chips`` — topology-aware, honors queued
  reservations) and builds its mesh over those devices, releasing the
  lease on ``close()``; without a runtime it meshes over the visible
  devices directly (the CPU-rig and bench path).
* **params** — sharded once at construction via ``lm_param_spec`` (q/k/v
  and SwiGLU gate/up over ``model`` on the output dim, o/down on the
  input dim, embeddings/norms replicated).
* **KV pages** — the page pools shard over ``data``; the
  :class:`~tpu_air.engine.dist.pool.ShardedPagedPool` keeps every slot's
  pages (null page included) inside that slot's own dp shard so
  ``gather_pages`` and the decode scatter stay shard-local, and XLA's
  SPMD partitioner inserts only the tp all-reduces the matmuls need.
* **admission** — capacity is gated PER dp REPLICA (a full replica can't
  borrow pages across a shard boundary): the predicate simulates the
  slot each candidate will land in (lowest free row first — the
  SlotManager's acquire order) and reserves against that replica.

Token parity with the single-chip engine and offline ``generate()`` is
the acceptance anchor, pinned by tests/test_kvpool.py's parity matrix on
the forced-8-device CPU mesh and by the subprocess rig in
tests/_mesh_parity_driver.py.
"""

from __future__ import annotations

from typing import List, Optional

import jax

from tpu_air.models.lm.generate import init_paged_cache
from tpu_air.parallel.mesh import make_mesh, visible_devices
from tpu_air.parallel.sharding import lm_param_shardings, lm_param_spec, \
    shard_params

from ..engine import InferenceEngine
from ..types import (EngineConfig, ExpertExchangeUnsupported,
                     refuse_pages_only)
from .pool import ShardedPagedPool
from .sharded import (
    make_sharded_page_copy_fn,
    make_sharded_paged_decode_step_fn,
    make_sharded_prefill_chunk_fn,
    make_sharded_step_feed_fns,
    paged_cache_shardings,
)


class MeshEngine(InferenceEngine):
    """Tensor-parallel, data-parallel paged decode over a leased mesh."""

    def __init__(self, model, params, config: Optional[EngineConfig] = None,
                 *, dp: int = 1, tp: int = 1, devices=None,
                 lease_timeout: Optional[float] = 60.0,
                 auto_start: bool = True, name: str = "mesh-engine"):
        cfg = config or EngineConfig()
        if cfg.adapter_slots > 0:
            raise ValueError(
                "adapter_slots is single-chip-only for now: the sharded "
                "decode step has no bank shardings (see docs/SERVING.md)")
        if cfg.num_slots % dp != 0:
            raise ValueError(
                f"num_slots {cfg.num_slots} not divisible by dp {dp}")
        self._dp = int(dp)
        self._tp = int(tp)
        self._lease: Optional[List[int]] = None
        self._runtime = None
        devs = self._acquire_devices(devices, lease_timeout)
        self.mesh = make_mesh(("data", "model"), (self._dp, self._tp),
                              devices=devs)
        super().__init__(model, params, cfg, auto_start=auto_start, name=name)
        self.metrics.set_topology(
            lease=self.lease_id, mesh=f"{self._dp}x{self._tp}",
            role="decode", decode_replicas=self._dp,
            mesh_devices=self._dp * self._tp,
        )

    # -- lease / device acquisition ------------------------------------------
    def _acquire_devices(self, devices, lease_timeout):
        n = self._dp * self._tp
        if devices is not None:
            devs = list(devices)
            if len(devs) < n:
                raise ValueError(
                    f"mesh {self._dp}x{self._tp} needs {n} devices, "
                    f"got {len(devs)}")
            return devs[:n]
        from tpu_air.core import runtime as _rt

        if _rt.is_initialized():
            rt = _rt.get_runtime()
            chips = rt.lease_chips(n, timeout=lease_timeout)
            self._lease = chips
            self._runtime = rt
            # a driver-level lease indexes this process's device list
            all_devs = jax.devices()
            beyond = [i for i in chips if i >= len(all_devs)]
            if beyond:
                rt.release_chips(chips)
                self._lease = None
                raise ValueError(
                    f"lease {list(chips)} names device(s) {beyond}; this "
                    f"process sees {len(all_devs)}")
            return [all_devs[i] for i in chips]
        devs = visible_devices()
        if len(devs) < n:
            raise ValueError(
                f"mesh {self._dp}x{self._tp} needs {n} devices, "
                f"only {len(devs)} visible")
        return devs[:n]

    @property
    def lease_id(self) -> str:
        if self._lease is None:
            return "local"
        return "chips:" + "-".join(str(c) for c in self._lease)

    # -- sharded device state -------------------------------------------------
    def _pages_per_replica(self) -> int:
        cfg = self.config
        if cfg.num_pages is None:
            # every slot of a replica can fill its slot_len, plus its own null
            # page (dp * this stays dp-divisible, unlike S*ppslot + 1)
            return (cfg.num_slots // self._dp) * cfg.pages_per_slot() + 1
        if cfg.num_pages % self._dp != 0:
            raise ValueError(
                f"num_pages {cfg.num_pages} not divisible by dp {self._dp}")
        per = cfg.num_pages // self._dp
        if per < 2:
            raise ValueError(
                f"num_pages {cfg.num_pages} leaves <2 pages per replica")
        return per

    def _build_paged_state(self) -> None:
        cfg = self.config
        refuse_pages_only(
            self.model, "MeshEngine shards page pools over data and has no "
            "sharding for per-slot recurrent state")
        mc = self.model.config
        if not getattr(mc, "holds_all_experts", True):
            raise ExpertExchangeUnsupported(
                f"the model holds experts {mc.experts_first}..+"
                f"{mc.experts_held} of the {mc.num_experts} its router "
                "scores; the exchange of the other assignments between "
                "ranks is not built (engine/dist/sharded.py, ROADMAP.md M1)")
        ppr = self._pages_per_replica()
        self.pool = ShardedPagedPool(
            self._dp, ppr, cfg.page_len, cfg.num_slots,
            cfg.pages_per_slot(), prefix_cache=cfg.prefix_cache,
        )
        cache = init_paged_cache(
            self.model, cfg.num_slots, self._dp * ppr, cfg.page_len,
            cfg.pages_per_slot(),
        )
        self._cache_sh = paged_cache_shardings(cache, self.mesh)
        self.cache = jax.tree_util.tree_map(
            jax.device_put, cache, self._cache_sh)
        self._param_sh = lm_param_shardings(self.params, self.mesh)
        self.params = shard_params(self.params, self.mesh, lm_param_spec)
        self._decode_step = make_sharded_paged_decode_step_fn(
            self.model, cfg.slot_len, self.mesh, self._param_sh,
            self._cache_sh)
        self._chunk_fn = make_sharded_prefill_chunk_fn(
            self.model, cfg.page_len, cfg.slot_len, self.mesh,
            self._param_sh, self._cache_sh)
        self._copy_fn = make_sharded_page_copy_fn(self.mesh, self._cache_sh)
        self._advance, self._set_row = make_sharded_step_feed_fns(self.mesh)

    # -- per-replica admission ------------------------------------------------
    def _begin_admission_round(self) -> None:
        self._round_reserved_r = [0] * self._dp
        # acquire order: lowest free row first — the predicate must know
        # which replica each admit lands in before any acquire happens
        self._round_free = self.slots.free_indices()

    def _can_admit(self, req) -> bool:
        if not self._round_free:
            return False
        idx = self._round_free[0]
        r = self.pool.replica_of(idx)
        need = self.pool.worst_case_pages(len(req.prompt), req.max_new_tokens)
        if self._round_reserved_r[r] + need > self.pool.replica_capacity(r):
            return False
        self._round_reserved_r[r] += need
        self._round_free.pop(0)
        return True

    # -- sharded-layout hooks -------------------------------------------------
    def _null_entry(self, slot_index: int) -> int:
        return self.pool.null_page_of(slot_index)

    def _insert_shipped_pages(self, cache, page_ids, payload):
        cache = super()._insert_shipped_pages(cache, page_ids, payload)
        # the eager scatters above may not preserve the pjit layout; pin
        # the rebuilt leaves back onto the engine shardings before the
        # donated decode step sees them
        return jax.tree_util.tree_map(jax.device_put, cache, self._cache_sh)

    # -- lifecycle ------------------------------------------------------------
    def close(self) -> None:
        super().close()
        if self._lease is not None and self._runtime is not None:
            try:
                self._runtime.release_chips(self._lease)
            finally:
                self._lease = None
                self._runtime = None
