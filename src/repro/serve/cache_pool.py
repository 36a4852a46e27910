"""Slot-indexed two-tier KV pool: explicit array-lifetime management.

The pool preallocates ONE decode cache at ``(max_slots, max_len)`` and
treats each batch row as an allocatable slot whose lifetime is a request
lifetime (OLLA's array-lifetime idea applied to serving: the cache rows
are the arrays, alloc/free is the plan).  Two tiers of state live here:

* device: the cache pytree itself (int8 K/V + f32 scales, per-slot
  ``pos`` lengths) — shapes NEVER change, so the decode step compiled
  against it is reused for the whole process lifetime;
* host: the free-list and alloc/free accounting — pure Python, no
  device sync on the scheduling path.

``scatter_request`` is the jitted join: it writes a freshly prefilled
single-request cache (already grown to ``max_len``) into a free slot with
one ``dynamic_update_slice`` per leaf and stamps the slot's length.
Retirement is free: the slot's rows simply stop being read (the engine
drops it from the active mask) and the host free-list gets the slot back.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import transformer
from repro.models.config import ModelConfig


@jax.named_scope("scatter")
def scatter_request(pool_cache: dict, req_cache: dict, slot, length) -> dict:
    """Write a prefilled request cache (leading batch dim 1, sequence axis
    already grown to the pool's ``max_len``) into ``slot``.

    ``slot``/``length`` may be traced scalars — joining a request never
    triggers a recompile.  Functional: returns a new cache pytree (jit
    with ``donate_argnums=(0,)`` to update in place).
    """
    out = dict(pool_cache)
    for name, ax in transformer.CACHE_SEQ_AXES.items():
        if name not in pool_cache:
            continue
        upd = req_cache[name]
        if upd.shape[ax] != pool_cache[name].shape[ax]:
            raise ValueError(
                f"scatter_request: {name} has {upd.shape[ax]} sequence "
                f"slots, pool holds {pool_cache[name].shape[ax]} — grow the "
                f"prefill cache to max_len first (transformer.grow_cache)")
        start = [0] * upd.ndim
        start[1] = slot                       # (L, B, ...) batch axis
        out[name] = jax.lax.dynamic_update_slice(
            pool_cache[name], upd.astype(pool_cache[name].dtype),
            tuple(start))
    out["pos"] = pool_cache["pos"].at[slot].set(
        jnp.asarray(length, jnp.int32))
    return out


class SlotPool:
    """Preallocated slot-pooled decode cache + host-side free-list.

    Every ``alloc`` must be matched by exactly one ``free``; the engine's
    slot-leak invariant (`allocs == frees` and ``occupancy == 0`` once a
    trace drains) is asserted in tests.
    """

    def __init__(self, cfg: ModelConfig, max_slots: int, max_len: int, *,
                 quantized: bool = True, mesh=None):
        if max_slots < 1:
            raise ValueError(f"SlotPool: max_slots must be >= 1, "
                             f"got {max_slots}")
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.quantized = quantized
        self.mesh = mesh
        self.cache = transformer.init_cache(cfg, max_slots, max_len,
                                            quantized=quantized)
        # per-slot lengths replace the lockstep scalar position: occupancy
        # is data, not shape
        self.cache["pos"] = jnp.zeros((max_slots,), jnp.int32)
        # mesh mode: K/V shard over "model" (kv-heads, or the sequence dim
        # as serve_kv_shard falls back); the slot axis stays whole — DP in
        # serving is separate engine replicas, not a sharded pool
        self.specs = None
        if mesh is not None:
            from repro.distributed import sharding as shd
            self.specs = shd.serve_cache_specs(cfg, self.cache, mesh)
            self.cache = jax.device_put(
                self.cache, shd.to_shardings(mesh, self.specs))
        self._free = list(range(max_slots - 1, -1, -1))   # pop() -> slot 0 first
        self._live: set[int] = set()
        self._quarantined: set[int] = set()
        self.allocs = 0
        self.frees = 0
        self.quarantines = 0

    # -- host-side lifetime management ------------------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def occupancy(self) -> int:
        return len(self._live)

    @property
    def quarantined(self) -> int:
        return len(self._quarantined)

    def alloc(self) -> int | None:
        """Claim a free slot id, or None when the pool is full."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._live.add(slot)
        self.allocs += 1
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._live:
            raise ValueError(f"SlotPool.free: slot {slot} is not live "
                             f"(double free or foreign slot)")
        self._live.remove(slot)
        self._free.append(slot)
        self.frees += 1

    # -- fault quarantine --------------------------------------------------
    def quarantine(self, slot: int) -> None:
        """Pull a poisoned live slot OUT of circulation: it is neither
        live (its request is gone) nor free (it must not be handed to a
        new request until the engine has audited the pool).  Release via
        :meth:`release_quarantined` — that is when the matching ``free``
        is counted, so ``allocs == frees`` still holds once a drained
        pool has released its quarantine."""
        if slot not in self._live:
            raise ValueError(f"SlotPool.quarantine: slot {slot} is not "
                             f"live")
        self._live.remove(slot)
        self._quarantined.add(slot)
        self.quarantines += 1

    def release_quarantined(self) -> list[int]:
        """Return quarantined slots to the free list (their bytes are
        dead by contract — the next ``scatter_request`` fully overwrites
        a slot's rows and re-stamps its length).  Call after
        :meth:`audit` passes."""
        released = sorted(self._quarantined)
        for slot in released:
            self._quarantined.remove(slot)
            self._free.append(slot)
            self.frees += 1
        return released

    def audit(self) -> dict:
        """Verify the pool's alloc/free invariant; raise on corruption.

        Checks: the free / live / quarantined sets partition the slot
        space exactly, and the alloc/free counters reconcile with what
        is currently outstanding.  Returns the accounting snapshot the
        engine attaches to its diagnostics."""
        free = set(self._free)
        report = {"free": len(free), "live": len(self._live),
                  "quarantined": len(self._quarantined),
                  "allocs": self.allocs, "frees": self.frees}
        if len(free) != len(self._free):
            raise RuntimeError(f"SlotPool.audit: duplicate slots on the "
                               f"free list ({sorted(self._free)})")
        overlap = (free & self._live) | (free & self._quarantined) \
            | (self._live & self._quarantined)
        if overlap:
            raise RuntimeError(f"SlotPool.audit: slots in two states: "
                               f"{sorted(overlap)}")
        missing = set(range(self.max_slots)) - free - self._live \
            - self._quarantined
        if missing:
            raise RuntimeError(f"SlotPool.audit: slots leaked out of all "
                               f"states: {sorted(missing)}")
        outstanding = len(self._live) + len(self._quarantined)
        if self.allocs - self.frees != outstanding:
            raise RuntimeError(
                f"SlotPool.audit: allocs({self.allocs}) - "
                f"frees({self.frees}) != live+quarantined({outstanding})")
        return report

    # -- accounting --------------------------------------------------------
    def bytes_per_slot(self) -> int:
        """Exact device bytes one resident request pins (cache bytes /
        max_slots — every leaf's batch axis is the slot axis)."""
        total = sum(x.size * x.dtype.itemsize
                    for k, x in self.cache.items() if k != "pos")
        return total // self.max_slots

    def bytes_per_slot_per_device(self) -> int:
        """Bytes one resident request pins on EACH chip: the sharded
        leaves divide by their shard count, so this is what a per-chip
        byte budget must admit against.  Equals :meth:`bytes_per_slot`
        on an unsharded pool."""
        if self.specs is None:
            return self.bytes_per_slot()
        from repro.distributed import sharding as shd
        total = sum(
            x.size * x.dtype.itemsize
            // shd.spec_shards(self.mesh, self.specs[k])
            for k, x in self.cache.items() if k != "pos")
        return total // self.max_slots
