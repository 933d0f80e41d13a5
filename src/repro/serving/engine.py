"""Slot-based continuous-batching inference engine.

One engine instance = one execution anchor's serving plane for one model:
a fixed decode batch of ``slots`` sequences sharing jitted prefill /
decode functions. Sessions join/leave slots independently (per-slot
positions in the cache make lockstep unnecessary). The engine is the
``v_cmp`` substrate AIS compute leases reserve against, and its
``export_slot``/``import_slot`` are the state-transfer primitive behind
make-before-break migration.

Hot-path disciplines (the per-token costs that separate a toy loop from a
serving engine):

* **Fused multi-step decode** — ``decode_round(steps=K)`` runs K decode
  steps inside ONE jitted ``lax.scan`` with on-device greedy sampling and
  an on-device active-slot mask: one dispatch and one device→host transfer
  per K tokens instead of per token.
* **Bucketed prefill** — prompts are right-padded to power-of-two buckets
  with the true length threaded through ``LM.prefill`` as a traced scalar,
  so the engine compiles O(log max_len) prefill variants instead of one
  per distinct prompt length (``prefill_compiles`` exposes the counter).
* **Donated, index-addressed slot state** — slot insert (admit / migrate
  in) and the decode cache update run under ``jax.jit(...,
  donate_argnums=...)`` with per-slot ``dynamic_update_slice`` writes, so
  admitting or exporting a session no longer materialises a second full
  cache.

The engine is single-device: its params and cache live on the process's
first device (a TPU chip, or the CPU in the tests, where Pallas kernels run
in interpret mode). No serving code builds a mesh yet.
"""

from __future__ import annotations

import itertools
import time
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.transformer import LM
from repro.models import kvcache as KV
from repro.obs import span

#: smallest prefill bucket — below this the compile is cheap enough that
#: further splitting buys nothing
_MIN_BUCKET = 16


class RequestRefused(ValueError):
    """A request this engine cannot serve as asked (prompt longer than
    ``max_len``, adapter not loaded). The serving plane answers it with a
    failed result (NO_FEASIBLE_BINDING); any other error is a fault."""


class PagePoolExhausted(RuntimeError):
    """The paged engine has no free KV pages for an allocation. This is the
    explicit admission signal the paged layout buys: sessions no longer
    reserve ``max_len`` up front, so running out of MEMORY (pages) is
    distinct from running out of decode SLOTS — the serving plane maps it
    to COMPUTE_SCARCITY, and pressure-driven reclamation (hibernate the
    coldest parked sessions) is supposed to keep it from firing at all."""


def prefill_buckets(max_len: int) -> List[int]:
    """Power-of-two padded prompt lengths, capped at ``max_len``.

    len(buckets) <= ceil(log2(max_len)): the compile-count ceiling the
    engine guarantees over any prompt-length mix.
    """
    out: List[int] = []
    b = _MIN_BUCKET
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


@dataclass
class SlotState:
    session_id: str
    position: int
    tokens_generated: int = 0
    last_token: int = 0
    #: tenant adapter bound to this session ("" = base model). Resolved
    #: to an int32 table index per decode round; travels with the
    #: export payload so migration/hibernation keep the binding.
    adapter_id: str = ""
    #: parked = bound-but-idle: the session keeps its slot (and pages) but
    #: rides decode rounds with active=False, so its state never advances —
    #: the cheap-resume tier between resident and hibernated
    parked: bool = False
    #: monotone use tick (engine-local LRU clock, not wall time)
    last_used: int = 0
    #: page ids owned by this slot, in block-table order (paged engines)
    pages: List[int] = field(default_factory=list)


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params=None, *, slots: int = 8,
                 max_len: int = 512, seed: int = 0,
                 paged: bool = False,
                 page_size: int = KV.DEFAULT_PAGE_SIZE,
                 num_pages: Optional[int] = None,
                 hibernation=None, clock=None, adapters=None):
        """``paged=True`` selects the block-table paged KV layout for
        families that support it (full-attention stacked KV — see
        ``kvcache.supports_paging``); other families silently keep the dense
        slot layout (their state is O(window)/O(1) and gains nothing from
        paging) but still park and hibernate. ``num_pages`` bounds device
        KV memory (default: enough for every slot at max_len, plus the
        scratch page — no worse than dense). ``hibernation`` is a
        :class:`~repro.serving.hibernation.HibernationStore` (or ``True``
        for a private unbounded one) enabling the host-memory tier.
        ``clock`` (any object with ``now()``) timestamps hibernation
        records so store-side TTL/LRU ordering sees real ages.
        ``adapters`` is an :class:`~repro.adapters.runtime.AdapterRuntime`
        (or ``True`` for a default-sized one) enabling per-session LoRA
        multiplexing over this engine's base model."""
        self.cfg = cfg
        self.lm = LM(cfg)
        self.slots = slots
        self.max_len = max_len
        if params is None:
            params = self.lm.init(jax.random.key(seed))
        self.params = params
        self.paged = bool(paged) and KV.supports_paging(cfg)
        if hibernation is True:
            from repro.serving.hibernation import HibernationStore
            hibernation = HibernationStore()
        if hibernation is False:                   # bool flag, not a store
            hibernation = None
        self.hibernation = hibernation
        self.clock = clock
        #: canonical exports: linear stacked-KV buffers zero their garbage
        #: tail (rows at index >= position: prefill bucket padding, stale
        #: rows of re-used slots), so the SAME logical state always
        #: fingerprints identically — across dense and paged engines, and
        #: across hibernate/resume round trips
        self._canonical = cfg.family in ("dense", "moe") \
            and not cfg.sliding_window
        if self.paged:
            self.page_size = KV.page_len(cfg, max_len, page_size)
            self.pages_per_slot = KV.pages_per_slot(max_len, self.page_size)
            full = 1 + slots * self.pages_per_slot      # incl. scratch page
            self.num_pages = full if num_pages is None \
                else max(2, int(num_pages))
            self.cache = KV.init_paged_cache(cfg, slots, max_len,
                                             self.num_pages, self.page_size)
            # free list excludes page 0 (the shared scratch/null page);
            # popped from the tail so allocation order is ascending
            self._free_page_list: List[int] = \
                list(range(self.num_pages - 1, 0, -1))
            self._block_host = np.zeros((slots, self.pages_per_slot),
                                        np.int32)
            self._paged_install = jax.jit(self._paged_install_impl,
                                          donate_argnums=(0,))
            self._paged_read = jax.jit(self._paged_read_impl)
        else:
            self.page_size = 0
            self.pages_per_slot = 0
            self.num_pages = 0
            self.cache = self.lm.init_cache(slots, max_len)
        self._slot_map: Dict[str, int] = {}
        self._slots: list[Optional[SlotState]] = [None] * slots
        self._use_clock = itertools.count(1)
        #: device "pos" may diverge from host truth once any row parks (the
        #: fused scan advances pos unconditionally); set -> resync next round
        self._pos_dirty = False
        self.buckets = prefill_buckets(max_len)
        self._compiled_buckets: set = set()
        #: (chunk size, adapter route) of the fused decode programs
        #: dispatched so far: the first dispatch of each compiles
        self._compiled_chunks: set = set()
        self._prefill = jax.jit(self._prefill_impl)
        # K-step fused decode: cache is DONATED — the scan updates it in
        # place instead of double-buffering the whole KV cache
        self._decode_fused = jax.jit(self._fused_impl, static_argnums=(4,),
                                     donate_argnums=(1,))
        self._decode_fused_adp = jax.jit(self._fused_adapter_impl,
                                         static_argnums=(7, 8),
                                         donate_argnums=(1,))
        if adapters is True:
            from repro.adapters.runtime import AdapterRuntime
            adapters = AdapterRuntime(cfg.d_model)
        self.adapters = adapters if adapters else None
        # slot insert: donate the full cache so admit/import is a per-slot
        # dynamic_update, not a full-cache copy
        self._slot_write = jax.jit(self._slot_write_impl, donate_argnums=(0,))
        self._slot_read = jax.jit(self._slot_read_impl)
        self._slot_read_canon = jax.jit(self._slot_read_canon_impl)
        # speculative decode: which cache leaves must be snapshotted per
        # scan step to make a round rollback-able (empty = pos-only)
        self._spec_paths = self._spec_stack_paths()
        self._spec_pending: Dict[str, dict] = {}
        self._spec_autoreg = jax.jit(self._spec_autoreg_impl,
                                     static_argnums=(4,),
                                     donate_argnums=(1,))
        self._spec_forced = jax.jit(self._spec_forced_impl,
                                    donate_argnums=(1,))

    # ------------------------------------------------------------------
    def free_slots(self) -> int:
        return sum(1 for s in self._slots if s is None)

    def has_slot(self, session_id: str) -> bool:
        return session_id in self._slot_map

    def position_of(self, session_id: str) -> int:
        """Current cache position (context length) of one session's slot —
        the authoritative payload size for migration."""
        idx = self._slot_map.get(session_id)
        if idx is None and self.hibernation is not None \
                and self.hibernation.has(session_id):
            return self.hibernation.record(session_id).position
        meta = self._slots[self._slot_map[session_id]]
        return meta.position

    # -- page-pool / session-tier accounting ----------------------------
    def free_pages(self) -> int:
        return len(self._free_page_list) if self.paged else 0

    def total_pages(self) -> int:
        """Usable pages (the scratch page is never allocatable)."""
        return self.num_pages - 1 if self.paged else 0

    def page_util(self) -> float:
        tot = self.total_pages()
        return 0.0 if tot <= 0 else 1.0 - len(self._free_page_list) / tot

    def pool_bytes(self) -> int:
        if self.paged:
            return KV.paged_cache_bytes(self.cfg, self.slots, self.max_len,
                                        self.num_pages, self.page_size)
        return KV.cache_bytes(self.cfg, self.slots, self.max_len)

    def resident_sessions(self) -> int:
        return len(self._slot_map)

    def parked_sessions(self) -> int:
        return sum(1 for s in self._slots if s is not None and s.parked)

    def hibernated_sessions(self) -> int:
        return len(self.hibernation) if self.hibernation is not None else 0

    def bound_sessions(self) -> int:
        """Sessions whose state this engine holds SOMEWHERE (resident slot
        or hibernation tier) — the number the lease layer binds against,
        decoupled from ``slots`` by paging + hibernation."""
        return self.resident_sessions() + self.hibernated_sessions()

    def is_parked(self, session_id: str) -> bool:
        idx = self._slot_map.get(session_id)
        return idx is not None and self._slots[idx] is not None \
            and self._slots[idx].parked

    def has_hibernated(self, session_id: str) -> bool:
        return self.hibernation is not None \
            and self.hibernation.has(session_id)

    def has_session(self, session_id: str) -> bool:
        return self.has_slot(session_id) or self.has_hibernated(session_id)

    # -- page allocation -------------------------------------------------
    def _alloc_pages(self, n: int) -> List[int]:
        if n > len(self._free_page_list):
            raise PagePoolExhausted(
                f"page pool exhausted: need {n} pages, "
                f"{len(self._free_page_list)} free of {self.total_pages()}")
        return [self._free_page_list.pop() for _ in range(n)]

    def _free_slot_pages(self, idx: int) -> None:
        meta = self._slots[idx]
        if meta is not None and meta.pages:
            self._free_page_list.extend(reversed(meta.pages))
            meta.pages = []
        self._block_host[idx, :] = 0

    def _ensure_pages(self, idx: int, upto_tokens: int) -> bool:
        """Grow slot ``idx``'s block table to cover token indices
        [0, upto_tokens). Under pool pressure, hibernates the coldest
        parked sessions first (LRU reclaim); raises PagePoolExhausted when
        reclamation cannot free enough."""
        meta = self._slots[idx]
        needed = min(-(-max(upto_tokens, 1) // self.page_size),
                     self.pages_per_slot)
        grow = needed - len(meta.pages)
        if grow <= 0:
            return False
        if grow > len(self._free_page_list):
            self._reclaim_pages(grow)
        new = self._alloc_pages(grow)
        meta.pages.extend(new)
        self._block_host[idx, :len(meta.pages)] = meta.pages
        return True

    def _reclaim_pages(self, need: int) -> None:
        """Hibernate coldest parked sessions until ``need`` pages are free
        (best effort; the caller's allocation raises if still short)."""
        if self.hibernation is None:
            return
        while len(self._free_page_list) < need:
            victim = None
            best = None
            for s in self._slots:
                if s is not None and s.parked and \
                        (best is None or s.last_used < best):
                    best, victim = s.last_used, s.session_id
            if victim is None:
                return
            if not self.hibernate_slot(victim):
                return          # store full: nothing more can page out

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill shapes traced so far (== jit cache entries:
        the padded width is the only shape that varies across prompts)."""
        return len(self._compiled_buckets)

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_len

    def _alloc(self, session_id: str) -> int:
        for i, s in enumerate(self._slots):
            if s is None:
                self._slot_map[session_id] = i
                return i
        raise RuntimeError("no free decode slots (lease accounting bug)")

    # ------------------------------------------------------------------
    def _batch_axis(self, path) -> int:
        """Slot/batch axis of a cache leaf: stacked families carry layers
        first ([L, b, ...]); hybrid leaves and 'pos' are slot-first."""
        keys = [getattr(k, "key", getattr(k, "idx", "")) for k in path]
        if "pos" in keys or self.cfg.family == "hybrid":
            return 0
        return 1 if any(str(k) in ("k", "v", "conv", "ssm", "cross_k",
                                   "cross_v") for k in keys) else 0

    def _slot_write_impl(self, cache, cache1, idx):
        """Insert a batch-1 cache into slot ``idx`` (donated, traced idx)."""
        def ins(path, full, one):
            ax = self._batch_axis(path)
            return jax.lax.dynamic_update_slice_in_dim(
                full, one.astype(full.dtype), idx, axis=ax)

        return jax.tree_util.tree_map_with_path(ins, cache, cache1)

    def _slot_read_impl(self, cache, idx):
        """Extract the batch-1 state of slot ``idx`` (no donation — the
        source keeps serving while migration is in flight)."""
        def ext(path, full):
            ax = self._batch_axis(path)
            return jax.lax.dynamic_slice_in_dim(full, idx, 1, axis=ax)

        return jax.tree_util.tree_map_with_path(ext, cache)

    def _slot_read_canon_impl(self, cache, idx, pos):
        """Canonical batch-1 export for linear stacked-KV families: zero
        the garbage tail (rows >= position) and report the host position,
        so identical logical state always fingerprints identically."""
        state = self._slot_read_impl(cache, idx)
        S = state["layers"]["k"].shape[2]
        valid = (jnp.arange(S) < pos)[None, None, :, None, None]
        out = dict(state)
        out["layers"] = {"k": jnp.where(valid, state["layers"]["k"], 0),
                         "v": jnp.where(valid, state["layers"]["v"], 0)}
        out["pos"] = jnp.full((1,), pos, jnp.int32)
        return out

    def _paged_install_impl(self, cache, k1, v1, idx, row, n):
        """Scatter a batch-1 linear KV cache ([L, 1, S', kh, hd]) into this
        slot's pages (cache donated). ``row`` [PPS] int32 holds the slot's
        page ids 0-padded: entries past the owned count scatter their
        (bucket-padding garbage) content into the scratch page, which is
        never read."""
        S = self.pages_per_slot * self.page_size

        def place(pool, src):
            src = src[:, 0]                              # [L, s, kh, hd]
            s = src.shape[1]
            if s < S:
                src = jnp.pad(src, ((0, 0), (0, S - s), (0, 0), (0, 0)))
            else:
                src = src[:, :S]
            src = src.reshape(src.shape[0], self.pages_per_slot,
                              self.page_size, src.shape[2],
                              src.shape[3]).astype(pool.dtype)
            return pool.at[:, row].set(src)

        return {"layers": {"k": place(cache["layers"]["k"], k1),
                           "v": place(cache["layers"]["v"], v1)},
                "block": cache["block"].at[idx].set(row),
                "pos": cache["pos"].at[idx].set(n)}

    def _paged_read_impl(self, cache, idx, pos):
        """Gather one slot's pages back into the canonical linear payload
        ([L, 1, max_len, kh, hd], tail zeroed) — the SAME bytes a dense
        engine exports for the same logical state, so fingerprints match
        across layouts and migration is layout-agnostic."""
        row = cache["block"][idx]                        # [PPS]
        valid = (jnp.arange(self.max_len) < pos)[None, :, None, None]

        def gather(pool):
            full = pool[:, row]                  # [L, PPS, page, kh, hd]
            full = full.reshape(full.shape[0], -1, full.shape[3],
                                full.shape[4])[:, :self.max_len]
            return jnp.where(valid, full, 0)[:, None]

        return {"layers": {"k": gather(cache["layers"]["k"]),
                           "v": gather(cache["layers"]["v"])},
                "pos": jnp.full((1,), pos, jnp.int32)}

    def _write_slot(self, idx: int, cache1):
        """Insert a batch-1 cache into slot ``idx`` of the engine cache."""
        self.cache = self._slot_write(self.cache, cache1, jnp.int32(idx))

    def export_slot(self, session_id: str):
        """Extract this session's state (the migration payload).

        Canonical families zero the KV tail and every family reports the
        host-side position (device pos drifts for parked rows — the fused
        scan advances it unconditionally), so the same logical state
        fingerprints identically across dense/paged layouts and across
        hibernate/resume round trips. Hibernated sessions export straight
        from the host tier: migrating a cold session needs no resume."""
        if session_id not in self._slot_map and self.has_hibernated(
                session_id):
            return self.hibernation.restore(session_id)
        idx = self._slot_map[session_id]
        meta = self._slots[idx]
        if self.paged:
            state = self._paged_read(self.cache, jnp.int32(idx),
                                     jnp.int32(meta.position))
        elif self._canonical:
            state = self._slot_read_canon(self.cache, jnp.int32(idx),
                                          jnp.int32(meta.position))
        else:
            state = dict(self._slot_read(self.cache, jnp.int32(idx)))
            state["pos"] = jnp.full((1,), meta.position, jnp.int32)
        return {"cache": state, "position": meta.position,
                "last_token": meta.last_token,
                "adapter_id": meta.adapter_id}

    def import_slot(self, session_id: str, payload) -> None:
        """Install a migrated session's state into a free slot. Raises
        AdmissionDenied when the target has no free slot — the migration
        abort cause (COMPUTE_SCARCITY), distinct from the lease-accounting
        bug the prefill path's exhaustion signals. On a paged engine the
        page allocation is part of admission: a pool too full to hold the
        payload denies the same way."""
        if self.free_slots() == 0:
            from repro.serving.state_transfer import AdmissionDenied
            raise AdmissionDenied(
                f"target admission denied: no free decode slots for "
                f"{session_id}")
        adapter_id = str(payload.get("adapter_id", ""))
        if adapter_id and (self.adapters is None
                           or not self.adapters.is_loaded(adapter_id)):
            # the adapter binding is part of the session contract: a
            # target that cannot realise it must refuse the transfer,
            # not silently continue on the base model
            from repro.serving.state_transfer import AdmissionDenied
            raise AdmissionDenied(
                f"target admission denied: adapter {adapter_id!r} not "
                f"loaded for {session_id}")
        idx = self._alloc(session_id)
        meta = SlotState(session_id, payload["position"],
                         last_token=payload["last_token"],
                         adapter_id=adapter_id,
                         last_used=next(self._use_clock))
        self._slots[idx] = meta
        if self.paged:
            try:
                self._ensure_pages(idx, max(int(payload["position"]), 1))
            except PagePoolExhausted as e:
                from repro.serving.state_transfer import AdmissionDenied
                self._slot_map.pop(session_id, None)
                self._slots[idx] = None
                raise AdmissionDenied(str(e)) from e
            row = np.zeros(self.pages_per_slot, np.int32)
            row[:len(meta.pages)] = meta.pages
            self.cache = self._paged_install(
                self.cache, payload["cache"]["layers"]["k"],
                payload["cache"]["layers"]["v"], jnp.int32(idx),
                jnp.asarray(row), jnp.int32(payload["position"]))
        else:
            self._write_slot(idx, payload["cache"])

    def _free_slot(self, session_id: str) -> None:
        """Free the slot and pages only — hibernated state (if any) stays."""
        idx = self._slot_map.pop(session_id, None)
        if idx is not None:
            if self.paged:
                self._free_slot_pages(idx)
            self._slots[idx] = None

    def release_slot(self, session_id: str) -> None:
        """End of session: free slot/pages AND purge any hibernated copy."""
        self._free_slot(session_id)
        if self.hibernation is not None:
            self.hibernation.drop(session_id)

    # -- tiering: resident <-> parked <-> hibernated ---------------------
    def park_slot(self, session_id: str) -> None:
        """Mark a resident session idle. It keeps its slot and pages but
        rides subsequent decode rounds with active=False — state frozen
        bit-exactly, resume is free."""
        meta = self._slots[self._slot_map[session_id]]
        meta.parked = True
        self._pos_dirty = True

    def hibernate_slot(self, session_id: str, *,
                       now: Optional[float] = None) -> bool:
        """Page a resident session out to the host tier, freeing its slot
        and pages for other sessions. Returns False — with the session left
        resident, state intact — when a capacity-bounded store refuses the
        payload: heartbeat/reclaim callers degrade (skip, retry next tick)
        instead of dying mid-tick. Records are stamped with ``now`` (or the
        engine clock) so store-side TTL/LRU ordering is real."""
        if self.hibernation is None:
            raise RuntimeError(
                f"cannot hibernate {session_id}: engine has no "
                f"hibernation store")
        if now is None:
            now = self.clock.now() if self.clock is not None else 0.0
        payload = self.export_slot(session_id)
        try:
            self.hibernation.put(session_id, payload, now=now)
        except MemoryError:
            # store_full is counted by the store itself; the session stays
            # resident/parked and a later tick retries once space frees up
            return False
        self._free_slot(session_id)
        return True

    def resume_slot(self, session_id: str) -> None:
        """Re-import a hibernated session. The store record is dropped only
        AFTER the import succeeds — a refused resume (no slot / no pages)
        must not lose the only copy of the state."""
        payload = self.hibernation.restore(session_id)
        self.import_slot(session_id, payload)
        self.hibernation.drop(session_id)

    def resume_session(self, session_id: str) -> None:
        """Bring a bound session back to active-resident from any tier."""
        idx = self._slot_map.get(session_id)
        if idx is not None:
            meta = self._slots[idx]
            meta.parked = False
            meta.last_used = next(self._use_clock)
            return
        if self.has_hibernated(session_id):
            self.resume_slot(session_id)
            return
        raise KeyError(f"unknown session {session_id}")

    # -- adapter lifecycle ------------------------------------------------
    def load_adapter(self, adapter_id: str, a, b) -> int:
        """Install adapter weights into this engine's device tables;
        idempotent. Returns the table index."""
        if self.adapters is None:
            raise RuntimeError("engine has no adapter runtime")
        return self.adapters.load(adapter_id, a, b)

    def unload_adapter(self, adapter_id: str) -> None:
        """Evict an adapter. Refused while any bound session (resident
        or parked) still references it — unloading under a live binding
        would silently continue those sessions on the base model."""
        if self.adapters is None:
            raise RuntimeError("engine has no adapter runtime")
        users = [s.session_id for s in self._slots
                 if s is not None and s.adapter_id == adapter_id]
        if users:
            raise RuntimeError(
                f"adapter {adapter_id!r} still bound by {users}")
        self.adapters.unload(adapter_id)

    # ------------------------------------------------------------------
    def prefill_session(self, session_id: str, prompt: np.ndarray, *,
                        adapter_id: str = "") -> dict:
        """Admit a session: run prefill, install the cache, return TTFT.

        The prompt is right-padded to its power-of-two bucket with the true
        length passed as a traced scalar — the whole mix of prompt lengths
        compiles at most ``len(self.buckets)`` prefill variants.

        ``adapter_id`` binds a tenant adapter for the session's lifetime;
        it must already be loaded on this engine (RequestRefused otherwise
        — the serving plane maps that to NO_FEASIBLE_BINDING).
        """
        t0 = time.perf_counter()
        aidx = 0
        if adapter_id:
            if self.adapters is None:
                raise RequestRefused(
                    f"engine has no adapter runtime; cannot bind "
                    f"{adapter_id!r} for {session_id}")
            try:
                aidx = self.adapters.index_of(adapter_id)
            except KeyError:
                raise RequestRefused(
                    f"adapter {adapter_id!r} not loaded on this engine "
                    f"for {session_id}")
        n = len(prompt)
        if n > self.max_len:
            # refuse rather than silently truncate: a truncated prefill
            # would condition generation on a clipped prefix while
            # position_of()/migration payload sizing report the full length
            raise RequestRefused(
                f"prompt of {n} tokens exceeds engine max_len "
                f"{self.max_len} for {session_id}")
        width = self._bucket(n)
        first = width not in self._compiled_buckets
        with span("engine.prefill", sid=session_id, tokens=n, bucket=width,
                  first=int(first)):
            tok = self._prefill_install(session_id, prompt, width, aidx,
                                        adapter_id)
        return {"first_token": tok,
                "ttfb_ms": (time.perf_counter() - t0) * 1e3}

    def _prefill_install(self, session_id: str, prompt: np.ndarray,
                         width: int, aidx: int, adapter_id: str) -> int:
        """Prefill ``prompt`` padded to ``width`` and install its cache in
        a free slot; returns the first generated token."""
        n = len(prompt)
        padded = np.zeros(width, np.int32)
        padded[:n] = prompt
        self._compiled_buckets.add(width)
        batch = {"tokens": jnp.asarray(padded[None, :], jnp.int32),
                 "length": jnp.int32(n)}
        lora = (self.adapters.A[aidx], self.adapters.B[aidx]) if aidx else ()
        logits, cache1 = self._prefill(self.params, batch, *lora)
        with span("engine.prefill.sync"):
            tok = int(jnp.argmax(logits[0]))
        idx = self._alloc(session_id)
        meta = SlotState(session_id, position=n, tokens_generated=1,
                         last_token=tok, adapter_id=adapter_id,
                         last_used=next(self._use_clock))
        self._slots[idx] = meta
        if self.paged:
            try:
                # only ceil(n / page) pages — NOT max_len worth: the whole
                # point of paging is that admission reserves what the
                # session actually uses
                self._ensure_pages(idx, n)
            except PagePoolExhausted:
                self._slot_map.pop(session_id, None)
                self._slots[idx] = None
                raise
            row = np.zeros(self.pages_per_slot, np.int32)
            row[:len(meta.pages)] = meta.pages
            with span("engine.slot_install"):
                self.cache = self._paged_install(
                    self.cache, cache1["layers"]["k"],
                    cache1["layers"]["v"], jnp.int32(idx),
                    jnp.asarray(row), jnp.int32(n))
        else:
            with span("engine.slot_install"):
                self._write_slot(idx, cache1)
        return tok

    # ------------------------------------------------------------------
    def _prefill_impl(self, params, batch, a1=None, b1=None):
        """Prefill of one padded prompt; ``a1``/``b1``: the session's LoRA
        rows, or None for the base model."""
        with jax.named_scope("prefill"):
            return self.lm.prefill(
                params, batch, self.max_len,
                adapter=None if a1 is None else (a1, b1))

    def _fused_impl(self, params, cache, last, active, steps: int):
        """K decode steps in one jitted scan. ``last``: [slots] int32 token
        feedback; ``active``: [slots] bool — inactive slots keep feeding
        their (zero) token so a fused chunk is bit-identical to K sequential
        single-step rounds regardless of who shares the batch.
        Returns (cache, token block [slots, K])."""
        def step(carry, _):
            c, fed = carry
            with jax.named_scope("decode_step"):
                logits, c = self.lm.decode_step(params, c, fed[:, None],
                                                active=active)
            nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            fed = jnp.where(active, nxt, fed)
            return (c, fed), fed

        (cache, _), toks = jax.lax.scan(step, (cache, last), None,
                                        length=steps)
        return cache, jnp.moveaxis(toks, 0, 1)          # [slots, K]

    def _fused_adapter_impl(self, params, cache, last, active, aidx,
                            adp_a, adp_b, steps: int, route: str):
        """Adapter-aware variant of the fused K-step scan: the per-slot
        int32 table ``aidx`` gathers stacked LoRA A/B rows inside every
        decode step. Tables are traced arguments (NOT closure constants),
        so load/unload between rounds needs no retrace — only the table
        contents change."""
        def step(carry, _):
            c, fed = carry
            with jax.named_scope("decode_step"):
                logits, c = self.lm.decode_step(
                    params, c, fed[:, None], active=active,
                    adapter=(adp_a, adp_b, aidx, route))
            nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            fed = jnp.where(active, nxt, fed)
            return (c, fed), fed

        (cache, _), toks = jax.lax.scan(step, (cache, last), None,
                                        length=steps)
        return cache, jnp.moveaxis(toks, 0, 1)          # [slots, K]

    # ------------------------------------------------------------------
    # Speculative decode: rollback-able scan rounds.
    #
    # Both the draft and verify role run the SAME shape of round: a
    # (γ+1)-step fused scan consuming [ℓ, t_1..t_γ] (ℓ = the slot's
    # unconsumed last token), whose post-step state at index n is exactly
    # the engine state after committing n of the γ candidate tokens. The
    # draft consumes its own outputs (autoregressive, producing the
    # proposals), the verifier consumes the proposals teacher-forced
    # (producing the target-greedy continuation y_0..y_γ in ONE fused
    # forward). ``spec_accept(n, y_n)`` then restores the index-n
    # snapshot: committed stream = d_1..d_n, y_n — bitwise what
    # target-only greedy decode would have produced.
    #
    # Rollback cost depends on the cache family: full-attention caches
    # written at absolute positions need NO snapshots (rows >= pos are
    # never attended and later overwritten — pos-only rollback, including
    # paged); recurrent/ring-buffer leaves (ssm conv/ssm, hybrid conv/h
    # and windowed k/v, sliding-window k/v) are destructive per step and
    # are stacked by the scan.
    # ------------------------------------------------------------------
    def _spec_stack_paths(self) -> List[tuple]:
        """Cache-leaf paths that must be snapshotted per scan step."""
        if self.cfg.family in ("dense", "moe", "encdec") \
                and not self.cfg.sliding_window:
            return []                       # pos-only rollback
        paths: List[tuple] = []
        layers = self.cache["layers"]
        if isinstance(layers, tuple):       # hybrid: per-layer dicts
            for i, layer in enumerate(layers):
                for key in sorted(layer.keys()):
                    paths.append(("layers", i, key))
        else:                               # stacked-layer dict carry
            for key in sorted(layers.keys()):
                if key in ("cross_k", "cross_v"):
                    continue                # static after prefill
                paths.append(("layers", key))
        return paths

    @staticmethod
    def _leaf_get(tree, path):
        for p in path:
            tree = tree[p]
        return tree

    @classmethod
    def _leaf_set(cls, tree, path, value):
        if not path:
            return value
        head = path[0]
        if isinstance(tree, tuple):
            return tuple(cls._leaf_set(t, path[1:], value) if i == head
                         else t for i, t in enumerate(tree))
        out = dict(tree)
        out[head] = cls._leaf_set(tree[head], path[1:], value)
        return out

    def _spec_autoreg_impl(self, params, cache, last, active, steps: int):
        """γ+1 autoregressive steps, snapshotting rollback leaves.
        Returns (cache, tokens [slots, steps], stacks [steps, ...])."""
        def step(carry, _):
            c, fed = carry
            logits, c = self.lm.decode_step(params, c, fed[:, None],
                                            active=active)
            nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            fed = jnp.where(active, nxt, fed)
            snap = [self._leaf_get(c, p) for p in self._spec_paths]
            return (c, fed), (fed, snap)

        (cache, _), (toks, stacks) = jax.lax.scan(
            step, (cache, last), None, length=steps)
        return cache, jnp.moveaxis(toks, 0, 1), stacks

    def _spec_forced_impl(self, params, cache, active, forced):
        """Teacher-forced scan over ``forced`` [slots, steps]: step t
        consumes forced[:, t] and emits the greedy next token — the one
        fused verify forward. Same snapshot discipline as the
        autoregressive round."""
        def step(c, tok):
            logits, c = self.lm.decode_step(params, c, tok[:, None],
                                            active=active)
            nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            snap = [self._leaf_get(c, p) for p in self._spec_paths]
            return c, (nxt, snap)

        cache, (ys, stacks) = jax.lax.scan(
            step, cache, jnp.moveaxis(forced, 0, 1))
        return cache, jnp.moveaxis(ys, 0, 1), stacks

    def _spec_prologue(self, session_id: str, gamma: int):
        """Shared admission for a spec round: slot lookup, bounds, page
        growth, device pos/block resync from host truth (a spec round
        always ends with host-side position authority)."""
        idx = self._slot_map[session_id]
        meta = self._slots[idx]
        if meta.adapter_id:
            raise ValueError(
                f"speculative decode does not support adapter-bound "
                f"sessions ({session_id} binds {meta.adapter_id!r})")
        if gamma < 1:
            raise ValueError("spec round needs gamma >= 1")
        if meta.position + gamma + 1 > self.max_len:
            raise ValueError(
                f"spec round of gamma={gamma} overruns max_len "
                f"{self.max_len} from position {meta.position}")
        if session_id in self._spec_pending:
            raise RuntimeError(
                f"spec round already pending for {session_id}; "
                f"spec_accept it first")
        last = np.zeros(self.slots, np.int32)
        active = np.zeros(self.slots, bool)
        last[idx] = meta.last_token
        active[idx] = True
        if self.paged:
            self._ensure_pages(idx, meta.position + gamma + 2)
        pos_host = np.zeros(self.slots, np.int32)
        for i, s in enumerate(self._slots):
            if s is not None:
                pos_host[i] = s.position
        cache = dict(self.cache)
        cache["pos"] = jnp.asarray(pos_host)
        if self.paged:
            cache["block"] = jnp.asarray(self._block_host)
        self.cache = cache
        return idx, meta, last, active

    def spec_round(self, session_id: str, gamma: int) -> List[int]:
        """Draft role: propose γ tokens autoregressively from the current
        state. The slot's host state does NOT advance — the round is
        pending until ``spec_accept`` commits a prefix of it. Only one
        slot runs; co-resident slots ride with active=False (frozen), so
        the snapshots are restorable wholesale."""
        gamma = int(gamma)
        idx, meta, last, active = self._spec_prologue(session_id, gamma)
        pre = [self._leaf_get(self.cache, p).copy()
               for p in self._spec_paths]
        self.cache, toks, stacks = self._spec_autoreg(
            self.params, self.cache, jnp.asarray(last),
            jnp.asarray(active), gamma + 1)
        toks = np.asarray(toks)
        self._spec_pending[session_id] = {"stacks": stacks, "pre": pre,
                                          "base_pos": meta.position,
                                          "gamma": gamma}
        self._pos_dirty = True      # device pos ran ahead of host truth
        return [int(t) for t in toks[idx, :gamma]]      # d_1..d_γ

    def spec_grade(self, session_id: str, tokens: List[int]) -> List[int]:
        """Verify role: consume ``tokens`` = [d_1..d_γ] teacher-forced in
        one fused forward and return the target-greedy continuation
        y_0..y_γ (y_t = greedy next after [.., ℓ, d_1..d_t]). Pending
        until ``spec_accept``."""
        gamma = len(tokens)
        idx, meta, last, active = self._spec_prologue(session_id, gamma)
        pre = [self._leaf_get(self.cache, p).copy()
               for p in self._spec_paths]
        forced = np.zeros((self.slots, gamma + 1), np.int32)
        forced[idx, 0] = meta.last_token
        forced[idx, 1:] = tokens
        self.cache, ys, stacks = self._spec_forced(
            self.params, self.cache, jnp.asarray(active),
            jnp.asarray(forced))
        ys = np.asarray(ys)
        self._spec_pending[session_id] = {"stacks": stacks, "pre": pre,
                                          "base_pos": meta.position,
                                          "gamma": gamma}
        self._pos_dirty = True
        return [int(t) for t in ys[idx]]                # y_0..y_γ

    def spec_accept(self, session_id: str, n_accept: int,
                    last_token: int) -> None:
        """Commit the longest agreeing prefix: restore the index-n
        snapshot (state after consuming ℓ, d_1..d_n), advance the host
        position by n+1 committed tokens, and make ``last_token`` (= y_n,
        the verifier's correction/extension) the new unconsumed token.
        n ∈ [0, γ]; n = γ accepts the whole round."""
        pend = self._spec_pending.pop(session_id)
        n = int(n_accept)
        if not (0 <= n <= pend["gamma"]):
            raise ValueError(
                f"n_accept {n} outside [0, {pend['gamma']}]")
        cache = self.cache
        for path, stacked in zip(self._spec_paths, pend["stacks"]):
            cache = self._leaf_set(cache, path, stacked[n])
        self.cache = cache
        idx = self._slot_map[session_id]
        meta = self._slots[idx]
        meta.position = pend["base_pos"] + n + 1
        meta.last_token = int(last_token)
        meta.tokens_generated += n + 1
        meta.last_used = next(self._use_clock)
        self._pos_dirty = True      # next round resyncs device pos

    def spec_abort(self, session_id: str) -> None:
        """Drop a pending round without committing anything: restore the
        pre-round snapshot of every destructive leaf (host position never
        advanced; device pos resyncs on the next round)."""
        pend = self._spec_pending.pop(session_id, None)
        if pend is not None:
            cache = self.cache
            for path, leaf in zip(self._spec_paths, pend["pre"]):
                cache = self._leaf_set(cache, path, leaf)
            self.cache = cache
        self._pos_dirty = True

    def override_last_token(self, session_id: str, token: int) -> None:
        """Re-point the slot's unconsumed token at an externally committed
        one. The draft half of a split session decodes the VERIFIER's
        token stream, not its own: after the draft-side prefill (and
        after every accepted round) the next token it must consume is
        whatever the verifier committed."""
        meta = self._slots[self._slot_map[session_id]]
        meta.last_token = int(token)

    def decode_round(self, steps: Optional[int] = None
                     ) -> Dict[str, Union[int, List[int]]]:
        """Continuous-batching decode for every active slot.

        ``steps=None`` — legacy single-step form: {session: token}.
        ``steps=K``    — fused K-step chunk: {session: [token, ...] * K},
        produced by ONE dispatch and ONE device→host transfer.
        """
        if not any(s is not None and not s.parked for s in self._slots):
            return {}
        k = 1 if steps is None else max(1, int(steps))
        route = None if self.adapters is None else self.adapters.route
        first = (k, route) not in self._compiled_chunks
        with span("engine.decode", steps=k, first=int(first)):
            out = self._decode_chunk(k, steps is None)
        self._compiled_chunks.add((k, route))
        return out

    def _decode_chunk(self, k: int, single: bool
                      ) -> Dict[str, Union[int, List[int]]]:
        with span("engine.decode.inputs"):
            last, active = self._decode_inputs(k)
        if self.adapters is not None:
            aidx = np.zeros(self.slots, np.int32)
            for i, s in enumerate(self._slots):
                if s is not None and s.adapter_id:
                    aidx[i] = self.adapters.index_of(s.adapter_id)
            self.cache, block = self._decode_fused_adp(
                self.params, self.cache, last, active, jnp.asarray(aidx),
                self.adapters.A, self.adapters.B, k, self.adapters.route)
        else:
            self.cache, block = self._decode_fused(
                self.params, self.cache, last, active, k)
        with span("engine.decode.wait"):
            block = np.asarray(block)                    # [slots, K]
        out: Dict[str, Union[int, List[int]]] = {}
        for i, s in enumerate(self._slots):
            if s is None or s.parked:
                continue
            s.last_token = int(block[i, -1])
            s.position += k
            s.tokens_generated += k
            s.last_used = next(self._use_clock)
            out[s.session_id] = (int(block[i, 0]) if single
                                 else [int(t) for t in block[i]])
        return out

    def _decode_inputs(self, k: int):
        """The chunk's device inputs: each slot's last token and whether it
        advances, after growing the block tables and resyncing the device
        positions where those may have drifted from the host's."""
        last = np.zeros(self.slots, np.int32)
        active = np.zeros(self.slots, bool)
        any_parked = False
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            if s.parked:
                any_parked = True
                continue
            last[i] = s.last_token
            active[i] = True
        if self.paged:
            # grow block tables BEFORE the fused chunk — the scan cannot
            # allocate mid-flight; under pressure this hibernates coldest
            # parked sessions or raises PagePoolExhausted
            for i, s in enumerate(self._slots):
                if s is not None and not s.parked:
                    self._ensure_pages(i, s.position + k)
        if self.paged or any_parked or self._pos_dirty:
            # resync device pos (and block table) from host truth: parked
            # rows' device pos advances inside the fused scan even though
            # their state is frozen
            pos_host = np.zeros(self.slots, np.int32)
            for i, s in enumerate(self._slots):
                if s is not None:
                    pos_host[i] = s.position
            cache = dict(self.cache)
            cache["pos"] = jnp.asarray(pos_host)
            if self.paged:
                cache["block"] = jnp.asarray(self._block_host)
            self.cache = cache
            self._pos_dirty = any_parked
        return jnp.asarray(last), jnp.asarray(active)

    # ------------------------------------------------------------------
    def serve(self, session_id: str, prompt_tokens: int, gen_tokens: int,
              *, prompt: Optional[np.ndarray] = None,
              chunk: int = 16, adapter_id: str = "") -> dict:
        """Unary convenience: prefill + chunked decode for one session.

        Synthetic prompts are crc32-seeded (NOT ``hash()``, which varies
        per process under PYTHONHASHSEED and would break reproducible
        traces and cross-process fingerprint checks)."""
        rng = np.random.default_rng(
            zlib.crc32(session_id.encode()) % 2**31)
        if prompt is None:
            prompt = rng.integers(0, self.cfg.vocab_size,
                                  size=prompt_tokens).astype(np.int32)
        t0 = time.perf_counter()
        pre = self.prefill_session(session_id, prompt,
                                   adapter_id=adapter_id)
        toks = [pre["first_token"]]
        remaining = gen_tokens - 1
        while remaining > 0:
            # pow2 chunk schedule: O(log chunk) compiled scan variants
            k = min(chunk, 1 << (remaining.bit_length() - 1))
            out = self.decode_round(steps=k)
            toks.extend(out[session_id])
            remaining -= k
        self.release_slot(session_id)
        total_ms = (time.perf_counter() - t0) * 1e3
        return {"tokens": toks, "ttfb_ms": pre["ttfb_ms"],
                "latency_ms": total_ms}
