"""Serving engines: prefill + KV-cache decode for any assigned arch.

Two engines share the model's cache layout contract:

  * ``ServingEngine`` — fixed-slot batches (seed behavior): every
    request is padded to the longest prompt and the whole batch drains
    before the next one starts.  The satellite tier serves small
    batches (latency/power bound); fine there.
  * ``ContinuousEngine`` — continuous batching driven by ONE *unified
    token-budget step*: every tick runs a mixed batch of (a) up to
    ``prefill_budget_tokens`` prefill-chunk tokens for admitting
    (PREFILLING) sequences and (b) one decode token per DECODING slot,
    so no tick runs more than ``budget + n_slots`` real tokens of
    model work (jit bucketing may round a chunk's executed width up to
    the next power of two — a constant per-engine factor, and exact
    for the default power-of-two budget) — a long arriving prompt can
    no longer stall in-flight decodes (or a contact pass's transmit
    lane) for its whole length.  Finished sequences are evicted
    immediately so queued arrivals join mid-flight instead of waiting
    for a batch to drain.

The continuous engine's KV memory comes in two layouts:

  * ``PagedSlotManager`` (default for dense/moe): a ``BlockAllocator``
    owns a global pool of fixed-size KV pages; each sequence holds a
    growable block table, so memory scales with
    ``sum_i ceil(len_i/page_size)`` instead of ``n_slots * max_seq`` and
    admission blocks on page exhaustion rather than slot count.
    Admission reserves the lifetime page budget but copies NOTHING:
    prompt chunks are written straight into incrementally allocated
    pages by ``models.transformer.prefill_chunk`` — the old
    whole-prompt prefill + template graft path is gone.
  * ``SlotManager`` (recurrent hybrid/ssm, and the memory baseline):
    one contiguous ``(n_slots, ..., max_seq, ...)`` cache row per slot.
    Recurrent prefix state integrates every input position, so these
    families keep monolithic prefill-at-admission (grafted into the
    slot row); their ticks are bounded by the family's fixed state
    size, not by prompt length chunking.

MoE serving prefill uses a *dynamic* per-chunk expert-capacity bound:
it starts near the mean load and doubles on overflow (reported through
the aux channel) until no routing is dropped — token-exact with the
static drop-free worst case (``C = G``) at a fraction of the dispatch
tensor size.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.launch.sharding import (SERVING_LOGICAL_MAP, paged_cache_pspecs,
                                   params_pspecs)
from repro.models import moe as M
from repro.models import transformer as T
from repro.models.pspec import mesh_rules, shard_count
from repro.serving.batching import Request, RequestQueue
from repro.serving.paging import (BlockAllocator, PagePrefixIndex,
                                  default_pool_pages, pages_for,
                                  per_device_pool_stats)

# Jitted engine callables shared across engine instances serving the
# same (hashable, frozen) ModelConfig: benchmark A/B replays and test
# sweeps construct many short-lived engines, and per-instance lambdas
# would recompile identical programs every time.  Keys carry the mesh
# FINGERPRINT alongside the config: a sharded engine's traces bake
# ``with_sharding_constraint`` ops into the jaxpr, so a sharded and an
# unsharded engine serving the same config must never share a callable.
_JIT_CACHE: Dict[tuple, object] = {}


def _cached_jit(key: tuple, make):
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = _JIT_CACHE[key] = make()
    return fn


def _mesh_fingerprint(mesh) -> Optional[tuple]:
    """Hashable identity of a mesh for jit-cache keys: axis names, axis
    sizes AND the concrete device ids — two meshes over different device
    subsets must not share compiled programs."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flat))


def _mesh_wrap(mesh, logical_map, fn):
    """Run ``fn`` with the engine's mesh rules installed, so the
    ``models.pspec.shard`` annotations inside the traced computation
    resolve against the serving mesh (trace-time; later calls hit the
    jit cache and the context is a cheap dict swap)."""
    if mesh is None:
        return fn

    def wrapped(*args, **kw):
        with mesh_rules(mesh, logical_map):
            return fn(*args, **kw)
    return wrapped


def _dynamic_capacity_prefill(prefill_fn, cfg: ModelConfig, n_tok: int):
    """Drop-free MoE prefill under a dynamic per-batch expert-capacity
    bound: start near the mean load and double on overflow until
    token-exact with the unbounded drop-free path.  ``prefill_fn(cap)``
    must return ``(logits, aux, cache)`` where aux counts overflowed
    routings (see ``moe.moe_fwd``); ``cap >= n_tok`` forces the exact
    drop-free worst case in ``moe_fwd``, so the loop always terminates
    with an exact result."""
    cap = M.initial_capacity(cfg, n_tok)
    while True:
        logits, aux, cache = prefill_fn(cap)
        if cap >= n_tok or float(aux) == 0.0:
            return logits, cache
        cap = min(cap * 2, n_tok)


def _graft(template: jax.Array, got: jax.Array) -> jax.Array:
    """Insert ``got`` into zeroed ``template`` along the (single) axis
    where their shapes differ (the cache sequence axis)."""
    if template.shape == got.shape:
        return got.astype(template.dtype)
    diff = [i for i, (a, b) in enumerate(zip(template.shape, got.shape))
            if a != b]
    assert len(diff) == 1, (template.shape, got.shape)
    return jax.lax.dynamic_update_slice_in_dim(
        template, got.astype(template.dtype), 0, axis=diff[0])


@dataclass
class GenerateResult:
    tokens: np.ndarray                 # (B, n_new)
    logits_last: np.ndarray            # (B, V) final-step logits
    prompt_logits: np.ndarray          # (B, V) last prompt-position logits


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_seq: int = 2048):
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self._prefill = _cached_jit(("fixed_prefill", cfg), lambda: jax.jit(
            lambda p, b: T.prefill(p, cfg, b)))
        self._prefill_cap = _cached_jit(("fixed_prefill_cap", cfg),
                                        lambda: jax.jit(
            lambda p, b, cap: T.forward(p, cfg, b, moe_drop_free=True,
                                        moe_capacity=cap, return_cache=True,
                                        remat=False),
            static_argnums=(2,)))
        self._decode = _cached_jit(("fixed_decode", cfg), lambda: jax.jit(
            lambda p, c, t, pos: T.decode_step(p, cfg, c, t, pos)))

    def _moe_prefill(self, batch):
        n_tok = int(np.prod(batch["tokens"].shape))
        logits, cache = _dynamic_capacity_prefill(
            lambda cap: self._prefill_cap(self.params, batch, cap),
            self.cfg, n_tok)
        return logits[:, -1:], cache

    @classmethod
    def init(cls, cfg: ModelConfig, seed: int = 0, max_seq: int = 2048):
        params = T.init_params(jax.random.PRNGKey(seed), cfg,
                               max_seq=max_seq)
        return cls(cfg, params, max_seq=max_seq)

    def full_cache(self, prompt_cache, batch: int):
        template = T.init_cache(self.cfg, batch, self.max_seq)
        return jax.tree.map(_graft, template, prompt_cache)

    def generate(self, tokens: np.ndarray, *, max_new: int = 16,
                 greedy: bool = True, extra_inputs: Optional[dict] = None,
                 seed: int = 0) -> GenerateResult:
        """tokens: (B, S_prompt) int32."""
        cfg = self.cfg
        B, S = tokens.shape
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
        if extra_inputs:
            batch.update({k: jnp.asarray(v) for k, v in extra_inputs.items()})
        if cfg.moe is not None:
            logits, cache = self._moe_prefill(batch)
        else:
            logits, cache = self._prefill(self.params, batch)
        cache = self.full_cache(cache, B)
        prompt_logits = np.asarray(logits[:, -1], np.float32)

        key = jax.random.PRNGKey(seed)
        pos = S
        if cfg.family == "vlm":
            pos = S + (extra_inputs or {}).get(
                "patch_embeds", np.zeros((B, 0, 1))).shape[1]
        out = np.empty((B, max_new), np.int32)
        cur_logits = logits[:, -1]
        for t in range(max_new):
            if greedy:
                nxt = jnp.argmax(cur_logits, axis=-1)
            else:
                key, sk = jax.random.split(key)
                nxt = jax.random.categorical(sk, cur_logits)
            out[:, t] = np.asarray(nxt)
            step_logits, cache = self._decode(
                self.params, cache, nxt[:, None].astype(jnp.int32),
                jnp.int32(pos + t))
            cur_logits = step_logits[:, 0]
        return GenerateResult(tokens=out,
                              logits_last=np.asarray(cur_logits, np.float32),
                              prompt_logits=prompt_logits)


# ==========================================================================
# continuous batching
# ==========================================================================

@dataclass
class RequestResult:
    rid: int
    tokens: np.ndarray                 # (n_new,) greedy continuation
    prompt_len: int
    admitted_step: int                 # engine clock at admission
    finished_step: int = 0
    first_token_step: int = 0          # clock when the prefill completed
    #                                    and the first token was emitted
    n_preemptions: int = 0             # times swapped out mid-decode
    logits_last: Optional[np.ndarray] = None   # (V,) final-step logits


# lifecycle phases of a slot-resident sequence: PREFILLING sequences are
# still streaming prompt chunks into the cache (no token emitted yet —
# they contribute prefill-chunk tokens to the unified step, not decode
# tokens); DECODING sequences step one token per tick.
PREFILLING = "prefill"
DECODING = "decode"


def chunk_bucket(n_tokens: int, max_seq: int) -> int:
    """Jit bucket for a prompt chunk of ``n_tokens`` real tokens: next
    power of two (floor 8), clamped to ``max_seq`` like the engine's
    ``_bucket_len``.  With a power-of-two budget >= 8 (the deployment
    default) the executed width never exceeds the budget itself."""
    b = 8
    while b < n_tokens:
        b *= 2
    return min(b, max_seq)


@dataclass
class _SlotState:
    request: Request
    pos: int                           # absolute position of the NEXT write
    next_tok: int                      # last emitted token (next decode input)
    emitted: List[int] = field(default_factory=list)
    admitted_step: int = 0
    first_token_step: int = 0          # clock at prefill completion
    phase: str = DECODING              # PREFILLING | DECODING
    n_preemptions: int = 0
    last_logits: Optional[np.ndarray] = None   # (V,) set at admission and
    #                                            finish (confidence routing)
    drafts: List[int] = field(default_factory=list)
    #                                  pending speculative draft tokens: the
    #                                  unified step verifies up to ``draft_k``
    #                                  of them in ONE prefill-chunk pass
    #                                  instead of stepping this slot's decode


class _SlotOccupancy:
    """Shared slot-occupancy bookkeeping for both cache layouts."""

    # -- occupancy ---------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.states) if s is None]

    def active_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.states) if s is not None]

    def decoding_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.states)
                if s is not None and s.phase == DECODING]

    def prefilling_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.states)
                if s is not None and s.phase == PREFILLING]

    def any_active(self) -> bool:
        return any(s is not None for s in self.states)

    # -- batched decode inputs --------------------------------------------
    def decode_inputs(self, skip=()):
        """(tokens (n_slots, 1) int32, pos (n_slots,) int32).  Inactive
        and PREFILLING slots — and ``skip`` slots, which already took a
        multi-token verify pass this tick — feed a dummy token at
        position 0 of a cache region no live sequence reads (their own
        private cache row here; the scratch page in the paged layout —
        ``block_tables`` maps non-decoding rows entirely to the scratch
        page), leaving live garbage there.  That is safe ONLY because
        admission rewrites positions [0, prefix) before the slot is
        read again and everything past a slot's ``kv_len`` is masked —
        any layout must preserve this overwrite-before-read
        guarantee."""
        toks = np.zeros((self.n_slots, 1), np.int32)
        pos = np.zeros((self.n_slots,), np.int32)
        for i, s in enumerate(self.states):
            if s is not None and s.phase == DECODING and i not in skip:
                toks[i, 0] = s.next_tok
                pos[i] = s.pos
        return toks, pos

    def kv_cache_stats(self) -> dict:
        leaves = jax.tree.leaves(self.cache)
        per_dev = 0
        n_shards = 1
        for l in leaves:
            itemsize = jnp.dtype(l.dtype).itemsize
            if hasattr(l, "sharding"):        # one device's slice of the leaf
                local = int(np.prod(l.sharding.shard_shape(l.shape)))
            else:
                local = l.size
            per_dev += local * itemsize
            n_shards = max(n_shards, l.size // max(local, 1))
        return {
            "kv_cache_bytes": int(sum(
                l.size * jnp.dtype(l.dtype).itemsize for l in leaves)),
            # per-device slice of the cache under the serving mesh (the
            # whole cache on a single device); n_kv_shards is the widest
            # shard factor across leaves — indivisible leaves replicate,
            # so per-device bytes may exceed global/n_kv_shards
            "kv_bytes_per_device": int(per_dev),
            "n_kv_shards": int(n_shards),
        }


class SlotManager(_SlotOccupancy):
    """Owns the contiguous multi-slot KV cache.

    The cache is ``models.transformer.init_cache(cfg, n_slots, max_seq)``
    — slot ``i`` is batch row ``i`` of every leaf.  Admission grafts a
    single-sequence prefix cache into a free slot; eviction just frees
    the slot id: stale keys/values beyond a new occupant's prefix are
    masked out by the per-slot ``kv_len`` until overwritten.
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, max_seq: int):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.cache = T.init_cache(cfg, n_slots, max_seq)
        self.states: List[Optional[_SlotState]] = [None] * n_slots
        self._graft = jax.jit(T.graft_slot_cache)
        self._template = None          # batch-1 cache, built on first snapshot
        self._extract = jax.jit(T.extract_slot_cache)

    # -- admission / eviction ----------------------------------------------
    def can_admit(self, req: Request) -> bool:
        return True                    # a free slot is the only resource

    def place(self, slot: int, prefix_cache, state: _SlotState) -> None:
        if self.states[slot] is not None:
            raise RuntimeError(f"slot {slot} occupied")
        self.cache = self._graft(self.cache, prefix_cache, jnp.int32(slot))
        self.states[slot] = state

    def evict(self, slot: int) -> None:
        self.states[slot] = None

    # -- preemption (snapshot / detach / restore) ---------------------------
    def snapshot(self, slot: int):
        """Host-side copy of slot ``slot``'s full cache row (the whole
        max_seq reservation, so restore needs no length bookkeeping)."""
        if self._template is None:
            self._template = T.init_cache(self.cfg, 1, self.max_seq)
        return jax.device_get(
            self._extract(self.cache, self._template, jnp.int32(slot)))

    def detach(self, slot: int, *, release_pages: bool = True) -> _SlotState:
        """Remove the slot's state without finishing it.  The contiguous
        row holds no pooled resource, so ``release_pages`` is a no-op."""
        st = self.states[slot]
        self.states[slot] = None
        return st

    def discard_detached(self, state: _SlotState) -> None:
        """Drop a detached sequence for good — no pooled resource to
        return in the contiguous layout."""

    def can_restore(self, state: _SlotState, spilled: bool) -> bool:
        return True

    def restore(self, slot: int, state: _SlotState, kv=None, *,
                spilled: bool = True) -> None:
        """Re-place a detached sequence; ``kv`` is a ``snapshot`` pytree
        (required here: the row may have been reused since detach)."""
        if self.states[slot] is not None:
            raise RuntimeError(f"slot {slot} occupied")
        if kv is None:
            raise RuntimeError("contiguous restore needs the KV snapshot")
        self.cache = self._graft(self.cache, jax.tree.map(jnp.asarray, kv),
                                 jnp.int32(slot))
        self.states[slot] = state

    def kv_cache_stats(self) -> dict:
        return {"kv_layout": "contiguous", **super().kv_cache_stats()}


@dataclass
class _PagedSlotState(_SlotState):
    pages: List[int] = field(default_factory=list)   # block table
    budget: int = 0                    # lifetime PRIVATE pages reserved
    #                                    (shared-attached pages cost no
    #                                    reservation — they are already
    #                                    in use elsewhere)
    synced_pages: int = 0              # leading pages bit-identical to the
    #                                    host spill store (KV-delta spills):
    #                                    decode writes lower the watermark,
    #                                    a spill/resume raises it
    shared_pages: int = 0              # leading pages attached by reference
    #                                    from the prefix index; a write into
    #                                    one forks a private copy first
    #                                    (copy-on-write) and lowers this


class PagedSlotManager(_SlotOccupancy):
    """Owns the paged KV pool and per-slot block tables.

    The cache is ``models.transformer.init_paged_cache(cfg, n_pages + 1,
    page_size)`` — page 0 is the scratch page inactive slots write to.
    Admission reserves a request's worst-case lifetime page count
    (``ceil((prompt + max_new - 1)/page_size)``) so neither prefill nor
    decode can ever stall mid-sequence, but allocates NO pages and
    copies NO cache: the sequence opens in the PREFILLING state and
    prompt chunks land directly in pages drawn chunk-by-chunk against
    the reservation (``grow_for_chunk``).  Decode grows the block table
    one page per ``page_size`` steps; eviction returns pages plus any
    unused reservation to the free list.  Stale KV in recycled pages
    beyond a slot's ``kv_len`` stays masked until overwritten — the
    same overwrite-before-read guarantee as the contiguous layout.
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, max_seq: int, *,
                 page_size: int = 16, pool_pages: Optional[int] = None,
                 prefix_cache: bool = False, mesh=None, logical_map=None):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.mesh = mesh
        if pool_pages is None:
            pool_pages = default_pool_pages(n_slots, max_seq, page_size)
        self.allocator = BlockAllocator(pool_pages)
        self.prefix_index = (PagePrefixIndex(self.allocator, page_size)
                             if prefix_cache else None)
        self.cow_copies = 0            # shared pages forked before a write
        self.prefill_positions_skipped = 0   # prompt positions attached by
        #                                      reference (never recomputed)
        self.max_bt = pages_for(max_seq, page_size)
        self.states: List[Optional[_PagedSlotState]] = [None] * n_slots
        if mesh is None:
            self.cache = T.init_paged_cache(cfg, pool_pages + 1, page_size)
            self._graft = jax.jit(T.graft_paged_cache)
            self._copy = jax.jit(T.copy_paged_pages)
        else:
            # place the pool: KV heads (MLA latent rank) over "model",
            # the layer/page/offset axes whole on every device — so the
            # extract gather below still device_gets a token-exact global
            # snapshot and graft scatters host pages back under GSPMD.
            # Each device makes its own share in place: a pool made whole
            # on the first device and then placed would sit there at once
            # in full, beyond that device's share
            def make():
                return T.init_paged_cache(cfg, pool_pages + 1, page_size)
            pool_sh = paged_cache_pspecs(mesh, cfg, jax.eval_shape(make),
                                         logical_map)
            self.cache = jax.jit(make, out_shardings=pool_sh)()
            # pin the output sharding of every pool-rewriting callable:
            # scatter sharding inference CAN keep the operand layout, but
            # pinning it makes resharding impossible rather than unlikely
            self._graft = jax.jit(T.graft_paged_cache, out_shardings=pool_sh)
            self._copy = jax.jit(T.copy_paged_pages, out_shardings=pool_sh)
        self._extract = jax.jit(T.extract_paged_cache)

    def _lifetime_pages(self, req: Request) -> int:
        return req.pages_needed(self.page_size)

    def _prefix_plan(self, req: Request):
        """(cached page ids to attach, resume position, private page
        budget) for admitting ``req``.  Attaches the longest indexed
        run of the prompt's leading FULL pages; prefill then resumes at
        the first uncovered position and is charged only for what it
        actually runs.  A fully covered prompt still re-runs its final
        position — the first emitted token needs that position's logits
        — which copy-on-writes the last shared page, budgeted as one
        extra private page."""
        lifetime = self._lifetime_pages(req)
        if self.prefix_index is None or req.prefill_pos:
            return [], req.prefill_pos, lifetime
        prompt = req.prompt
        pages = self.prefix_index.match(prompt)
        k = min(len(pages), len(prompt) // self.page_size)
        pages = pages[:k]
        if k and k * self.page_size == len(prompt):
            return pages, len(prompt) - 1, lifetime - k + 1
        return pages, k * self.page_size, lifetime - k

    # -- admission / eviction ----------------------------------------------
    def can_admit(self, req: Request) -> bool:
        _, _, budget = self._prefix_plan(req)
        if self.allocator.can_reserve(budget):
            return True
        # index-only pages (refcount 1) are reclaimable: admission may
        # evict cached prefixes rather than block behind them
        return (self.prefix_index is not None
                and self.allocator.available()
                + self.prefix_index.reclaimable() >= budget)

    def fits_pool(self, req: Request) -> bool:
        """Whether the request could EVER be admitted (pool capacity)."""
        return self._lifetime_pages(req) <= self.allocator.n_pages

    def place_prefilling(self, slot: int, req: Request, clock: int) -> None:
        """Open ``slot`` in the PREFILLING state: reserve the request's
        worst-case lifetime budget of PRIVATE pages (admission control
        is unchanged when nothing is shared) but allocate nothing —
        prompt chunks allocate their pages as they land
        (``grow_for_chunk``), and no prefix cache is ever grafted.
        With a prefix index, cache-hit pages attach by reference: the
        model work for the covered positions is skipped outright
        (``Request.prefill_pos`` opens past them, so the unified step
        charges 0 prefill tokens for them) and the shared pages cost no
        reservation."""
        if self.states[slot] is not None:
            raise RuntimeError(f"slot {slot} occupied")
        pages, resume, budget = self._prefix_plan(req)
        if not self.allocator.can_reserve(budget) and self.prefix_index:
            self.prefix_index.evict(budget - self.allocator.available())
        self.allocator.reserve(budget)
        self.allocator.share(pages)
        if self.prefix_index is not None and not req.prefill_pos:
            self.prefix_index.note_attach(len(pages))
        if pages:
            self.prefill_positions_skipped += resume
        req.prefill_pos = resume
        self.states[slot] = _PagedSlotState(
            request=req, pos=resume, next_tok=0,
            admitted_step=clock, phase=PREFILLING, pages=list(pages),
            budget=budget, synced_pages=len(pages),
            shared_pages=len(pages))

    def _fork_shared(self, slot: int, first_write: int) -> None:
        """Copy-on-write: before ``slot`` writes into page
        ``first_write``, give it private copies of every shared page
        from there on (in practice only the last shared page, when a
        fully covered prompt re-runs its final position).  A page still
        referenced elsewhere is duplicated device-side
        (``copy_paged_pages``) into a page drawn from the slot's own
        reservation and this sequence's reference on the original is
        dropped; a page nobody else holds any more is simply
        reclassified as private."""
        st = self.states[slot]
        if first_write >= st.shared_pages:
            return
        for d in range(first_write, st.shared_pages):
            old = st.pages[d]
            if self.allocator.refcount(old) > 1:
                new = self.allocator.alloc(1)[0]
                self.cache = self._copy(self.cache,
                                        jnp.asarray([old], jnp.int32),
                                        jnp.asarray([new], jnp.int32))
                st.pages[d] = new
                self.allocator.release([old])
                self.cow_copies += 1
        st.shared_pages = first_write
        st.synced_pages = min(st.synced_pages, first_write)

    def grow_for_chunk(self, slot: int, n_positions: int) -> None:
        """Allocate pages (against the admission reservation) so the
        slot's block table covers prompt positions [0, n_positions),
        forking any shared page the chunk would write into
        (copy-on-write), and lower the ``synced_pages`` watermark to
        the first page this chunk writes — those pages now diverge from
        any host spill copy."""
        st = self.states[slot]
        first_write = st.pos // self.page_size
        self._fork_shared(slot, first_write)
        while len(st.pages) * self.page_size < n_positions:
            st.pages.extend(self.allocator.alloc(1))
        st.synced_pages = min(st.synced_pages, first_write)

    def note_prefill_complete(self, slot: int) -> None:
        """Register the sequence's IMMUTABLE prompt pages (fully covered
        by the prompt — decode never writes into them) in the prefix
        index, so later requests sharing the prefix attach them by
        reference instead of recomputing."""
        if self.prefix_index is None:
            return
        st = self.states[slot]
        prompt = st.request.prompt
        self.prefix_index.insert(prompt,
                                 st.pages[:len(prompt) // self.page_size])

    def evict(self, slot: int) -> None:
        st = self.states[slot]
        n_private = len(st.pages) - st.shared_pages
        self.allocator.release(st.pages,
                               unreserve=st.budget - n_private)
        self.states[slot] = None

    # -- preemption (snapshot / detach / restore) ---------------------------
    def snapshot(self, slot: int, since: int = 0):
        """Host-side copy of the slot's live pages as a prefix-shaped
        pytree (leaves (L, 1, n_pages * page_size, ...)) — the
        ``extract_paged_cache`` inverse of the admission graft, so
        restore round-trips bit-exactly through ``graft_paged_cache``.
        ``since`` skips the first ``since`` (clean) pages — the KV-delta
        spill path, which ships only pages dirtied since the last spill.
        Returns None when there is nothing newer than ``since``.  The
        slice happens host-side so the jitted gather is keyed only on
        the delta's page count, not on (table length, since) pairs."""
        st = self.states[slot]
        if since >= len(st.pages):
            return None
        return jax.device_get(
            self._extract(self.cache,
                          jnp.asarray(st.pages[since:], jnp.int32)))

    def snapshot_state(self, state: _PagedSlotState):
        """Host-side copy of a DETACHED-but-resident sequence's pages
        (a resident swap entry at checkpoint time — its pages are still
        committed in the pool but it owns no slot).  None when the
        sequence holds no pages yet."""
        if not state.pages:
            return None
        return jax.device_get(
            self._extract(self.cache, jnp.asarray(state.pages, jnp.int32)))

    def detach(self, slot: int, *, release_pages: bool) -> _PagedSlotState:
        """Remove the slot's state without finishing it.  With
        ``release_pages`` (spill preemption) the sequence's PRIVATE
        pages and its unused reservation go back to the pool —
        reclaimable by waiting requests — and the caller must hold a
        ``snapshot`` of them; shared-prefix pages keep this sequence's
        reference (they are pinned in the pool, never spilled, and cost
        nothing to re-attach at resume).  Without (resident preemption)
        everything stays committed and restore is free."""
        st = self.states[slot]
        self.states[slot] = None
        if release_pages:
            private = st.pages[st.shared_pages:]
            self.allocator.release(private,
                                   unreserve=st.budget - len(private))
            st.pages = st.pages[:st.shared_pages]
        return st

    def discard_detached(self, state: _PagedSlotState) -> None:
        """Drop a detached (spilled) sequence without resuming it — the
        redo-from-prefill path.  Releases the shared-prefix references
        the spill kept pinned; private pages and reservation were
        already returned at detach."""
        if state.pages:
            self.allocator.release(state.pages)
            state.pages = []
        state.shared_pages = 0
        state.synced_pages = 0

    def can_restore(self, state: _PagedSlotState, spilled: bool) -> bool:
        """Spilled sequences re-reserve their full lifetime budget, so a
        restore can never stall mid-decode once admitted — the same
        discipline as first admission."""
        return (not spilled) or self.allocator.can_reserve(state.budget)

    def restore(self, slot: int, state: _PagedSlotState, kv=None, *,
                spilled: bool = True) -> None:
        """Re-place a detached sequence.  ``spilled`` re-reserves the
        private lifetime budget (the detach released it); ``kv`` is the
        host snapshot of the PRIVATE pages, grafted into freshly
        allocated ones appended after the still-attached shared prefix
        — None for a resident swap, or for a sequence preempted before
        its first private page landed (nothing to restore: chunks
        redo)."""
        if self.states[slot] is not None:
            raise RuntimeError(f"slot {slot} occupied")
        if spilled:
            self.allocator.reserve(state.budget)
            if kv is not None:                 # realloc + graft back
                leaf = jax.tree.leaves(kv)[0]
                n = leaf.shape[2] // self.page_size
                new = self.allocator.alloc(n)
                state.pages.extend(new)
                self.cache = self._graft(self.cache,
                                         jax.tree.map(jnp.asarray, kv),
                                         jnp.asarray(new, jnp.int32))
        self.states[slot] = state

    # -- paged decode plumbing ---------------------------------------------
    def ensure_write_pages(self, skip=()) -> None:
        """Grow each active slot's block table to cover its next write
        position.  Draws on the reservation made at admission, so it
        cannot fail mid-sequence.  Also lowers the slot's ``synced_pages``
        watermark to the page this tick writes into — that page now
        diverges from any host spill copy, so the next spill must ship
        it again (everything below the watermark stays delta-exempt).
        PREFILLING slots — and ``skip`` slots, whose verify pass grew
        its own pages through ``grow_for_chunk`` — are skipped: their
        pages grow chunk-by-chunk.  A write landing in a shared page
        forks a private copy first (copy-on-write) — no decode write
        ever touches a page another holder can read."""
        for slot, st in enumerate(self.states):
            if st is None or st.phase != DECODING or slot in skip:
                continue
            self._fork_shared(slot, st.pos // self.page_size)
            while len(st.pages) <= st.pos // self.page_size:
                st.pages.extend(self.allocator.alloc(1))
            st.synced_pages = min(st.synced_pages, st.pos // self.page_size)

    def block_tables(self, skip=()) -> np.ndarray:
        """(n_slots, max_bt) int32 page ids for the DECODE sub-batch;
        unused entries — and whole rows of inactive, PREFILLING or
        ``skip`` slots, whose dummy decode write must not touch their
        real pages — point at the scratch page 0."""
        bt = np.zeros((self.n_slots, self.max_bt), np.int32)
        for i, st in enumerate(self.states):
            if st is not None and st.phase == DECODING and i not in skip:
                bt[i, :len(st.pages)] = st.pages
        return bt

    def chunk_block_table(self, slot: int) -> np.ndarray:
        """(1, max_bt) int32 — the single-sequence block table a prefill
        chunk writes through (unused entries at the scratch page)."""
        bt = np.zeros((1, self.max_bt), np.int32)
        pages = self.states[slot].pages
        bt[0, :len(pages)] = pages
        return bt

    def kv_cache_stats(self) -> dict:
        a = self.allocator
        base = super().kv_cache_stats()
        return {
            "kv_layout": "paged",
            "page_size": self.page_size,
            "pool_pages": a.n_pages,
            "peak_pages_in_use": a.peak_in_use,
            "peak_pages_committed": a.peak_committed,
            "page_pool_utilization": round(a.utilization(), 4),
            "cow_page_copies": self.cow_copies,
            "prefill_positions_skipped": self.prefill_positions_skipped,
            **(self.prefix_index.stats()
               if self.prefix_index is not None else {}),
            **base,
            # per-device ledger view: the page axes are never sharded, so
            # every device's allocator state IS the global ledger
            **per_device_pool_stats(
                a, n_shards=base["n_kv_shards"],
                kv_bytes_per_device=base["kv_bytes_per_device"]),
        }


class ContinuousEngine:
    """Continuous-batching greedy decoding under one unified
    token-budget step.

    Supported families: dense / moe (incl. MLA) / hybrid / ssm.  vlm and
    audio need per-request side inputs (patch embeds, encoder frames)
    and are served by the fixed-slot engine.

    Paged families (dense/moe) admit through CHUNKED prefill: an
    admitted sequence opens in the PREFILLING state and every tick
    spends up to ``prefill_budget_tokens`` prompt tokens across the
    PREFILLING slots (FIFO by admission, at most one chunk per slot per
    tick), written straight into incrementally allocated KV pages by
    ``models.transformer.prefill_chunk`` — no whole-prompt forward, no
    prefix-cache graft.  Chunk shapes are bucketed (next power of two,
    floor 8, capped at max_seq) so the jitted chunk step hits a handful
    of compiled shapes; pad positions write to the scratch page and are
    masked out.  The budget counts REAL prompt tokens — the executed
    width is the bucket, so each chunk may round up to the floor/next
    power of two; with a power-of-two budget >= 8 (the default) a
    chunk's width never exceeds the budget itself.
    ``prefill_budget_tokens=None`` removes the bound (each prompt lands
    as one chunk — the monolithic comparator the benchmark gates
    against).  Recurrent families (hybrid/ssm, always contiguous)
    prefill monolithically at the exact prompt length — their prefix
    state integrates every input position, so chunking or padding would
    change it.

    kv_layout: "paged" (default for dense/moe via "auto") pools KV in
    fixed-size pages with per-sequence block tables — admission then
    blocks on page-pool exhaustion instead of slot count; "contiguous"
    reserves a full max_seq row per slot (always used for the
    fixed-size recurrent state of hybrid/ssm).  page_size / pool_pages
    are the paged pool's sizing knobs (pool_pages defaults to 75% of
    the contiguous layout's positions; see ``paging.default_pool_pages``).

    prefix_cache=True (paged only) turns on prefix sharing: a
    ``paging.PagePrefixIndex`` keeps finished prompts' immutable full
    pages alive in the pool, admission attaches matching leading pages
    by REFERENCE (refcounted — shared pages are not double-budgeted)
    and skips the model work for the covered positions entirely (they
    charge 0 tokens against the unified step's prefill budget; a fully
    covered prompt still re-runs its final position for the first
    token's logits, copy-on-write-forking the last shared page).
    Token-exact with prefix_cache=False: cached pages hold exactly the
    KV the skipped chunks would have recomputed.

    Speculative draft verification (paged layouts): a DECODING slot
    holding pending draft tokens (attached via ``attach_drafts`` or a
    ``Request.draft_toks`` stream) verifies up to ``draft_k`` of them
    in ONE ``prefill_chunk`` pass instead of taking that tick's decode
    step — the chunk runs ``[next_tok, d_1..d_k]`` at the slot's
    current position, the per-position argmaxes give the longest
    agreeing draft prefix, and the first disagreeing position's argmax
    is the correction token, so the emitted stream is token-for-token
    identical to plain greedy decode whatever the drafts were.
    Rejected draft positions leave stale KV beyond the slot's
    ``kv_len``, which the same masking that recycles pages already
    hides — rollback is free.

    ``last_tick_prefill_tokens`` / ``last_tick_decode_tokens`` /
    ``last_tick_verify_tokens`` expose the unified step's per-tick
    token accounting (prefill tokens spent; decoding slots stepped;
    draft+input tokens verified) — the benchmark and the property
    suite gate ``prefill <= budget`` and ``decode <= n_slots`` on
    them (verify adds at most ``n_slots * (draft_k + 1)``).
    ``counters()`` snapshots the cumulative work counters the tick
    keeps beside ``prefill_tokens_total``: decode rows run against rows
    holding a sequence, live KV pages against the pages the kernel's
    grid visits, padded chunk width against real prompt tokens.

    Under a ``jax.profiler`` session each ``step`` is an ``engine.step``
    span and each blocking device-to-host read of its tokens (and
    final logits) an ``engine.read`` span, on the device trace's clock;
    without a session the spans cost a few no-op calls a tick.
    """

    FAMILIES = ("dense", "moe", "hybrid", "ssm")
    PAGED_FAMILIES = ("dense", "moe")

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 4,
                 max_seq: int = 2048, queue_capacity: Optional[int] = None,
                 kv_layout: str = "auto", page_size: int = 16,
                 pool_pages: Optional[int] = None,
                 prefill_budget_tokens: Optional[int] = 64,
                 prefix_cache: bool = False, draft_k: int = 8,
                 mesh=None, logical_map=None):
        if cfg.family not in self.FAMILIES:
            raise NotImplementedError(
                f"ContinuousEngine does not serve family {cfg.family!r}")
        if kv_layout not in ("auto", "paged", "contiguous"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if draft_k < 1:
            raise ValueError("draft_k must be >= 1 (max draft tokens "
                             "verified per slot per tick)")
        if kv_layout == "auto":
            kv_layout = ("paged" if cfg.family in self.PAGED_FAMILIES
                         else "contiguous")
        if prefill_budget_tokens is not None and prefill_budget_tokens < 1:
            raise ValueError("prefill_budget_tokens must be >= 1 (or None "
                             "for an unbounded, monolithic-style tick)")
        if prefix_cache and kv_layout != "paged":
            raise ValueError("prefix_cache needs the paged KV layout "
                             "(sharing is page-granular)")
        if mesh is not None and kv_layout != "paged":
            raise ValueError("mesh serving shards the paged KV pool — "
                             "contiguous/recurrent layouts are unsharded")
        self.cfg = cfg
        self.mesh = mesh
        self.logical_map = (dict(logical_map or SERVING_LOGICAL_MAP)
                            if mesh is not None else None)
        mkey = _mesh_fingerprint(mesh)
        if mesh is not None:
            # tensor-parallel placement: attention/FFN weights split over
            # "model", experts expert-parallel, everything else replicated
            # (k/v projections too where the KV heads do not split)
            params = jax.device_put(
                params, params_pspecs(mesh, params, self.logical_map, cfg))
        self.params = params
        self.max_seq = max_seq
        self.kv_layout = kv_layout
        self.prefill_budget_tokens = prefill_budget_tokens
        wrap = lambda fn: _mesh_wrap(mesh, self.logical_map, fn)  # noqa: E731
        if kv_layout == "paged":
            self.slots = PagedSlotManager(cfg, n_slots, max_seq,
                                          page_size=page_size,
                                          pool_pages=pool_pages,
                                          prefix_cache=prefix_cache,
                                          mesh=mesh,
                                          logical_map=self.logical_map)
            # named functions name the programs in a device trace
            # (``jit_decode_step``, ``jit_prefill_chunk``, ``jit_prefill``)
            def decode_step(p, c, t, pos, bt):
                return T.decode_step(p, cfg, c, t, pos, block_tables=bt)

            def prefill_chunk(p, c, t, nv, off, bt, cap):
                return T.prefill_chunk(p, cfg, c, t, nv, off, bt,
                                       moe_capacity=cap)

            self._decode = _cached_jit(
                ("cont_decode_paged", cfg, mkey),
                lambda: wrap(jax.jit(decode_step)))
            self._chunk = _cached_jit(
                ("prefill_chunk", cfg, mkey),
                lambda: wrap(jax.jit(prefill_chunk, static_argnums=(6,))))
        else:
            self.slots = SlotManager(cfg, n_slots, max_seq)

            def decode_step(p, c, t, pos):
                return T.decode_step(p, cfg, c, t, pos)

            self._decode = _cached_jit(
                ("cont_decode", cfg, mkey),
                lambda: wrap(jax.jit(decode_step)))
        self.queue = RequestQueue(max_batch=n_slots,
                                  capacity=queue_capacity)
        self.draft_k = draft_k
        self.clock = 0                        # unified-step ticks
        self.finish_order: List[int] = []
        self.results: Dict[int, RequestResult] = {}
        self.last_tick_prefill_tokens = 0
        self.last_tick_decode_tokens = 0
        self.last_tick_verify_tokens = 0
        self.prefill_tokens_total = 0         # prompt tokens actually run
        #                                       (prefix-cache hits charge 0)
        self.chunk_bucket_tokens_total = 0    # padded width of their chunks
        self.decode_rows_total = 0            # decode rows run (n_slots each)
        self.decode_tokens_total = 0          # rows holding a decoding seq
        self.decode_live_pages_total = 0      # pages those rows read (paged)
        self.decode_grid_pages_total = 0      # block-table entries
        self.spec_verify_passes = 0           # one-chunk draft verifications
        self.spec_drafted_total = 0           # draft tokens verified
        self.spec_accepted_total = 0          # draft tokens accepted
        self.spec_draft_streams_dropped = 0   # streams whose first draft
        #                                       disagreed with the prefill
        self._spent_this_tick = 0
        self._verify_this_tick = 0
        self._tick_budget_left = self._budget()

        def prefill(p, t, cap):
            return T.forward(p, cfg, {"tokens": t}, moe_drop_free=True,
                             moe_capacity=cap, return_cache=True,
                             remat=False)

        self._prefill = _cached_jit(
            ("cont_prefill", cfg, mkey),
            lambda: wrap(jax.jit(prefill, static_argnums=(2,))))

    def clone_fresh(self) -> "ContinuousEngine":
        """A new engine with the same config/params/layout knobs and
        EMPTY serving state — the reboot path: device KV, slots, queue
        and results do not survive a crash; only a host checkpoint does
        (``serving.scheduler.PreemptiveScheduler.restore``).  Jitted
        callables come from the module cache, so this is cheap."""
        kw = dict(n_slots=self.slots.n_slots, max_seq=self.max_seq,
                  queue_capacity=self.queue.capacity,
                  kv_layout=self.kv_layout,
                  prefill_budget_tokens=self.prefill_budget_tokens,
                  draft_k=self.draft_k,
                  mesh=self.mesh, logical_map=self.logical_map)
        if self.kv_layout == "paged":
            kw.update(page_size=self.slots.page_size,
                      pool_pages=self.slots.allocator.n_pages,
                      prefix_cache=self.slots.prefix_index is not None)
        return ContinuousEngine(self.cfg, self.params, **kw)

    def _budget(self):
        b = self.prefill_budget_tokens
        return float("inf") if b is None else b

    @classmethod
    def init(cls, cfg: ModelConfig, seed: int = 0, **kw):
        params = T.init_params(jax.random.PRNGKey(seed), cfg,
                               max_seq=kw.get("max_seq", 2048))
        return cls(cfg, params, **kw)

    # -- admission ---------------------------------------------------------
    def submit(self, req: Request) -> int:
        if req.max_new < 1:
            raise ValueError(
                f"request {req.rid}: max_new must be >= 1 "
                "(the prefill always emits one token)")
        if len(req.prompt) + req.max_new > self.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + max_new "
                f"{req.max_new} exceeds max_seq {self.max_seq}")
        if self.kv_layout == "paged" and not self.slots.fits_pool(req):
            raise ValueError(
                f"request {req.rid}: needs more KV pages than the whole "
                f"pool ({self.slots.allocator.n_pages} x "
                f"{self.slots.page_size}) — raise pool_pages")
        if req.draft_toks is not None:
            d = np.asarray(req.draft_toks)
            if d.ndim != 1:
                raise ValueError(
                    f"request {req.rid}: draft_toks must be 1-D token ids, "
                    f"got shape {d.shape}")
            req.draft_toks = d.astype(np.int32)
        return self.queue.submit(req)

    def _bucket_len(self, S: int) -> int:
        if self.cfg.family in ("hybrid", "ssm"):
            return S                          # recurrent state is length-exact
        b = 8
        while b < S:
            b *= 2
        return min(b, self.max_seq)

    def _run_prefill(self, toks: np.ndarray):
        """Drop-free prefill; MoE archs use the dynamic per-batch
        expert-capacity bound (``_dynamic_capacity_prefill``)."""
        toks = jnp.asarray(toks)
        if self.cfg.moe is None:
            logits, _, pcache = self._prefill(self.params, toks, None)
            return logits, pcache
        return _dynamic_capacity_prefill(
            lambda cap: self._prefill(self.params, toks, cap),
            self.cfg, int(toks.size))

    def _admit(self, req: Request, slot: int) -> None:
        """Place ``req`` into ``slot``.  Paged layouts open the slot in
        the PREFILLING state and immediately spend whatever remains of
        this tick's prefill budget on its first chunk(s); contiguous
        layouts (recurrent families and the memory baseline) keep the
        monolithic prefill + slot graft."""
        if self.kv_layout == "paged":
            self.slots.place_prefilling(slot, req, self.clock)
            self._pump_prefill(slot)
            return
        S = len(req.prompt)
        bucket = self._bucket_len(S)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :S] = req.prompt
        logits, pcache = self._run_prefill(toks)
        with jax.profiler.TraceAnnotation("engine.read"):
            first = int(jnp.argmax(logits[0, S - 1]))
            last = np.asarray(logits[0, S - 1], np.float32)
        st = _SlotState(request=req, pos=S, next_tok=first, emitted=[first],
                        admitted_step=self.clock,
                        first_token_step=self.clock, last_logits=last)
        self.slots.place(slot, pcache, st)
        if len(st.emitted) >= req.max_new:    # max_new == 1: done at prefill
            self._finish(slot)

    # -- chunked prefill (paged layout) -------------------------------------
    def _run_chunk(self, toks: np.ndarray, n_valid: int, pos_offset: int,
                   bt: np.ndarray):
        """One jitted chunk forward; MoE archs run the dynamic
        per-chunk expert-capacity doubling loop (token-exact with the
        unbounded drop-free path on success)."""
        args = (jnp.asarray(toks), jnp.int32(n_valid), jnp.int32(pos_offset),
                jnp.asarray(bt))
        if self.cfg.moe is None:
            logits, _, cache = self._chunk(self.params, self.slots.cache,
                                           *args, None)
            return logits, cache
        return _dynamic_capacity_prefill(
            lambda cap: self._chunk(self.params, self.slots.cache, *args, cap),
            self.cfg, int(toks.size))

    def _pump_prefill(self, slot: int) -> None:
        """Spend the tick's remaining prefill-token budget streaming
        prompt chunks of ``slot``'s PREFILLING sequence into its pages.
        When the last chunk lands the sequence emits its first token
        and flips to DECODING (joining this very tick's decode batch,
        or finishing outright when ``max_new == 1``)."""
        st = self.slots.states[slot]
        req = st.request
        S = len(req.prompt)
        while st.phase == PREFILLING and self._tick_budget_left > 0:
            off = req.prefill_pos
            C = int(min(self._tick_budget_left, S - off))
            Cb = chunk_bucket(C, self.max_seq)
            toks = np.zeros((1, Cb), np.int32)
            toks[0, :C] = req.prompt[off:off + C]
            self.slots.grow_for_chunk(slot, off + C)
            logits, self.slots.cache = self._run_chunk(
                toks, C, off, self.slots.chunk_block_table(slot))
            req.prefill_pos = off + C
            st.pos = off + C
            self._tick_budget_left -= C
            self._spent_this_tick += C
            self.prefill_tokens_total += C
            self.chunk_bucket_tokens_total += Cb
            if req.prefill_pos >= S:
                with jax.profiler.TraceAnnotation("engine.read"):
                    first = int(jnp.argmax(logits[0, C - 1]))
                    st.last_logits = np.asarray(logits[0, C - 1], np.float32)
                st.phase = DECODING
                st.next_tok = first
                st.emitted = [first]
                st.first_token_step = self.clock
                self.slots.note_prefill_complete(slot)
                if len(st.emitted) >= req.max_new:
                    self._finish(slot)
                elif req.draft_toks is not None and len(req.draft_toks):
                    # a draft stream rides the request (the satellite
                    # tier's answer): its head must reproduce the
                    # prefill token or the whole stream is stale
                    if int(req.draft_toks[0]) == first:
                        self.attach_drafts(slot, req.draft_toks[1:])
                    else:
                        self.spec_draft_streams_dropped += 1

    # -- speculative draft verification (paged layout) ----------------------
    def attach_drafts(self, slot: int, draft_toks) -> int:
        """Queue draft tokens on a DECODING slot for one-pass
        verification by the unified step.  Clamped so drafts that could
        never be emitted (the slot needs one free position for the
        correction/bonus token) are dropped HERE, before any verify
        pass runs or any ledger meters them.  Returns the number
        actually queued (0 under the contiguous layout, which has no
        chunk machinery to verify through — plain decode proceeds)."""
        st = self.slots.states[slot]
        if st is None or st.phase != DECODING:
            raise RuntimeError(
                f"slot {slot}: drafts need a DECODING occupant")
        if self.kv_layout != "paged":
            return 0
        rem = st.request.max_new - len(st.emitted)
        take = max(0, min(len(draft_toks), rem - 1 - len(st.drafts)))
        st.drafts.extend(int(t) for t in draft_toks[:take])
        return take

    def _verify_slot(self, slot: int) -> bool:
        """Verify up to ``draft_k`` of the slot's pending draft tokens
        in ONE prefill-chunk pass: run ``[next_tok, d_1..d_k]`` at the
        slot's current position (their KV lands in pages drawn from the
        admission reservation, exactly like a prompt chunk), accept the
        longest prefix of drafts agreeing with the per-position
        argmaxes and emit the first disagreeing position's argmax as
        the correction (or bonus) token — token-for-token identical to
        ``n_ok + 1`` plain greedy decode steps.  KV written for
        rejected positions sits beyond the slot's new ``kv_len`` and is
        masked until overwritten, so no rollback copy is needed.
        Returns False when there is no room left to speculate (the
        drafts are dropped and plain decode emits the final token)."""
        st = self.slots.states[slot]
        req = st.request
        rem = req.max_new - len(st.emitted)
        k = min(len(st.drafts), self.draft_k, rem - 1)
        if k <= 0:
            st.drafts = []
            return False
        C = k + 1
        Cb = chunk_bucket(C, self.max_seq)
        toks = np.zeros((1, Cb), np.int32)
        toks[0, 0] = st.next_tok
        toks[0, 1:C] = st.drafts[:k]
        self.slots.grow_for_chunk(slot, st.pos + C)
        logits, self.slots.cache = self._run_chunk(
            toks, C, st.pos, self.slots.chunk_block_table(slot))
        with jax.profiler.TraceAnnotation("engine.read"):
            preds = np.asarray(jnp.argmax(logits[0, :C], -1))
        n_ok = 0
        while n_ok < k and int(preds[n_ok]) == st.drafts[n_ok]:
            n_ok += 1
        out = st.drafts[:n_ok] + [int(preds[n_ok])]
        rest = st.drafts[k:]
        # leftover drafts (stream longer than draft_k) survive only a
        # full acceptance whose bonus token matches their head — any
        # disagreement makes the rest of the stream stale
        st.drafts = (rest[1:] if n_ok == k and rest and rest[0] == out[-1]
                     else [])
        st.emitted.extend(out)
        st.pos += n_ok + 1
        st.next_tok = out[-1]
        self.spec_verify_passes += 1
        self.spec_drafted_total += k
        self.spec_accepted_total += n_ok
        self._verify_this_tick += C
        if len(st.emitted) >= req.max_new:
            with jax.profiler.TraceAnnotation("engine.read"):
                st.last_logits = np.asarray(logits[0, n_ok], np.float32)
            self._finish(slot)
        return True

    def _verify_pending(self) -> set:
        """Run the draft-verify pass for every DECODING slot holding
        pending drafts; returns the slots that advanced (they sit out
        this tick's batched decode — their tokens already landed)."""
        verified = set()
        if self.kv_layout != "paged":
            return verified
        for slot in self.slots.decoding_slots():
            if self.slots.states[slot].drafts and self._verify_slot(slot):
                verified.add(slot)
        return verified

    def spec_stats(self) -> dict:
        """Speculative-verification counters (cumulative)."""
        return {"draft_k": self.draft_k,
                "verify_passes": self.spec_verify_passes,
                "drafted": self.spec_drafted_total,
                "accepted": self.spec_accepted_total,
                "draft_streams_dropped": self.spec_draft_streams_dropped}

    COUNTERS = ("prefill_tokens_total", "chunk_bucket_tokens_total",
                "decode_rows_total", "decode_tokens_total",
                "decode_live_pages_total", "decode_grid_pages_total")

    def counters(self) -> Dict[str, int]:
        """Snapshot of the cumulative work counters: prompt tokens run
        and the padded chunk width they ran in; the rows decode launches
        ran (``n_slots`` each), the rows holding a decoding sequence, the
        KV pages those rows read and the pages the paged kernel's grid
        visits (both 0 under the contiguous layout).  Observability,
        not serving state: of these the scheduler's checkpoint carries
        only ``prefill_tokens_total``, and ``clone_fresh`` starts every
        one at 0.  So a ratio of two counters' growth holds only over a
        window that lies wholly after the last restore."""
        return {k: int(getattr(self, k)) for k in self.COUNTERS}

    def _finish(self, slot: int) -> None:
        st = self.slots.states[slot]
        req = st.request
        self.results[req.rid] = RequestResult(
            rid=req.rid, tokens=np.asarray(st.emitted, np.int32),
            prompt_len=len(req.prompt), admitted_step=st.admitted_step,
            finished_step=self.clock, first_token_step=st.first_token_step,
            n_preemptions=st.n_preemptions,
            logits_last=st.last_logits)
        self.finish_order.append(req.rid)
        self.slots.evict(slot)

    # -- the serve loop ----------------------------------------------------
    def _admit_arrivals(self) -> None:
        """Admit arrived requests (FIFO) into free slots.  Paged layout:
        admission additionally blocks while the page pool cannot cover
        the head request's worst-case lifetime — eviction returns pages,
        so the head is admitted once enough earlier sequences finish."""
        for slot in self.slots.free_slots():
            req = self.queue.peek()
            if req is None or req.arrival_t > self.clock:
                break
            if not self.slots.can_admit(req):
                break                         # page pool exhausted: wait
            self._admit(self.queue.pop(), slot)

    def _prefilling_order(self) -> List[int]:
        """PREFILLING slots in admission order (FIFO, slot id ties)."""
        sl = self.slots
        return sorted(sl.prefilling_slots(),
                      key=lambda s: (sl.states[s].admitted_step, s))

    def _end_tick(self) -> None:
        """Close the tick's token accounting and open the next budget."""
        self.last_tick_prefill_tokens = self._spent_this_tick
        self.last_tick_verify_tokens = self._verify_this_tick
        self.clock += 1
        self._spent_this_tick = 0
        self._verify_this_tick = 0
        self._tick_budget_left = self._budget()

    def _idle_tick(self) -> None:
        """A clock tick with no compute (a contact pass holding the
        engine, or nothing to serve) — the prefill budget still resets,
        so the next tick starts with a full allowance."""
        self.last_tick_decode_tokens = 0
        self._end_tick()

    def _decode_batch(self, skip=frozenset()) -> None:
        """ONE batched decode step over every DECODING slot (PREFILLING
        and empty slots — and ``skip`` slots, already advanced by this
        tick's verify pass — ride along masked to the scratch region)
        and evict finished sequences."""
        decoding = [s for s in self.slots.decoding_slots() if s not in skip]
        self.last_tick_decode_tokens = len(decoding)
        if not decoding:
            return
        toks, pos = self.slots.decode_inputs(skip)
        self.decode_rows_total += self.slots.n_slots
        self.decode_tokens_total += len(decoding)
        if self.kv_layout == "paged":
            self.slots.ensure_write_pages(skip)
            bt = self.slots.block_tables(skip)
            ps = self.slots.page_size
            # each decoding row reads ceil(kv_len / page_size) of its bt
            # entries (kv_len = pos + 1): the kernel stops at the last one
            self.decode_live_pages_total += int(np.sum(
                (pos[decoding] + ps) // ps))
            self.decode_grid_pages_total += bt.size
            logits, self.slots.cache = self._decode(
                self.params, self.slots.cache, jnp.asarray(toks),
                jnp.asarray(pos), jnp.asarray(bt))
        else:
            logits, self.slots.cache = self._decode(
                self.params, self.slots.cache, jnp.asarray(toks),
                jnp.asarray(pos))
        states = self.slots.states
        finishing = [s for s in decoding if len(states[s].emitted) + 1
                     >= states[s].request.max_new]
        with jax.profiler.TraceAnnotation("engine.read"):
            nxt = np.asarray(jnp.argmax(logits[:, 0], -1))
            # fetch the final-step logits row only for sequences
            # finishing now (confidence routing); copying every step
            # would put a (n_slots, V) host transfer on the hot path
            last = {s: np.asarray(logits[s, 0], np.float32)
                    for s in finishing}
        for slot in decoding:
            st = states[slot]
            st.emitted.append(int(nxt[slot]))
            st.next_tok = int(nxt[slot])
            st.pos += 1
            if slot in last:
                st.last_logits = last[slot]
                self._finish(slot)

    def _unified_step(self) -> None:
        """ONE unified token-budget tick: spend what remains of the
        tick's ``prefill_budget_tokens`` across PREFILLING slots (FIFO
        by admission — admission itself already draws on the same
        allowance), verify pending draft tokens (one chunk pass per
        drafted slot, up to ``draft_k + 1`` tokens each), then run one
        batched decode step over the remaining DECODING slots.  Total
        model work this tick is therefore bounded by
        ``prefill_budget_tokens + n_slots * (draft_k + 1)`` tokens,
        whatever arrives."""
        if not self.slots.any_active():
            self._idle_tick()                 # wait for arrivals
            return
        for slot in self._prefilling_order():
            if self._tick_budget_left <= 0:
                break
            self._pump_prefill(slot)
        verified = self._verify_pending()
        self._decode_batch(skip=verified)
        self._end_tick()

    def step(self) -> List[int]:
        """Admit arrived requests into free slots, run one unified
        token-budget step, evict finished sequences.  Returns the rids
        finished during this step.  (``serving.scheduler`` drives
        ``_admit_arrivals`` / ``_unified_step`` separately to interpose
        preemption.)"""
        with jax.profiler.TraceAnnotation("engine.step"):
            before = len(self.finish_order)
            self._admit_arrivals()
            self._unified_step()
            return self.finish_order[before:]

    def run(self, requests: Optional[List[Request]] = None
            ) -> Dict[int, RequestResult]:
        """Drain: submit ``requests`` (sorted by arrival), then step until
        queue and slots are empty.  Returns rid -> RequestResult."""
        for r in sorted(requests or [], key=lambda r: r.arrival_t):
            self.submit(r)
        while len(self.queue) or self.slots.any_active():
            self.step()
        return self.results

    def mesh_stats(self) -> dict:
        """Mesh/sharding accounting: device count, per-axis sizes and
        the MoE expert-parallel split (experts_per_device is the
        per-device dispatch width of serving prefill — the whole expert
        set without a mesh or for dense archs' 0 experts)."""
        E = self.cfg.moe.n_experts if self.cfg.moe is not None else 0
        if self.mesh is None:
            return {"mesh_devices": 1, "mesh_axes": {},
                    "n_expert_shards": 1, "experts_per_device": E}
        with mesh_rules(self.mesh, self.logical_map):
            n_exp = shard_count("expert", E) if E else 1
        return {
            "mesh_devices": int(self.mesh.size),
            "mesh_axes": {str(a): int(self.mesh.shape[a])
                          for a in self.mesh.axis_names},
            "n_expert_shards": int(n_exp),
            "experts_per_device": E // n_exp if E else 0,
        }

    def kv_cache_stats(self) -> dict:
        """Cache-memory accounting: total cache bytes plus, for the
        paged layout, the page-pool sizing knobs, peak utilization and
        the per-device (mesh-sharded) slice of each; mesh/expert
        accounting rides along for the bench's sharded lane."""
        return {**self.slots.kv_cache_stats(), **self.mesh_stats()}
