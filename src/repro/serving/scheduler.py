"""Continuous-batching scheduler: admission queue, slots, one compiled step.

The serving subsystem's control plane (DESIGN.md §8).  Requests enter a
FIFO admission queue; ``num_slots`` decode slots run as one fixed-shape
batch.  A free slot triggers **prefill-on-free-slot**: the head-of-queue
request is prefilled (batch-1, padded to ``prefill_len``), its KV inserted
into the slot's pages, and from the next step on it decodes alongside the
other slots.  A request retires the moment it emits ``eos_id`` or reaches
its ``max_new`` — the slot and its cache blocks free immediately and the
next queued request takes them mid-decode.

Shape discipline is the whole design: prefill, insert, and decode each
compile **once** for the engine lifetime (``decode_compiles`` asserts it) —
per-slot positions, per-row RoPE, and page-table indirection make request
churn invisible to XLA.  Host-side bookkeeping (queue, slot states, block
allocator) is plain Python/numpy and never enters a trace.

Cache layouts (serving/paged_cache.py):

* ``paged`` — dense/moe GQA families: block pool + page tables, slot memory
  bounded by actual length, pool oversubscribable.  When a growth
  allocation fails, the youngest slot is **preempted** — its request goes
  back to the queue front carrying its generated tokens and resumes later
  by re-prefilling prompt+generated (greedy decode makes this exact).
* ``slots`` — MLA latent caches (already rank-compressed): each slot owns
  one row of a contiguous cache; no allocator, no preemption.

Speculative decoding (``speculative_k > 0``, DESIGN.md §13): each step
drafts k tokens with ``draft_params`` (a rank-truncated derivation of the
full params — serving/speculative.py), then verifies them in ONE chunked
full-model forward and emits the longest matching prefix plus the full
model's bonus token — 1..k+1 tokens per step, token-exact vs. plain greedy
decode.  Draft and verify share the slot's cache: draft KV is overwritten
by verify KV in the same step, and the rejected tail is dead by masking
until the next step's writes reclaim it (KV rollback costs nothing).  The
compile-once contract extends to the two extra programs: one draft-decode
and one verify executable for the engine lifetime (``draft_compiles`` /
``verify_compiles``).  The serving window is padded by k internally so
draft lookahead never writes past the cache.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import RunConfig
from repro.distributed import (FROZEN_PARAM_RULES, named_shardings,
                               paged_pool_specs)
from repro.launch import steps as steps_mod
from repro.obs import NULL_LOG, EventLog, span
from repro.serving import paged_cache as pc
from repro.serving import speculative
from repro.serving.radix_cache import RadixCache

__all__ = ["Request", "Scheduler"]


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle record.

    Latency anchors are all measured from ``arrival`` on the shared trace
    clock: ``t_started`` is the FIRST prefill start (set once — a
    preempted request keeps it through its re-prefill, so queue wait is
    the initial admission delay), ``t_first`` the first generated token
    (also set once: time-to-first-token for a preempted-then-resumed
    request is measured from the original arrival, not the re-prefill),
    ``t_done`` retirement."""

    rid: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new: int
    eos_id: Optional[int]
    arrival: float = 0.0  # virtual seconds from run start (trace replay)
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_started: Optional[float] = None  # first prefill start (queue wait)
    t_first: Optional[float] = None  # first-token latency anchor
    t_done: Optional[float] = None
    preemptions: int = 0
    prefix_hit_len: int = 0  # prompt tokens served from the radix cache
    drafted: int = 0  # speculative draft tokens proposed for this request
    accepted: int = 0  # draft tokens the verify pass kept

    @property
    def done(self) -> bool:
        return self.t_done is not None

    def fed_tokens(self) -> np.ndarray:
        """Tokens whose KV must exist before the pending token is fed:
        the prompt plus all generated-but-last (the last generated token is
        the one the next decode step consumes)."""
        if not self.tokens:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens[:-1], np.int32)])


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    pos: int = 0  # logical position the next decode step writes at
    token: int = 0  # pending token (last generated, not yet fed)
    admitted_at: int = 0  # admission counter, for youngest-first preemption

    @property
    def active(self) -> bool:
        return self.req is not None


class Scheduler:
    """Admission queue + slot table + the three compiled steps.

    Parameters
    ----------
    num_slots     : decode batch width (fixed for the engine lifetime).
    max_len       : serving window — prompt_len + max_new must fit.
    prefill_len   : fixed padded prompt length (<= max_len); also the
                    re-prefill budget for preemption resume.
    block_size    : paged layout block width (positions per block).
    num_blocks    : physical pool size incl. the reserved sink block;
                    default fully provisions num_slots * max_len (set it
                    lower to oversubscribe and exercise preemption).
    on_token      : optional streaming callback ``(request, token)`` fired
                    per generated token.
    obs           : optional ``repro.obs.EventLog`` receiving per-request
                    lifecycle events (queued → prefill → first-token →
                    retired/preempted), per-step slot/pool occupancy, and
                    compile-cache events (DESIGN.md §12).
    speculative_k : draft tokens per step (0 = plain decode).  With k > 0
                    each step runs k draft-model decodes plus one chunked
                    full-model verify and emits 1..k+1 tokens per slot.
    draft_params  : the draft model's params (serving/speculative.py);
                    defaults to ``params`` (acceptance 1.0, no speedup —
                    useful for exactness tests).
    prefix_cache  : radix-tree prompt-prefix cache (DESIGN.md §14,
                    serving/radix_cache.py): retired requests donate their
                    prompt KV blocks to a token trie, admission reuses
                    matched blocks copy-on-write and prefills only the
                    suffix.  Paged layout only (a contiguous MLA cache has
                    no blocks to share — the flag is a no-op there).
    """

    def __init__(self, run: RunConfig, params: Any, mesh, *,
                 num_slots: int = 4, max_len: int = 256,
                 prefill_len: Optional[int] = None, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 on_token: Optional[Callable[[Request, int], None]] = None,
                 obs: Optional[EventLog] = None,
                 speculative_k: int = 0, draft_params: Any = None,
                 prefix_cache: bool = False):
        cfg = run.model
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"Scheduler supports decoder-only LM families (dense/moe), "
                f"not {cfg.family!r}; use ServeEngine.generate's fixed-batch "
                f"path for encdec/vlm/ssm/hybrid")
        self.run_config = run
        self.params = params
        self.mesh = mesh
        self.num_slots = num_slots
        self.max_len = max_len
        self.prefill_len = min(prefill_len or max_len, max_len)
        self.on_token = on_token
        self.spec_k = max(int(speculative_k), 0)
        self.draft_params = (draft_params if draft_params is not None
                             else params) if self.spec_k else None
        # draft lookahead writes up to pos + spec_k; pad the physical
        # window so the overshoot never leaves the cache (requests still
        # obey the user-facing prompt + max_new <= max_len contract)
        window = max_len + self.spec_k

        # TP-sharded serving (DESIGN.md §14): on a multi-device mesh the
        # served params take the FROZEN placement — replicated over data,
        # TP over model only where the forward consumes the shard locally —
        # so a serving step has zero parameter collectives.  Exported int8
        # factor leaves (u_q/u_scale/...) and non-uniform per-layer ranks
        # resolve through the same path-based rules (divisibility fallbacks
        # re-apply per layer at heterogeneous ranks).
        self._sharded = mesh.devices.size > 1
        if self._sharded:
            self.params = jax.device_put(
                params, named_shardings(params, mesh, FROZEN_PARAM_RULES))
            if self.draft_params is not None:
                self.draft_params = jax.device_put(
                    self.draft_params,
                    named_shardings(self.draft_params, mesh,
                                    FROZEN_PARAM_RULES))

        self.layout = "paged" if pc.supports_paged(cfg) else "slots"
        if self.layout == "paged":
            self.block_size = block_size
            max_blocks = pc.blocks_for(window, block_size)
            if num_blocks is None:
                num_blocks = 1 + num_slots * max_blocks
            self.pages = pc.PageTableManager(num_slots, max_blocks,
                                             num_blocks, block_size)
            self.cache = pc.init_paged_cache(cfg, num_slots, num_blocks,
                                             block_size, max_blocks)
            # commit the pool to its lifetime placement up front: pool
            # leaves KV-head-sharded over model (page tables replicated).
            # Every program that returns the cache pins its output to the
            # same placement (``out_shardings``), so the executable
            # signature never drifts between the first call (fresh pool,
            # uploaded table) and steady state (echoed jit outputs): left
            # to itself, jit may name an equivalent placement differently,
            # on one device too.
            pinned = jax.tree_util.tree_map(
                lambda x, sp: NamedSharding(mesh, sp), self.cache,
                paged_pool_specs(self.cache, mesh, cfg.num_kv_heads))
            self.cache = jax.device_put(self.cache, pinned)
            # the cache operand is donated: the pool updates in place
            # instead of double-buffering (2x the KV memory the paged
            # design exists to bound).  A function of this scheduler's
            # own, so its compile count is this engine's alone.
            def _insert_fn(cache, pcache, page_row):
                return pc.insert_prefill_paged(cache, pcache, page_row)

            self._insert = jax.jit(_insert_fn, donate_argnums=(0,),
                                   out_shardings=pinned)
            self.prefix = (RadixCache(self.pages.allocator, block_size)
                           if prefix_cache else None)
        else:
            self.pages = None
            pinned = None  # contiguous rows: placement left to jit
            self.cache = pc.init_slot_cache(cfg, num_slots, window)
            self._insert = jax.jit(pc.insert_prefill_rows,
                                   donate_argnums=(0,))
            self.prefix = None  # contiguous rows: nothing to share

        self._prefill = jax.jit(steps_mod.build_slot_prefill_step(run, mesh))
        self._decode = jax.jit(steps_mod.build_serve_step(run, mesh),
                               donate_argnums=(1,),
                               out_shardings=(None, pinned, None))
        self._extend = (jax.jit(steps_mod.build_extend_step(run, mesh),
                                donate_argnums=(1,),
                                out_shardings=(pinned, None))
                        if self.prefix is not None else None)
        if self.spec_k:
            # two extra once-compiled programs: the k-step fused draft
            # chain (draft params, one dispatch for all k tokens) and the
            # (B, k+1) chunked verify
            self._draft = jax.jit(
                steps_mod.build_draft_chain(run, mesh, self.spec_k),
                donate_argnums=(1,), out_shardings=(pinned, None))
            self._verify = jax.jit(steps_mod.build_verify_step(run, mesh),
                                   donate_argnums=(1,),
                                   out_shardings=(pinned, None))
        else:
            self._draft = self._verify = None

        self.queue: Deque[Request] = deque()
        self.slots = [_Slot() for _ in range(num_slots)]
        self.finished: Dict[int, Request] = {}
        self._rid = 0
        self._admit_seq = 0
        self._t0: Optional[float] = None
        self._positions = np.zeros((num_slots,), np.int32)
        self._tokens = np.zeros((num_slots, 1), np.int32)
        self._pt_version = -1  # last page-table version shipped to device
        self.obs = obs if obs is not None else NULL_LOG
        # compile-cache watermarks: a change after a prefill/decode call
        # becomes a compile_cache event (the single-compile contract,
        # observable instead of test-only)
        self._compiles_seen = {"prefill": 0, "decode": 0,
                               "draft": 0, "verify": 0,
                               "insert": 0, "extend": 0}
        #: speculative-decoding counters (drafted/accepted are TOKEN
        #: counts over active slots; acceptance compares draft tokens to
        #: the verify chunk's greedy choices, independent of how many
        #: tokens a mid-chunk retirement actually emitted)
        self.spec_stats = {"spec_steps": 0, "drafted": 0, "accepted": 0,
                           "rejected": 0, "emitted": 0}
        #: radix-prefix-cache counters.  ``prefill_tokens`` counts REAL
        #: (unpadded) tokens run through a prefill/extend forward and is
        #: maintained with the cache off too — it is the apples-to-apples
        #: "prefill compute" the cached-vs-uncached bench rows compare.
        self.prefix_stats = {"lookups": 0, "hits": 0, "hit_tokens": 0,
                             "prefill_tokens": 0, "evicted_blocks": 0}

    # -- metrics -----------------------------------------------------------

    @property
    def decode_compiles(self) -> int:
        """Compiled serve_step executables — the contract is exactly 1."""
        return self._decode._cache_size()

    @property
    def prefill_compiles(self) -> int:
        return self._prefill._cache_size()

    @property
    def draft_compiles(self) -> int:
        """Compiled draft-decode executables — exactly 1 when speculating."""
        return self._draft._cache_size() if self._draft is not None else 0

    @property
    def verify_compiles(self) -> int:
        """Compiled chunked-verify executables — exactly 1 when speculating."""
        return self._verify._cache_size() if self._verify is not None else 0

    @property
    def insert_compiles(self) -> int:
        """Compiled prefill-insert executables — the contract is exactly 1."""
        return self._insert._cache_size()

    @property
    def extend_compiles(self) -> int:
        """Compiled suffix-extend executables — exactly 1 once the radix
        cache has served a hit (0 before the first hit / with the cache
        off)."""
        return self._extend._cache_size() if self._extend is not None else 0

    def acceptance_rate(self) -> float:
        """Cumulative draft acceptance since the last ``reset_stats``."""
        st = self.spec_stats
        return st["accepted"] / st["drafted"] if st["drafted"] else 0.0

    def cache_bytes(self) -> int:
        return pc.paged_pool_bytes(self.cache) if self.layout == "paged" \
            else sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(self.cache))

    # -- submission --------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new: int = 32,
               eos_id: Optional[int] = None, arrival: float = 0.0) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0 or prompt.size > self.prefill_len:
            raise ValueError(
                f"prompt length {prompt.size} outside (0, prefill_len="
                f"{self.prefill_len}]")
        if prompt.size + max_new > self.max_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new {max_new} exceeds "
                f"max_len {self.max_len}")
        req = Request(self._rid, prompt, max_new, eos_id, arrival=arrival)
        self._rid += 1
        self.queue.append(req)
        if self.obs.active:
            self.obs.emit("request_queued", rid=req.rid,
                          prompt_len=int(prompt.size), max_new=max_new,
                          arrival=arrival)
        return req.rid

    def has_work(self) -> bool:
        return bool(self.queue) or any(s.active for s in self.slots)

    # -- internals ---------------------------------------------------------

    def _now(self) -> float:
        if self._t0 is None:
            self._t0 = time.monotonic()
        return time.monotonic() - self._t0

    def _emit(self, slot: _Slot, tok: int) -> None:
        req = slot.req
        req.tokens.append(tok)
        if req.t_first is None:
            req.t_first = self._now()
            if self.obs.active:
                self.obs.emit("request_first_token", rid=req.rid,
                              ttft_s=req.t_first - req.arrival)
        if self.on_token is not None:
            self.on_token(req, tok)
        if (req.eos_id is not None and tok == req.eos_id) \
                or len(req.tokens) >= req.max_new:
            self._retire(slot)
        else:
            slot.token = tok

    def _retire(self, slot: _Slot) -> None:
        req = slot.req
        req.t_done = self._now()
        self.finished[req.rid] = req
        if self.obs.active:
            self.obs.emit("request_retired", rid=req.rid,
                          latency_s=req.t_done - req.arrival,
                          tokens=len(req.tokens),
                          preemptions=req.preemptions,
                          drafted_tokens=req.drafted,
                          accepted_tokens=req.accepted,
                          prefix_hit_len=req.prefix_hit_len)
        self._release(slot)

    def _release(self, slot: _Slot) -> None:
        idx = next(i for i, s in enumerate(self.slots) if s is slot)
        if self.pages is not None:
            self.pages.release(idx)
        slot.req = None
        slot.pos = 0
        self._positions[idx] = 0
        self._tokens[idx, 0] = 0

    def _preemptable(self, slot: _Slot) -> bool:
        """Resume needs a re-prefill of prompt+generated[:-1] — possible
        only while that still fits the fixed prefill shape."""
        req = slot.req
        return (req.prompt.size + max(len(req.tokens) - 1, 0)
                <= self.prefill_len)

    def _preempt(self, slot: _Slot) -> None:
        """Push a running request back to the queue front; it resumes by
        re-prefilling prompt+generated (exact under greedy decode)."""
        slot.req.preemptions += 1
        if self.obs.active:
            self.obs.emit("request_preempted", rid=slot.req.rid,
                          generated=len(slot.req.tokens))
        self.queue.appendleft(slot.req)
        self._release(slot)

    def _match_prefix(self, fed: np.ndarray) -> List[int]:
        """Radix lookup for an admission, capped so the suffix keeps >= 1
        token: even a full-prompt hit re-feeds the last fed token, so the
        extend step always has logits to sample the next token from AND its
        writes start at a block boundary in slot-private blocks — shared
        blocks stay strictly read-only (copy-on-write by construction)."""
        if self.prefix is None:
            return []
        self.prefix_stats["lookups"] += 1
        blocks = self.prefix.match(fed)
        usable = min(len(blocks), (fed.size - 1) // self.block_size)
        shared = blocks[:usable]
        if shared:
            self.prefix_stats["hits"] += 1
            self.prefix_stats["hit_tokens"] += usable * self.block_size
        return shared

    def _admit(self, now: float) -> None:
        for idx, slot in enumerate(self.slots):
            if slot.active or not self.queue:
                continue
            req = self.queue[0]
            if req.arrival > now:
                break  # FIFO: later arrivals wait behind the head
            fed = req.fed_tokens()
            # +1 covers the first decode write, so a fresh admission always
            # makes at least one token of progress before it can be
            # preempted again (no admit/preempt livelock on a dry pool);
            # +spec_k covers the draft lookahead of that first step.
            need_len = fed.size + 1 + self.spec_k
            shared = self._match_prefix(fed) if self.pages is not None else []
            if self.pages is not None \
                    and not self.pages.admit(idx, need_len, shared=shared):
                if self.prefix is not None:
                    # reclaim cached-only blocks before waiting/preempting
                    # (dropping cache beats stalling live work); the
                    # just-matched prefix is protected — it is not
                    # refcounted by the slot yet
                    deficit = (pc.blocks_for(need_len, self.block_size)
                               - len(shared)
                               - self.pages.allocator.free_blocks)
                    self.prefix_stats["evicted_blocks"] += \
                        self.prefix.evict(deficit, protect=shared)
                if not self.pages.admit(idx, need_len, shared=shared):
                    if not any(s.active for s in self.slots):
                        # with no slots active the pool is as free as
                        # eviction can make it; drop the matched prefix too
                        # and retry with a full allocation before declaring
                        # the request unservable
                        if self.prefix is not None:
                            self.prefix_stats["evicted_blocks"] += \
                                self.prefix.evict(self.prefix.cached_blocks)
                            shared = []
                            if self.pages.admit(idx, need_len):
                                self.queue.popleft()
                                self._start(idx, slot, req, fed, shared)
                                continue
                        raise RuntimeError(
                            f"request {req.rid} needs "
                            f"{pc.blocks_for(need_len, self.block_size)} "
                            f"blocks but the pool has "
                            f"{self.pages.allocator.free_blocks} free at "
                            f"idle — raise num_blocks")
                    break  # no pages — wait for a retirement
            self.queue.popleft()
            self._start(idx, slot, req, fed, shared)

    def _note_compiles(self, fn: str) -> None:
        """Emit a compile_cache event when an executable cache grew — in
        steady state the single-compile contract (DESIGN.md §8) means this
        fires exactly once per fn for the scheduler lifetime."""
        n = {"decode": self.decode_compiles,
             "prefill": self.prefill_compiles,
             "draft": self.draft_compiles,
             "verify": self.verify_compiles,
             "insert": self.insert_compiles,
             "extend": self.extend_compiles}[fn]
        if n != self._compiles_seen[fn]:
            self._compiles_seen[fn] = n
            self.obs.emit("compile_cache", fn=fn, compiles=n)

    def _start(self, idx: int, slot: _Slot, req: Request,
               fed: np.ndarray, shared: Optional[List[int]] = None) -> None:
        """Prefill (or extend past a radix hit) one admitted request into
        slot ``idx``, insert its KV and read back its first token: one
        ``sched.prefill`` span on the trace."""
        with span("sched.prefill", rid=req.rid, fed_len=int(fed.size)):
            self._prefill_into(idx, slot, req, fed, shared)

    def _prefill_into(self, idx: int, slot: _Slot, req: Request,
                      fed: np.ndarray, shared: Optional[List[int]]) -> None:
        now = self._now()
        resume = bool(req.tokens)
        hit = len(shared or []) * (self.block_size if self.pages else 0)
        if req.t_started is None:
            req.t_started = now
        if not resume:
            req.prefix_hit_len = hit
        if self.obs.active:
            self.obs.emit("request_prefill", rid=req.rid, slot=idx,
                          fed_len=int(fed.size), resume=resume,
                          queue_wait_s=max(req.t_started - req.arrival, 0.0),
                          prefix_hit_len=hit)
        self.prefix_stats["prefill_tokens"] += int(fed.size) - hit
        if hit:
            # radix hit: the shared blocks already hold KV for fed[:hit] —
            # forward only the suffix through the chunked extend step
            # (writes land past the shared prefix, in slot-private blocks)
            suffix = fed[hit:]
            padded = np.zeros((1, self.prefill_len), np.int32)
            padded[0, :suffix.size] = suffix
            self.cache, nxt = self._extend(
                self.params, self.cache, jnp.asarray(padded),
                jnp.asarray(self.pages.table[idx]),
                jnp.asarray(hit, jnp.int32))
            if self.obs.active:
                self._note_compiles("extend")
            first_tok = int(np.asarray(nxt)[0, suffix.size - 1])
        else:
            padded = np.zeros((1, self.prefill_len), np.int32)
            padded[0, :fed.size] = fed
            batch = {"tokens": jnp.asarray(padded),
                     "labels": jnp.zeros_like(jnp.asarray(padded))}
            last, pcache = self._prefill(
                self.params, batch, jnp.asarray([fed.size - 1], jnp.int32))
            if self.obs.active:
                self._note_compiles("prefill")
            if self.pages is not None:
                self.cache = self._insert(
                    self.cache, pcache, jnp.asarray(self.pages.table[idx]))
            else:
                self.cache = self._insert(self.cache, pcache,
                                          jnp.asarray(idx, jnp.int32))
            if self.obs.active and self.pages is not None:
                self._note_compiles("insert")
            first_tok = int(np.asarray(jnp.argmax(last, axis=-1))[0])
        if self.prefix is not None:
            # index this slot's fully-written prompt blocks; insert() adopts
            # only the novel tail (already-cached prefixes keep their owner)
            n_full = fed.size // self.block_size
            if n_full:
                self.prefix.insert(fed, self.pages.blocks(idx)[:n_full])
        slot.req = req
        slot.pos = fed.size
        slot.admitted_at = self._admit_seq
        self._admit_seq += 1
        if req.tokens:  # preemption resume: pending token already known
            slot.token = req.tokens[-1]
        else:
            self._emit(slot, first_tok)

    def _ensure_pages(self) -> None:
        """Grow page tables so every active slot can write at its position;
        preempt youngest-first (possibly the growing slot itself) when the
        pool runs dry."""
        if self.pages is None:
            return
        for idx, slot in enumerate(self.slots):
            while slot.active and \
                    not self.pages.ensure(idx, slot.pos + self.spec_k):
                if self.prefix is not None:
                    # cached-only blocks go before live work does
                    need = (pc.blocks_for(slot.pos + self.spec_k + 1,
                                          self.block_size)
                            - self.pages.allocated(idx))
                    freed = self.prefix.evict(need)
                    self.prefix_stats["evicted_blocks"] += freed
                    if freed:
                        continue
                victims = [s for s in self.slots
                           if s.active and self._preemptable(s)]
                if not victims:
                    raise RuntimeError(
                        "page pool dry and every active request grew past "
                        "prefill_len (cannot re-prefill) — size num_blocks "
                        "for the live working set")
                victim = max(victims, key=lambda s: s.admitted_at)
                self._preempt(victim)
                if victim is slot:
                    break

    def _spec_decode(self, active) -> None:
        """One speculative step: k draft decodes, one chunked verify, then
        emit the longest matching prefix plus the full model's bonus token.

        Draft KV lands in the shared cache at pos..pos+k-1 and is
        immediately overwritten by the verify pass's full-model KV at the
        same positions; whatever tail the acceptance rule rejects stays
        masked by ``kv_len`` until the NEXT step's writes (which start at
        or before the stale range) reclaim it — rollback is free.
        Emission reuses ``_emit`` one token at a time, so eos / max_new
        retirement mid-chunk behaves exactly like plain decode reaching
        the same token."""
        k = self.spec_k
        # two dispatches per step regardless of k: the fused draft chain
        # (all k tokens inside one program) then the chunked verify — the
        # only host syncs are the two reads after verify
        with span("sched.draft"):
            self.cache, chunk_dev = self._draft(
                self.draft_params, self.cache, jnp.asarray(self._tokens),
                jnp.asarray(self._positions))
        with span("sched.verify"):
            self.cache, verify = self._verify(
                self.params, self.cache, chunk_dev,
                jnp.asarray(self._positions))
        with span("sched.readback"):
            chunk = np.asarray(chunk_dev)
            verify = np.asarray(verify)
        ns = speculative.accept_lengths(chunk, verify)
        drafted = accepted = emitted = 0
        with span("sched.emit"):
            for i, s in active:
                if not s.active:
                    continue
                acc = int(ns[i])
                drafted += k
                accepted += acc
                s.req.drafted += k
                s.req.accepted += acc
                for j in range(acc):
                    s.pos += 1
                    self._emit(s, int(chunk[i, j + 1]))
                    emitted += 1
                    if not s.active:  # retired mid-chunk (eos / max_new)
                        break
                if s.active:
                    s.pos += 1
                    self._emit(s, int(verify[i, acc]))
                    emitted += 1
        st = self.spec_stats
        st["spec_steps"] += 1
        st["drafted"] += drafted
        st["accepted"] += accepted
        st["rejected"] += drafted - accepted
        st["emitted"] += emitted
        # lookahead pressure valve: rejected-draft blocks past pos are
        # idle reservations — when the pool is dry AND someone is waiting,
        # trim every active slot back to its committed length so the queue
        # head can admit instead of forcing a preemption
        if self.pages is not None and self.queue \
                and self.pages.allocator.free_blocks == 0:
            for i, s in enumerate(self.slots):
                if s.active:
                    self.pages.trim(i, s.pos + 1)
        if self.obs.active:
            rate = accepted / drafted if drafted else 0.0
            self.obs.emit("spec_step", drafted=drafted, accepted=accepted,
                          emitted=emitted, acceptance_rate=rate)

    # -- the step ----------------------------------------------------------

    def step(self) -> None:
        """Admit what fits, then run one fixed-shape decode step.

        On the trace: ``sched.step`` around it all; inside, ``sched.admit``
        (admission with its ``sched.prefill`` spans, and page growth),
        ``sched.page_table`` (the table upload), ``sched.decode`` (the
        dispatch, or ``sched.draft`` + ``sched.verify``),
        ``sched.readback`` (the blocking read of the tokens) and
        ``sched.emit`` (the per-token bookkeeping)."""
        with span("sched.step"):
            self._step()

    def _step(self) -> None:
        now = self._now()
        with span("sched.admit"):
            self._admit(now)
            self._ensure_pages()
        active = [(i, s) for i, s in enumerate(self.slots) if s.active]
        if not active:
            return
        for i, s in active:
            self._positions[i] = s.pos
            self._tokens[i, 0] = s.token
        if self.pages is not None and self._pt_version != self.pages.version:
            # the decoded cache echoes its page table, so steps that didn't
            # admit/grow/release skip the host->device table upload; the
            # upload uses the step's own output sharding so the executable
            # signature never flips between uploaded and echoed tables
            with span("sched.page_table"):
                self.cache = pc.with_page_table(
                    self.cache, self.pages.table,
                    sharding=NamedSharding(self.mesh, PartitionSpec()))
            self._pt_version = self.pages.version
        if self.spec_k:
            self._spec_decode(active)
        else:
            with span("sched.decode"):
                _, self.cache, nxt = self._decode(
                    self.params, self.cache, jnp.asarray(self._tokens),
                    jnp.asarray(self._positions), None)
            with span("sched.readback"):
                nxt = np.asarray(nxt)
            with span("sched.emit"):
                for i, s in active:
                    if not s.active:  # preempted between bookkeeping passes
                        continue
                    s.pos += 1
                    self._emit(s, int(nxt[i, 0]))
        if self.obs.active:
            if self.spec_k:
                self._note_compiles("draft")
                self._note_compiles("verify")
            else:
                self._note_compiles("decode")
            ev = {"active_slots": sum(1 for s in self.slots if s.active),
                  "queued": len(self.queue)}
            if self.pages is not None:
                ev.update(pool_used=self.pages.used_blocks,
                          pool_free=self.pages.allocator.free_blocks,
                          pool_high_water=self.pages.high_water)
            self.obs.emit("serve_step", **ev)

    def run(self, poll: float = 0.0005) -> Dict[int, np.ndarray]:
        """Drive until queue and slots drain; returns rid -> tokens."""
        while self.has_work():
            if not any(s.active for s in self.slots) and self.queue:
                wait = self.queue[0].arrival - self._now()
                if wait > 0:
                    time.sleep(min(wait, poll * 100))
                    continue
            self.step()
        return {rid: np.asarray(r.tokens, np.int32)
                for rid, r in self.finished.items()}

    # -- trace stats -------------------------------------------------------

    #: latency_stats() keys — schema-stable: with no finished requests the
    #: dict carries explicit zeros under exactly these keys, never ``{}``,
    #: so downstream row builders don't need per-key existence checks.
    STAT_KEYS = ("requests", "generated_tokens", "tok_per_s",
                 "p50_latency_s", "p95_latency_s", "p99_latency_s",
                 "p50_first_token_s", "p95_first_token_s",
                 "p50_queue_wait_s", "p95_queue_wait_s",
                 "preemptions", "preempted_requests",
                 "spec_steps", "drafted_tokens", "accepted_tokens",
                 "acceptance_rate",
                 "prefill_tokens", "prefix_lookups", "prefix_hits",
                 "prefix_hit_tokens", "prefix_evicted_blocks")

    def reset_stats(self) -> None:
        """Drop finished-request records and re-anchor the trace clock.

        Contract: callable only while idle (no queued or running work —
        raises otherwise, because in-flight requests hold timestamps on
        the old clock); the next ``_now()`` re-anchors virtual time at
        zero, so arrival offsets of a subsequently submitted trace are
        relative to that moment.  Compile caches and the page pool
        survive — only per-request records reset.
        Call it between a compile-warmup run and a measured trace replay.
        """
        if self.has_work():
            raise RuntimeError("reset_stats with work in flight")
        self.finished.clear()
        for key in self.spec_stats:
            self.spec_stats[key] = 0
        for key in self.prefix_stats:
            self.prefix_stats[key] = 0
        self._t0 = None

    def latency_stats(self) -> Dict[str, float]:
        """Latency/throughput summary over finished requests.

        Every anchor is relative to the request's ORIGINAL ``arrival``:
        queue wait is first prefill start − arrival, first-token latency
        is first generated token − arrival (unchanged by preemption —
        ``Request.t_first`` is set exactly once), completion latency is
        retirement − arrival.  ``preemptions`` counts preemption events,
        ``preempted_requests`` counts requests preempted at least once.
        Returns all ``STAT_KEYS`` with explicit zeros when nothing
        finished.
        """
        reqs = list(self.finished.values())
        if not reqs:
            return {k: 0.0 for k in self.STAT_KEYS}
        lat = np.asarray([r.t_done - r.arrival for r in reqs])
        first = np.asarray([r.t_first - r.arrival for r in reqs])
        wait = np.asarray([(r.t_started or r.arrival) - r.arrival
                           for r in reqs])
        total_tok = sum(len(r.tokens) for r in reqs)
        span = max(max(r.t_done for r in reqs), 1e-9)
        return {
            "requests": float(len(reqs)),
            "generated_tokens": float(total_tok),
            "tok_per_s": total_tok / span,
            "p50_latency_s": float(np.percentile(lat, 50)),
            "p95_latency_s": float(np.percentile(lat, 95)),
            "p99_latency_s": float(np.percentile(lat, 99)),
            "p50_first_token_s": float(np.percentile(first, 50)),
            "p95_first_token_s": float(np.percentile(first, 95)),
            "p50_queue_wait_s": float(np.percentile(wait, 50)),
            "p95_queue_wait_s": float(np.percentile(wait, 95)),
            "preemptions": float(sum(r.preemptions for r in reqs)),
            "preempted_requests": float(
                sum(1 for r in reqs if r.preemptions)),
            "spec_steps": float(self.spec_stats["spec_steps"]),
            "drafted_tokens": float(self.spec_stats["drafted"]),
            "accepted_tokens": float(self.spec_stats["accepted"]),
            "acceptance_rate": self.acceptance_rate(),
            "prefill_tokens": float(self.prefix_stats["prefill_tokens"]),
            "prefix_lookups": float(self.prefix_stats["lookups"]),
            "prefix_hits": float(self.prefix_stats["hits"]),
            "prefix_hit_tokens": float(self.prefix_stats["hit_tokens"]),
            "prefix_evicted_blocks": float(
                self.prefix_stats["evicted_blocks"]),
        }
