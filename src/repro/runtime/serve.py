"""Plan-sharded continuous-batching serving engine.

A fixed pool of ``slots`` requests decodes together in one jitted
pool-wide step; admission and eviction happen *between* decode steps:

- **chunked prefill**: admitting a request resets its slot and fills the
  KV / recurrent cache in O(prompt_len / prefill_chunk) device dispatches
  (``LM.prefill_chunk``), touching only that slot's row.  The first
  output token is sampled from the prefill logits.
- **slot scheduler**: per-slot position / output-count tracking, EOS and
  max-new-token retirement, a hard halt when the cache is full (pos ==
  max_len — the seed server silently indexed past the cache end), and a
  waiting queue that backfills freed slots.
- **isolation**: each slot attends only its own cache (per-slot length
  masking in ``attend_cache`` / ``attend_paged``), positions are
  per-slot, and a freed slot is zeroed (linear) or unmapped (paged)
  before reuse — co-resident requests cannot leak into each other.
- **batched sampling**: greedy / temperature / top-k over the whole pool
  inside the jitted decode step (``sample_tokens``), with per-(request,
  token-index) PRNG keys so a request's stream is pool-invariant.
- **plan sharding**: with a solver ``ShardingPlan`` and a mesh, params
  and the pool cache are placed per the plan (``ShardingPlan.for_pool``
  drops batch cuts that stop dividing the slot count; cache roles ride
  models/sharding.py CACHE_RULES) and the decode/prefill jits donate the
  cache buffer so the pool state is updated in place.

Paged serving tier (``ServeConfig.paged``, DESIGN.md §15):

- **block-pool KV cache**: the device holds one block pool per layer
  plus a per-slot block table (``LM.init_cache_paged``); the host side
  of the allocator lives in runtime/paged.py (``BlockPool`` refcounts,
  ``PrefixTrie`` radix cache).  ``slots`` can exceed what a linear
  cache's ``slots * max_len`` reservation would fit — memory is
  committed per *block actually written*, admission fails over to the
  waiting queue on pool exhaustion (``NoFreeBlocks``), and decode-time
  growth preempts the youngest slot (LIFO) when the trie has nothing
  left to evict.  Preempted requests are requeued front-of-line with
  their generated tokens folded into the prompt and resume via prefill
  (plus trie re-linking), continuing their sampled stream exactly
  (per-(rid, token-index) keys).
- **shared-prefix reuse**: admissions walk the trie; fully-matched
  blocks are re-linked into the slot's table (refcounted, shared),
  a partially-matched block is copied copy-on-write, and only the
  unmatched suffix is prefilled (``prompt_cache_hits`` counts reused
  tokens, ``prefill_dispatches`` the dispatches actually paid).
- **self-speculative decoding** (``spec_k > 1``): one dispatch drafts
  ``spec_k`` tokens per slot by scanning the exact plan-sharded decode
  step, then (dense families) one batched read-only re-score verifies
  the draft; the emitted tokens always come from the draft pass, so the
  output stream stays bit-equal to sequential decoding while tokens
  arrive ``spec_k`` per dispatch.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.model import LM, paged_ok
from ..obs import metrics as _metrics
from ..obs.tracing import instant as _instant, span as _span
from .paged import BlockPool, NoFreeBlocks, PrefixTrie

PyTree = Any

# sentinel budget for "generate until EOS / cache full"
_UNBOUNDED = 1 << 60


@dataclasses.dataclass
class ServeConfig:
    slots: int = 8
    max_len: int = 256
    prefill_chunk: int = 16
    # "auto" | "scan" | "parallel" — see LM.prefill_chunk
    prefill_impl: str = "auto"
    eos_id: Optional[int] = None
    temperature: float = 0.0       # 0 -> greedy
    top_k: int = 0                 # 0 -> full distribution
    seed: int = 0
    # "auto" | "xla" | "pallas" — decode-step attention kernel; auto
    # resolves to the Pallas decode kernel on TPU, XLA elsewhere (the
    # kernel-routed path is exercised on CPU via interpret mode by the
    # parity tests / kernels-smoke cell, not in production serving)
    attn_impl: str = "auto"
    # -- paged KV tier (dense full-attention families only) ---------------
    paged: bool = False
    block_len: int = 16            # must divide max_len
    # pool size; None -> slots * (max_len // block_len) + 1 (the +1 is
    # the reserved null block — same capacity as the linear cache)
    n_blocks: Optional[int] = None
    prefix_cache: bool = True      # radix shared-prefix reuse
    # -- self-speculative decoding ----------------------------------------
    spec_k: int = 1                # tokens drafted per dispatch; 1 = off
    spec_verify: bool = True       # batched re-score of the draft


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: Optional[int] = None
    # outputs already generated before a preemption; the resume prompt
    # carries them, sampling continues at this token index
    prior_out: int = 0


def sample_tokens(logits, key, temperature: float = 0.0, top_k: int = 0):
    """Batched sampling over the pool: logits [B, V] -> tokens [B].
    Greedy when temperature == 0; otherwise temperature softmax,
    restricted to the top_k logits when top_k > 0.  temperature/top_k
    are compile-time constants (the engine jits one sampler per config).

    ``key`` is a single PRNG key shared by the batch, or a [B] stack of
    per-row keys — the engine passes per-slot keys derived from
    (request id, token index) so a request's sampled stream does not
    depend on what else is resident in the pool."""
    logits = logits.astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    scaled = logits / temperature
    per_row = jnp.asarray(key).ndim == 2
    if top_k:
        vals, idx = jax.lax.top_k(scaled, top_k)
        if per_row:
            s = jax.vmap(jax.random.categorical)(key, vals)
        else:
            s = jax.random.categorical(key, vals, axis=-1)
        return jnp.take_along_axis(
            idx, s[..., None], -1)[..., 0].astype(jnp.int32)
    if per_row:
        return jax.vmap(jax.random.categorical)(key,
                                                scaled).astype(jnp.int32)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


class Server:
    """Continuous-batching slot-pool server (see module docstring).

    Scheduler API:
      submit(prompt, max_new_tokens) -> rid     enqueue a request
      step() -> events                          admissions + one decode
      run(max_steps) -> {rid: tokens}           drive until drained
      pending() -> {rid: "waiting"|"inflight"}  what run() did NOT finish
    Lower-level pieces (used by the benchmark harness and tests):
      admit_waiting() / decode_once(forced_tokens) / spec_once()
      admit(prompt, slot, ...) -> rid           direct admission
      generate(n) -> per-slot outputs           seed-compat demo API
    """

    def __init__(self, model: LM, params: PyTree, scfg: ServeConfig,
                 mesh=None, registry: Optional[_metrics.Registry] = None,
                 monitor=None):
        self.scfg = scfg
        # scheduler-side metrics; None -> shared no-op instruments, so
        # an unobserved server (warm-up, tests) records nothing
        reg = registry if registry is not None else _metrics.NULL
        self.registry = registry
        # continuous SLO/anomaly monitor (obs.monitor.Monitor); when
        # None the token hot path pays exactly one attribute check
        self.monitor = monitor
        self._t_submit: Dict[int, float] = {}   # rid -> submit time
        self._t_last: Dict[int, float] = {}     # rid -> last token time
        self._m_tokens = reg.counter(
            "serve.tokens", help="tokens emitted across all requests")
        self._m_preempt = reg.counter(
            "serve.preemptions", help="slot preemptions")
        self._m_prefix_hits = reg.counter(
            "serve.prompt_cache_hits",
            help="prompt tokens served from the prefix trie")
        self._m_pool_util = reg.gauge(
            "serve.block_pool_utilization",
            help="fraction of KV pool blocks in use (post-dispatch)")
        self.mesh = mesh if mesh is not None else model.mesh
        self.plan = model.plan
        n = scfg.slots
        self.sharded = self.plan is not None and self.mesh is not None
        if self.sharded:
            sizes = dict(zip(self.mesh.axis_names,
                             self.mesh.devices.shape))
            self.plan = self.plan.for_pool(n, sizes)
        attn_impl = scfg.attn_impl
        if attn_impl == "auto":
            attn_impl = ("pallas" if jax.default_backend() == "tpu"
                         else model.attn_impl)
        self.model = dataclasses.replace(model, plan=self.plan,
                                         mesh=self.mesh,
                                         attn_impl=attn_impl)

        # host-side scheduler state
        self.active = np.zeros((n,), bool)
        self.next_tok = np.zeros((n,), np.int32)
        self.pos = np.zeros((n,), np.int64)         # mirror of cache pos
        self.n_out = np.zeros((n,), np.int64)
        self.budget = np.full((n,), _UNBOUNDED, np.int64)
        self.prompt_len = np.zeros((n,), np.int64)
        self.slot_rid = np.full((n,), -1, np.int64)
        self.slot_seq = np.full((n,), -1, np.int64)  # admission order
        self.outputs: Dict[int, List[int]] = {}
        self.finished: Dict[int, str] = {}          # rid -> retire reason
        self.waiting: collections.deque = collections.deque()
        self.prefill_logits = np.zeros((n, model.cfg.vocab), np.float32)
        self.last_logits: Any = None      # device array, see decode_once
        self._next_rid = 0
        self._seq = itertools.count()
        self._key = jax.random.PRNGKey(scfg.seed)
        self._slot_prompt: Dict[int, List[int]] = {}
        self._events: List[Tuple] = []    # preemption events, drained
        # counters (the paged bench gates on these)
        self.prefill_dispatches = 0
        self.decode_dispatches = 0
        self.verify_dispatches = 0
        self.preemptions = 0
        self.prompt_cache_hits = 0        # prompt tokens served from trie

        # paged allocator state (host side of the block pool)
        self.paged = scfg.paged
        self.pool: Optional[BlockPool] = None
        self.trie: Optional[PrefixTrie] = None
        if self.paged:
            self.bl = scfg.block_len
            if scfg.max_len % self.bl:
                raise ValueError(
                    f"block_len={self.bl} must divide "
                    f"max_len={scfg.max_len}")
            self.mb = scfg.max_len // self.bl
            nb = (scfg.n_blocks if scfg.n_blocks is not None
                  else n * self.mb + 1)
            if nb < self.mb + 1:
                raise ValueError(
                    f"n_blocks={nb} cannot hold one full-length request "
                    f"({self.mb} blocks + the reserved null block) — "
                    "the scheduler could deadlock")
            self.n_blocks = nb
            self.pool = BlockPool(nb)
            if scfg.prefix_cache:
                self.trie = PrefixTrie(self.pool, self.bl)
            self.table = np.zeros((n, self.mb), np.int32)
            self.n_slot_blocks = np.zeros((n,), np.int64)
        self._table_dirty = False
        self._pos_dirty = False
        self._can_verify = paged_ok(self.model.cfg)

        t, k = scfg.temperature, scfg.top_k
        base_key = self._key

        def slot_key(rid, count):
            # per-(request, token-index) stream: sampling is invariant
            # to whatever else is resident in the pool
            return jax.random.fold_in(
                jax.random.fold_in(base_key, jnp.maximum(rid, 0)), count)

        def decode_fn(params, cache, tokens, rids, counts, active):
            logits, cache = self.model.decode_step(params, cache, tokens,
                                                   active=active)
            keys = jax.vmap(slot_key)(rids, counts)
            toks = sample_tokens(logits, keys, t, k)
            return toks, logits.astype(jnp.float32), cache

        def prefill_fn(params, cache, tokens, slot, n_valid):
            return self.model.prefill_chunk(params, cache, tokens, slot,
                                            n_valid,
                                            impl=scfg.prefill_impl)

        def prefill_scan_fn(params, cache, tokens, slot, n_valid):
            # preemption-resume path: the scan prefill IS the sequential
            # decode step, so recomputing decode-written K/V is
            # bit-exact (the parallel path re-associates the softmax)
            return self.model.prefill_chunk(params, cache, tokens, slot,
                                            n_valid, impl="scan")

        K, max_len = scfg.spec_k, scfg.max_len

        def spec_fn(params, cache, tokens, rids, counts, active):
            """Draft K tokens per active slot by scanning the exact
            decode step (same keys as K sequential decode_once calls, so
            the draft IS the sequential stream).  Rows whose position
            reaches max_len freeze mid-draft (per-step active mask)."""
            def body(carry, _):
                cache, toks, counts = carry
                act = active & (cache["pos"] < max_len)
                logits, cache = self.model.decode_step(
                    params, cache, toks, active=act)
                keys = jax.vmap(slot_key)(rids, counts)
                nt = sample_tokens(logits, keys, t, k)
                nt = jnp.where(act, nt, toks)
                counts = counts + act.astype(counts.dtype)
                return ((cache, nt, counts),
                        (nt, logits.astype(jnp.float32)))

            (cache, _, _), (toks, logits) = jax.lax.scan(
                body, (cache, tokens, counts), None, length=K)
            return toks, logits, cache      # toks [K, B]

        def verify_fn(params, cache, feed, base_pos, rids, counts):
            """Batched re-score of a K-token draft: logits for feeding
            feed[b, j] at position base_pos[b] + j of row b, sampled
            with the same per-(rid, token-index) keys the draft used.
            Read-only — the cache already holds the drafted K/V."""
            b, kk = feed.shape
            rows = jnp.repeat(jnp.arange(b), kk)
            positions = (base_pos[:, None] + jnp.arange(kk)).reshape(-1)
            logits = self.model.decode_rescore(
                params, cache, feed.reshape(-1), rows, positions)
            keys = jax.vmap(slot_key)(
                jnp.repeat(rids, kk),
                (counts[:, None] + jnp.arange(kk)).reshape(-1))
            return sample_tokens(logits, keys, t, k).reshape(b, kk)

        def copy_fn(cache, dst, src):
            """Copy-on-write: duplicate pool block ``src`` into ``dst``
            across all layers (both K and V pools)."""
            new = dict(cache)
            new["pages"] = {kk: a.at[:, dst].set(a[:, src])
                            for kk, a in cache["pages"].items()}
            return new

        with self._ctx():
            if self.paged:
                cache = self.model.init_cache_paged(
                    n, scfg.max_len, self.n_blocks, self.bl)
            else:
                cache = self.model.init_cache(n, scfg.max_len)
            self._pos_sh = self._table_sh = None
            if self.sharded:
                from ..models.sharding import CACHE_RULES, tree_shardings
                params = jax.device_put(
                    params, tree_shardings(self.plan, params, self.mesh))
                sh = tree_shardings(self.plan, cache, self.mesh,
                                    rules=CACHE_RULES)
                cache = jax.device_put(cache, sh)
                self._pos_sh = sh["pos"]
                self._table_sh = sh.get("block_table")
            self.params = params
            self.cache = cache
        self._decode = jax.jit(decode_fn, donate_argnums=(1,))
        self._prefill = jax.jit(prefill_fn, donate_argnums=(1,))
        self._prefill_resume = jax.jit(prefill_scan_fn,
                                       donate_argnums=(1,))
        self._reset = jax.jit(self.model.reset_slot, donate_argnums=(0,))
        self._spec = jax.jit(spec_fn, donate_argnums=(1,))
        self._verify = jax.jit(verify_fn)      # read-only: NO donation
        self._copy = jax.jit(copy_fn, donate_argnums=(0,))
        self._sample1 = jax.jit(
            lambda lg, rid, count: sample_tokens(
                lg[None], slot_key(rid, count), t, k)[0])

    def adopt_jits(self, other: "Server") -> "Server":
        """Take another (configuration-identical) server's compiled
        jits, so benchmark harnesses can warm up on a throwaway pool and
        measure a fresh one without paying compiles in the timed window.
        The single place that knows which jits a Server carries."""
        self._decode = other._decode
        self._prefill = other._prefill
        self._prefill_resume = other._prefill_resume
        self._reset = other._reset
        self._spec = other._spec
        self._verify = other._verify
        self._copy = other._copy
        self._sample1 = other._sample1
        return self

    def _ctx(self):
        return jax.set_mesh(self.mesh) if self.mesh is not None \
            else contextlib.nullcontext()

    def _drain(self) -> List[Tuple]:
        ev, self._events = self._events, []
        return ev

    def _flush_host_state(self) -> None:
        """Push the host-side truth (block table, positions) to the
        device cache.  The host mutates its mirrors freely between
        dispatches (admission, preemption, speculative rollback) and
        flushes once before the next dispatch."""
        if self._table_dirty:
            tbl = jnp.asarray(self.table)
            if self._table_sh is not None:
                tbl = jax.device_put(tbl, self._table_sh)
            self.cache["block_table"] = tbl
            self._table_dirty = False
        if self._pos_dirty:
            pos = jnp.asarray(self.pos.astype(np.int32))
            if self._pos_sh is not None:
                pos = jax.device_put(pos, self._pos_sh)
            self.cache["pos"] = pos
            self._pos_dirty = False

    # -- request intake ---------------------------------------------------
    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None) -> int:
        """Enqueue a request; it is admitted by a later step() when a
        slot frees up."""
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if len(prompt) > self.scfg.max_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens does not fit the "
                f"max_len={self.scfg.max_len} cache")
        rid = self._next_rid
        self._next_rid += 1
        self.waiting.append(Request(rid, list(prompt), max_new_tokens))
        if self.monitor is not None:
            self._t_submit[rid] = time.perf_counter()
        return rid

    def admit(self, prompt: Sequence[int], slot: int,
              max_new_tokens: Optional[int] = None,
              method: str = "chunked") -> int:
        """Admit a request directly into ``slot`` (must be free).
        ``method``: "chunked" (prefill_chunk-sized pieces) or
        "tokenwise" (chunk size 1 — the per-token reference path)."""
        if self.active[slot]:
            raise ValueError(f"slot {slot} is busy")
        rid = self._next_rid
        self._next_rid += 1
        self._admit(Request(rid, list(prompt), max_new_tokens), slot,
                    method)
        return rid

    def _admit(self, req: Request, slot: int,
               method: str = "chunked") -> List[Tuple]:
        with _span("serve.admit", rid=req.rid, slot=slot,
                   prompt_len=len(req.prompt)):
            return self._admit_impl(req, slot, method)

    def _admit_impl(self, req: Request, slot: int,
                    method: str) -> List[Tuple]:
        scfg = self.scfg
        if not 1 <= len(req.prompt) <= scfg.max_len:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens does not fit the "
                f"max_len={scfg.max_len} cache")
        prompt = np.asarray(req.prompt, np.int32)
        if self.paged:
            # may raise NoFreeBlocks — before any state is touched
            logits = self._prefill_paged(prompt, slot, method,
                                         resume_tail=req.prior_out)
        else:
            logits = self._prefill_linear(prompt, slot, method)
        if req.prior_out:
            _instant("serve.resume", rid=req.rid, slot=slot)
        else:
            _instant("serve.admitted", rid=req.rid, slot=slot)
        with self._ctx():
            tok = int(self._sample1(logits, req.rid, req.prior_out))
        self.prefill_logits[slot] = np.asarray(logits)
        self.active[slot] = True
        self.slot_rid[slot] = req.rid
        self.slot_seq[slot] = next(self._seq)
        self.prompt_len[slot] = len(prompt)
        self.pos[slot] = len(prompt)
        self.n_out[slot] = req.prior_out
        self.budget[slot] = (req.max_new_tokens
                             if req.max_new_tokens is not None
                             else _UNBOUNDED)
        # a resumed (preempted) request keeps its accumulated outputs
        self.outputs.setdefault(req.rid, [])
        self._slot_prompt[slot] = [int(x) for x in prompt]
        events = [("admit", req.rid, slot)]
        events += self._append(slot, tok)
        return events

    def _prefill_linear(self, prompt: np.ndarray, slot: int,
                        method: str):
        c = self.scfg.prefill_chunk if method == "chunked" else 1
        with _span("serve.prefill", slot=slot,
                   tokens=len(prompt)), self._ctx():
            self.cache = self._reset(self.cache, slot)
            logits = None
            for i in range(0, len(prompt), c):
                chunk = prompt[i:i + c]
                nv = len(chunk)
                if nv < c:
                    chunk = np.pad(chunk, (0, c - nv))
                logits, self.cache = self._prefill(
                    self.params, self.cache, jnp.asarray(chunk),
                    slot, nv)
                self.prefill_dispatches += 1
        return logits

    # -- paged admission: trie match + CoW + suffix prefill ---------------
    def _prefill_paged(self, prompt: np.ndarray, slot: int,
                       method: str, resume_tail: int = 0):
        with _span("serve.prefill", slot=slot, tokens=len(prompt)):
            return self._prefill_paged_impl(prompt, slot, method,
                                            resume_tail)

    def _prefill_paged_impl(self, prompt: np.ndarray, slot: int,
                            method: str, resume_tail: int = 0):
        """Build the slot's block-table row — re-linking trie-cached
        prefix blocks, copy-on-write for a partial block match, fresh
        blocks for the suffix — then prefill only the unmatched suffix.
        ``resume_tail`` > 0 marks a preempted request coming back: the
        last ``resume_tail`` prompt tokens were decode-written before
        preemption, so they re-run through the scan prefill (bitwise
        the decode step), while the original-prompt region keeps the
        configured prefill impl and chunk boundaries — a full recompute
        then reproduces the original admission bit-for-bit.
        Raises NoFreeBlocks (with every acquired reference rolled back)
        before touching any scheduler or device state."""
        scfg, bl = self.scfg, self.bl
        p_len = len(prompt)
        toks = [int(x) for x in prompt]
        acquired: List[int] = []    # one caller reference each
        row: List[int] = []
        pending_copy = None
        cached = 0
        full: List[int] = []
        part = cow = None
        take = 0
        try:
            # at least one suffix token must remain to produce logits
            limit = p_len - 1
            if self.trie is not None:
                with _span("serve.trie_match", slot=slot) as sp:
                    full, part = self.trie.match(toks)
                    sp.set(full_blocks=len(full),
                           partial=part is not None)
                acquired += full
                if part is not None:
                    acquired.append(part[0])
            keep = min(len(full), limit // bl)
            if len(full) > keep:
                # prompt fully covered: the next full block degrades to
                # a CoW source for its first (limit - keep*bl) tokens
                cow = (full[keep], bl)
            elif part is not None:
                cow = part
            row = list(full[:keep])
            cached = keep * bl
            if cow is not None:
                take = min(cow[1], limit - cached)
            if take > 0:
                dst = self._alloc_block()
                acquired.append(dst)
                pending_copy = (dst, cow[0])
                row.append(dst)
                cached += take
            while len(row) < (p_len - 1) // bl + 1:
                b = self._alloc_block()
                acquired.append(b)
                row.append(b)
        except NoFreeBlocks:
            for b in acquired:
                self.pool.decref(b)
            raise
        # drop the references we did not keep: unused full matches past
        # the CoW source, the partial match when a full block won the
        # CoW slot, and the CoW source itself when nothing was taken
        drop_now = list(full[keep + 1:])
        if part is not None and (cow is None or cow[0] != part[0]):
            drop_now.append(part[0])
        if cow is not None and take <= 0:
            drop_now.append(cow[0])
        for b in drop_now:
            self.pool.decref(b)

        self.table[slot, :] = 0
        self.table[slot, :len(row)] = row
        self.n_slot_blocks[slot] = len(row)
        self.pos[slot] = cached
        self._table_dirty = True
        self._pos_dirty = True
        self.prompt_cache_hits += cached
        self._m_prefix_hits.inc(cached)
        c = scfg.prefill_chunk if method == "chunked" else 1
        # the decode-written tail of a resumed prompt must scan; the
        # original-prompt region keeps the configured impl, with chunks
        # capped at the boundary exactly as the original admission
        # capped them at its prompt end
        split = p_len - resume_tail
        with self._ctx():
            if pending_copy is not None:
                self.cache = self._copy(self.cache,
                                        np.int32(pending_copy[0]),
                                        np.int32(pending_copy[1]))
                self.pool.decref(pending_copy[1])
            self._flush_host_state()
            logits = None
            i = cached
            while i < p_len:
                if i < split:
                    j, fn = min(i + c, split), self._prefill
                else:
                    j, fn = min(i + c, p_len), self._prefill_resume
                chunk = prompt[i:j]
                nv = j - i
                if nv < c:
                    chunk = np.pad(chunk, (0, c - nv))
                logits, self.cache = fn(
                    self.params, self.cache, jnp.asarray(chunk),
                    slot, nv)
                self.prefill_dispatches += 1
                i = j
        if self.trie is not None:
            self.trie.insert(toks, row[:p_len // bl])
        return logits

    # -- paged allocator glue ---------------------------------------------
    def _alloc_block(self, protect: Optional[int] = None,
                     allow_preempt: bool = False) -> int:
        """One free pool block, reclaiming in escalation order: free
        list -> trie LRU eviction -> (decode-time only) preempting the
        youngest active slot.  Admissions never preempt — they requeue
        on NoFreeBlocks instead, so a burst cannot thrash the pool."""
        while True:
            try:
                return self.pool.alloc()
            except NoFreeBlocks:
                if self.trie is not None and self.trie.evict(1):
                    continue
                if not allow_preempt:
                    raise
                victim = self._pick_victim(protect)
                if victim is None:
                    raise
                self._preempt(victim)

    def _pick_victim(self, protect: Optional[int]) -> Optional[int]:
        best, best_seq = None, -1
        for s in range(self.scfg.slots):
            if s == protect or not self.active[s]:
                continue
            if self.slot_seq[s] > best_seq:
                best_seq, best = int(self.slot_seq[s]), s
        return best

    def _preempt(self, slot: int) -> None:
        """LIFO preemption: release the slot's blocks (registering the
        full-block prefix in the trie so the resume re-links instead of
        recomputing) and requeue front-of-line with generated tokens
        folded into the prompt.  Sampling resumes at ``prior_out`` so
        the output stream continues exactly."""
        rid = int(self.slot_rid[slot])
        outs = list(self.outputs.get(rid, []))
        self._release_blocks(slot, rid)
        self.active[slot] = False
        self.slot_rid[slot] = -1
        self.pos[slot] = 0
        self._pos_dirty = True
        b = int(self.budget[slot])
        self.waiting.appendleft(Request(
            rid, self._slot_prompt.get(slot, []) + outs,
            None if b >= _UNBOUNDED else b, prior_out=len(outs)))
        self.preemptions += 1
        self._m_preempt.inc()
        if self.monitor is not None:
            self.monitor.bump("preempt")
        _instant("serve.preempt", rid=rid, slot=slot)
        self._events.append(("preempt", rid, slot))

    def _release_blocks(self, slot: int, rid: int) -> None:
        """Give the slot's block-table row back to the pool, first
        caching the full-block prefix of (prompt + outputs-in-cache)
        in the trie for later shared-prefix admissions."""
        nb = int(self.n_slot_blocks[slot])
        row = [int(b) for b in self.table[slot, :nb]]
        if self.trie is not None and row:
            pos = int(self.pos[slot])
            seq = (self._slot_prompt.get(slot, [])
                   + self.outputs.get(rid, []))
            nfull = pos // self.bl
            self.trie.insert(seq[:pos], row[:nfull])
            # the partially-filled tail block too: a preempted request
            # resumes by re-linking these exact bytes (CoW), keeping
            # the resume bit-exact instead of recomputing K/V
            if pos % self.bl and nfull < len(row):
                self.trie.insert_partial(seq[:pos], row[nfull])
        for b in row:
            self.pool.decref(b)
        self.table[slot, :] = 0
        self.n_slot_blocks[slot] = 0
        self._table_dirty = True

    def _ensure_blocks(self, slot: int, last_pos: int) -> None:
        """Map pool blocks covering writes up to position ``last_pos``
        (escalating through trie eviction and preemption; the slot
        itself is protected)."""
        while int(self.n_slot_blocks[slot]) * self.bl <= last_pos:
            blk = self._alloc_block(protect=slot, allow_preempt=True)
            self.table[slot, int(self.n_slot_blocks[slot])] = blk
            self.n_slot_blocks[slot] += 1
            self._table_dirty = True

    # -- slot bookkeeping -------------------------------------------------
    def _observe_token(self, rid: int) -> None:
        """Feed the monitor one emitted token: first token since submit
        is TTFT, every later one an ITL.  A preemption gap lands in the
        ITL stream — that is what the client experiences."""
        now = time.perf_counter()
        last = self._t_last.get(rid)
        if last is None:
            t0 = self._t_submit.pop(rid, None)
            if t0 is not None:
                self.monitor.observe("ttft", now - t0)
        else:
            self.monitor.observe("itl", now - last)
        self._t_last[rid] = now

    def _append(self, slot: int, tok: int) -> List[Tuple]:
        rid = int(self.slot_rid[slot])
        self.outputs[rid].append(tok)
        self._m_tokens.inc()
        if self.monitor is not None:
            self._observe_token(rid)
        self.n_out[slot] += 1
        self.next_tok[slot] = tok
        events: List[Tuple] = [("token", rid, tok)]
        scfg = self.scfg
        if scfg.eos_id is not None and tok == scfg.eos_id:
            events.append(self._retire(slot, "eos"))
        elif self.n_out[slot] >= self.budget[slot]:
            events.append(self._retire(slot, "length"))
        elif self.pos[slot] >= scfg.max_len:
            # cache full: feeding one more token would index past the
            # cache end (the seed server's silent-overflow bug)
            events.append(self._retire(slot, "max_len"))
        return events

    def _retire(self, slot: int, reason: str) -> Tuple:
        rid = int(self.slot_rid[slot])
        if self.paged:
            self._release_blocks(slot, rid)
        self.active[slot] = False
        self.slot_rid[slot] = -1
        self.finished[rid] = reason
        self._t_last.pop(rid, None)
        self._t_submit.pop(rid, None)
        _instant("serve.retire", rid=rid, slot=slot, reason=reason)
        return ("retire", rid, reason)

    # -- the serving loop -------------------------------------------------
    def admit_waiting(self) -> List[Tuple]:
        """Backfill free slots from the waiting queue.  A request whose
        admission fails is either requeued (NoFreeBlocks — the paged
        pool is transiently full; admission order is preserved) or
        retired with reason "rejected" (invalid request) — never
        silently dropped."""
        if self.monitor is not None:
            self.monitor.observe("queue_depth", float(len(self.waiting)))
        events: List[Tuple] = []
        for slot in range(self.scfg.slots):
            if not self.waiting:
                break
            if self.active[slot]:
                continue
            req = self.waiting[0]
            try:
                ev = self._admit(req, slot)
            except NoFreeBlocks:
                break          # stays queued; retires will free blocks
            except ValueError:
                self.waiting.popleft()
                self.outputs.setdefault(req.rid, [])
                self.finished[req.rid] = "rejected"
                events.append(("retire", req.rid, "rejected"))
                continue
            self.waiting.popleft()
            events += ev
        return self._drain() + events

    def decode_once(self, forced_tokens: Optional[np.ndarray] = None
                    ) -> List[Tuple]:
        """One pool-wide decode step: feed each active slot's next token
        (or ``forced_tokens`` — teacher forcing, used by the conformance
        cell), sample, append, retire.  No-op when nothing is active.
        Idle slots are masked out of the dispatch (their cache position
        must not drift between requests)."""
        events = self._drain()
        if not self.active.any():
            return events
        if self.paged:
            for slot in np.nonzero(self.active)[0]:
                s = int(slot)
                if self.active[s]:      # an earlier iteration may preempt
                    self._ensure_blocks(s, int(self.pos[s]))
        act = self.active.copy()        # after any preemption
        events += self._drain()
        if not act.any():
            return events
        self._flush_host_state()
        feed = (self.next_tok if forced_tokens is None
                else np.asarray(forced_tokens, np.int32))
        slots = [int(s) for s in np.nonzero(act)[0]]
        with _span("serve.decode", slots=slots), self._ctx():
            toks, logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(feed),
                jnp.asarray(self.slot_rid, jnp.int32),
                jnp.asarray(self.n_out, jnp.int32),
                jnp.asarray(act))
            toks = np.asarray(toks)
        # device array, materialized lazily — only diagnostic consumers
        # (tests, the conformance cell) pay the [slots, vocab] transfer
        self.last_logits = logits
        self.decode_dispatches += 1
        if self.pool is not None:
            self._m_pool_util.set(1.0 - self.pool.n_free / self.n_blocks)
        # only the rows that actually decoded advance (the seed server
        # advanced every slot, so an idle slot's mirror drifted)
        self.pos[act] += 1
        for slot in slots:
            events += self._append(slot, int(toks[slot]))
        return events

    def spec_once(self) -> List[Tuple]:
        """One speculative round: draft ``spec_k`` tokens per active
        slot in a single dispatch, optionally verify with one batched
        re-score, then accept the longest draft/verify-agreeing prefix
        (at least one token — forced progress).  Emitted tokens always
        come from the draft pass — which runs the exact sequential
        decode step — so the stream is bit-equal to decode_once."""
        events = self._drain()
        if not self.active.any():
            return events
        kk = self.scfg.spec_k
        if self.paged:
            for slot in np.nonzero(self.active)[0]:
                s = int(slot)
                if self.active[s]:
                    self._ensure_blocks(
                        s, min(int(self.pos[s]) + kk - 1,
                               self.scfg.max_len - 1))
        act = self.active.copy()
        events += self._drain()
        if not act.any():
            return events
        self._flush_host_state()
        base_pos = self.pos.copy()
        base_out = self.n_out.copy()
        slots = [int(s) for s in np.nonzero(act)[0]]
        with self._ctx():
            with _span("serve.draft", slots=slots, k=kk):
                toks, logits, self.cache = self._spec(
                    self.params, self.cache, jnp.asarray(self.next_tok),
                    jnp.asarray(self.slot_rid, jnp.int32),
                    jnp.asarray(self.n_out, jnp.int32),
                    jnp.asarray(act))
                toks = np.asarray(toks)           # [K, B]
            self.decode_dispatches += 1
            accept = np.full((self.scfg.slots,), kk, np.int64)
            if kk > 1 and self.scfg.spec_verify and self._can_verify:
                # feed[j] is the token that produced draft token j
                feed = np.concatenate([self.next_tok[None], toks[:-1]],
                                      axis=0)     # [K, B]
                with _span("serve.verify", slots=slots, k=kk):
                    vt = np.asarray(self._verify(
                        self.params, self.cache,
                        jnp.asarray(feed.T.copy()),   # [B, K]
                        jnp.asarray(base_pos.astype(np.int32)),
                        jnp.asarray(self.slot_rid, jnp.int32),
                        jnp.asarray(base_out.astype(np.int32))))
                self.verify_dispatches += 1
                agree = vt.T == toks              # [K, B]
                for s in range(self.scfg.slots):
                    if not act[s] or agree[:, s].all():
                        continue
                    accept[s] = max(1, int(np.argmin(agree[:, s])))
        self.last_logits = logits[-1]
        for slot in np.nonzero(act)[0]:
            s = int(slot)
            for j in range(int(accept[s])):
                if not self.active[s]:
                    break                         # retired mid-round
                self.pos[s] += 1
                events += self._append(s, int(toks[j, s]))
        # the device ran spec_k steps ahead of what was accepted (and a
        # mid-round retirement stops even earlier): roll positions back
        # to the host truth.  Rolled-back K/V entries are overwritten by
        # the next write at the same position before any attend can
        # reach them (length masking), so only pos needs the rollback.
        self._pos_dirty = True
        self._flush_host_state()
        return events + self._drain()

    def step(self) -> List[Tuple]:
        """One scheduler iteration: admissions, then one decode (or
        speculative) round.  Returns event tuples
        ("admit"|"token"|"retire"|"preempt", rid, value)."""
        events = self.admit_waiting()
        if self.scfg.spec_k > 1:
            return events + self.spec_once()
        return events + self.decode_once()

    def run(self, max_steps: Optional[int] = None) -> Dict[int, List[int]]:
        """Drive until the queue and the pool drain (or max_steps —
        check pending() for what a capped run left unfinished)."""
        steps = 0
        while self.waiting or self.active.any():
            if max_steps is not None and steps >= max_steps:
                break
            self.step()
            steps += 1
        return {rid: list(toks) for rid, toks in self.outputs.items()}

    def pending(self) -> Dict[int, str]:
        """Requests run() did not finish: rid -> "waiting" (still
        queued) or "inflight" (admitted, mid-generation).  The seed
        returned run()'s outputs with no way to tell a completed
        request from one cut off by max_steps."""
        out = {req.rid: "waiting" for req in self.waiting}
        for slot in np.nonzero(self.active)[0]:
            out[int(self.slot_rid[slot])] = "inflight"
        return out

    # -- seed-compat demo API ---------------------------------------------
    def generate(self, n_tokens: int) -> List[List[int]]:
        """Decode until every currently-active slot has ``n_tokens``
        outputs (counting the prefill-sampled first token), then return
        the per-slot output lists.  Compat shim for the seed demo API —
        production drivers use submit()/run().  The budget is *clamped*
        (min), never raised: a request admitted with a smaller
        max_new_tokens keeps its own budget."""
        rids = [int(self.slot_rid[s]) if self.active[s] else None
                for s in range(self.scfg.slots)]
        for s in range(self.scfg.slots):
            if self.active[s]:
                self.budget[s] = min(self.budget[s], n_tokens)
        while any(self.active[s] for s in range(self.scfg.slots)
                  if rids[s] is not None):
            self.decode_once()
        return [list(self.outputs.get(r, []))[:n_tokens]
                if r is not None else [] for r in rids]
