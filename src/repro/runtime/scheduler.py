"""In-flight (continuous) batching scheduler over the paged KV cache.

One :class:`ContinuousBatchingScheduler` owns a fixed set of decode slots
backed by a :class:`~repro.runtime.kv_cache.PagedKVCache` and advances all
in-flight requests together, one ``tick()`` at a time:

1. **admit** — FIFO: while the head of the queue fits (a free slot and
   enough free pages for its prompt), move it into a slot.  Strict FIFO —
   a large request at the head blocks later ones rather than being starved
   by them.
2. **prefill** — at most one *chunk* (``prefill_chunk`` tokens) of the
   oldest prefilling request is processed, so a long prompt never stalls
   the running decode batch for more than one chunk's latency.
3. **decode** — every slot in the decode phase takes one step in a single
   fixed-shape batched call; finished requests retire immediately and their
   slot/pages are reusable at the very next tick.

The decode step gathers each slot's pages into a contiguous per-slot view
and runs the *same* ``model.forward_decode`` the synchronous oracle uses,
``vmap``-ed over slots with per-slot write positions — so the batched path
is the oracle's per-request computation, batched, and token-for-token
equivalence against ``greedy_generate`` is testable (tests/test_serving.py).
Chunked prefill reuses decode mode too: a chunk of ``n`` tokens is one
multi-token decode step at ``cache_index = tokens already prefilled``.

Sampling is a per-request hook: ``temperature <= 0`` is greedy argmax
(bitwise the oracle's choice); ``temperature > 0`` draws from the softmax
with a per-request deterministic RNG.  A scheduler-level ``sample_fn``
overrides both.

Telemetry (optional): a run sink receives ``request_start`` /
``request_end`` / ``request_evicted`` events, each with the queue depth,
``request_end`` with the request's TTFT and TPOT
(``scripts/render_run.py`` renders the percentiles).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.runtime.kv_cache import (
    CacheOOM,
    PagedCacheConfig,
    PagedKVCache,
    flat_positions,
    gather_pages,
    scatter_tokens,
)

QUEUED, PREFILLING, DECODING, FINISHED = ("queued", "prefilling",
                                          "decoding", "finished")


@dataclasses.dataclass(eq=False)          # identity eq: prompts are arrays
class Request:
    """One generation request.  ``tokens`` fills in as the scheduler runs;
    timing fields are stamped by the scheduler's clock."""

    prompt: np.ndarray                 # (S,) int32 token ids
    max_new: int
    rid: int = -1                      # assigned at submit when < 0
    temperature: float = 0.0           # <= 0: greedy
    seed: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    state: str = QUEUED
    slot: int = -1
    prefilled: int = 0                 # prompt tokens already in the cache
    t_submit: float = 0.0
    t_first: float = 0.0
    t_end: float = 0.0

    @property
    def done(self) -> bool:
        return self.state == FINISHED

    @property
    def ttft_s(self) -> float:
        return self.t_first - self.t_submit

    @property
    def tpot_s(self) -> float:
        """Mean time per output token after the first."""
        return (self.t_end - self.t_first) / max(len(self.tokens) - 1, 1)


class TokenStream:
    """Iterator handed back by ``submit``: yields tokens as they are
    generated, driving ``scheduler.tick()`` while the request is live."""

    def __init__(self, scheduler: "ContinuousBatchingScheduler",
                 request: Request):
        self.request = request
        self._scheduler = scheduler
        self._emitted = 0

    def __iter__(self) -> Iterator[int]:
        while True:
            stalled = 0
            while (self._emitted >= len(self.request.tokens)
                   and not self.request.done):
                before = len(self.request.tokens) + self.request.prefilled
                self._scheduler.tick()
                stalled = (0 if len(self.request.tokens)
                           + self.request.prefilled != before else stalled + 1)
                if stalled > 100_000:
                    raise RuntimeError(
                        f"request {self.request.rid} made no progress")
            if self._emitted >= len(self.request.tokens):
                return
            tok = self.request.tokens[self._emitted]
            self._emitted += 1
            yield tok


def _default_sample(logits: np.ndarray, request: Request,
                    rng: np.random.Generator) -> int:
    """Greedy at temperature <= 0; otherwise softmax sampling."""
    if request.temperature <= 0.0:
        return int(np.argmax(logits))
    x = logits.astype(np.float64) / request.temperature
    x -= x.max()
    p = np.exp(x)
    return int(rng.choice(len(p), p=p / p.sum()))


class ContinuousBatchingScheduler:
    """Continuous batching over ``model`` with paged KV storage.

    ``model`` / ``params`` follow the ``ServingEngine`` conventions (params
    already in the serving dtype); ``cache_cfg`` sizes the page pool.  Use
    ``repro.serving.build`` rather than constructing this directly.
    """

    def __init__(self, model: Any, params: Any, cache_cfg: PagedCacheConfig,
                 *, prefill_chunk: int = 32, dtype=jnp.bfloat16,
                 sample_fn: Optional[Callable] = None,
                 sink: Any = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.model = model
        self.params = params
        self.cache = PagedKVCache(cache_cfg, dtype)
        self.prefill_chunk = int(prefill_chunk)
        self.sink = sink
        self._clock = clock
        self._sample = sample_fn or _default_sample
        self._queue: collections.deque[Request] = collections.deque()
        self._slots: list[Optional[Request]] = [None] * cache_cfg.num_slots
        self._admit_order: collections.deque[Request] = collections.deque()
        self._next_rid = 0
        self._finished = 0
        self._generated = 0
        self._evicted = 0
        self._rngs: dict[int, np.random.Generator] = {}
        self._decode_fn = compat.jit(self._decode_step)
        self._prefill_fn = compat.jit(self._prefill_step)

    # ------------------------------------------------------------ jitted
    def _decode_step(self, params, k_pages, v_pages, tokens, block_tables,
                     lens):
        """One batched decode tick: tokens (B,), block_tables (B, Pmax),
        lens (B,) -> (logits (B, V) fp32, new k/v pools).

        Each slot runs the oracle's single-request ``forward_decode`` on its
        gathered page view (vmap over slots), then only the new token's k/v
        is scattered back into the pool at the slot's write position.
        Idle lanes carry an all-null block table, so their writes land in
        the null page and their logits are ignored by the host."""
        page = self.cache.config.page_size
        gk = gather_pages(k_pages, block_tables)
        gv = gather_pages(v_pages, block_tables)
        # +1 pad keeps dynamic_update_slice from clamping at full capacity
        pad = ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0))
        gk, gv = jnp.pad(gk, pad), jnp.pad(gv, pad)

        def one(tok, ck, cv, ln):
            cache = {"k": ck[:, None], "v": cv[:, None]}
            logits, nc = self.model.forward_decode(
                params, tok[None, None], cache, ln,
                kv_len=jnp.reshape(ln + 1, (1,)))
            nk = jax.lax.dynamic_index_in_dim(nc["k"], ln, axis=2,
                                              keepdims=False)
            nv = jax.lax.dynamic_index_in_dim(nc["v"], ln, axis=2,
                                              keepdims=False)
            return logits[0, -1], nk[:, 0], nv[:, 0]

        logits, nk, nv = jax.vmap(one, in_axes=(0, 1, 1, 0))(
            tokens, gk, gv, lens)
        flat = flat_positions(block_tables, lens[:, None], page)[:, 0]
        k_pages = scatter_tokens(k_pages, flat, jnp.moveaxis(nk, 0, 1))
        v_pages = scatter_tokens(v_pages, flat, jnp.moveaxis(nv, 0, 1))
        return logits, k_pages, v_pages

    def _prefill_step(self, params, k_pages, v_pages, tokens, block_table,
                      done, n_valid):
        """One prompt chunk for one slot: tokens (1, chunk) padded,
        block_table (1, Pmax), done = tokens already in the cache, n_valid =
        real tokens in this chunk.  A chunk is a multi-token decode step at
        ``cache_index=done``; pad lanes write into the null page and the
        returned logits row is the last *valid* position's."""
        page = self.cache.config.page_size
        chunk = tokens.shape[1]
        gk = gather_pages(k_pages, block_table)
        gv = gather_pages(v_pages, block_table)
        pad = ((0, 0), (0, 0), (0, chunk), (0, 0), (0, 0))
        gk, gv = jnp.pad(gk, pad), jnp.pad(gv, pad)
        logits, nc = self.model.forward_decode(
            params, tokens, {"k": gk, "v": gv}, done,
            kv_len=jnp.reshape(done + n_valid, (1,)))
        ck = jax.lax.dynamic_slice_in_dim(nc["k"], done, chunk, axis=2)[:, 0]
        cv = jax.lax.dynamic_slice_in_dim(nc["v"], done, chunk, axis=2)[:, 0]
        positions = done + jnp.arange(chunk)
        flat = flat_positions(block_table, positions[None], page)[0]
        flat = jnp.where(jnp.arange(chunk) < n_valid, flat,
                         positions % page)              # pads -> null page
        k_pages = scatter_tokens(k_pages, flat, ck)
        v_pages = scatter_tokens(v_pages, flat, cv)
        last = jax.lax.dynamic_index_in_dim(logits, n_valid - 1, axis=1,
                                            keepdims=False)[0]
        return last, k_pages, v_pages

    # ------------------------------------------------------------ API
    def submit(self, request: Request) -> TokenStream:
        needed = len(request.prompt) + request.max_new - 1
        if needed > self.cache.config.slot_capacity:
            raise CacheOOM(
                f"request needs {needed} cache positions; per-slot capacity "
                f"is {self.cache.config.slot_capacity} "
                f"(max_context={self.cache.config.max_context})")
        if request.max_new < 1 or len(request.prompt) < 1:
            raise ValueError("need a non-empty prompt and max_new >= 1")
        if request.rid < 0:
            request.rid = self._next_rid
        self._next_rid = max(self._next_rid, request.rid) + 1
        request.prompt = np.asarray(request.prompt, np.int32)
        request.t_submit = self._clock()
        request.state = QUEUED
        self._queue.append(request)
        self._emit("request_start", request,
                   prompt_tokens=int(len(request.prompt)),
                   max_new=int(request.max_new))
        return TokenStream(self, request)

    def tick(self) -> dict:
        """Advance every in-flight request by one scheduling quantum."""
        self._admit()
        self._prefill_tick()
        self._decode_tick()
        return self.stats()

    def run_until_drained(self, max_ticks: int = 100_000) -> None:
        for _ in range(max_ticks):
            if not self._queue and not any(self._slots):
                return
            before = (len(self._queue), self._finished, self._generated,
                      sum(r.prefilled for r in self._slots if r))
            self.tick()
            after = (len(self._queue), self._finished, self._generated,
                     sum(r.prefilled for r in self._slots if r))
            if before == after:
                raise CacheOOM(
                    "scheduler made no progress — the queued request cannot "
                    "ever fit (pool too small for its prompt)")
        raise RuntimeError(f"not drained after {max_ticks} ticks")

    def stats(self) -> dict:
        active = [r for r in self._slots if r is not None]
        return {
            "queued": len(self._queue),
            "prefilling": sum(r.state == PREFILLING for r in active),
            "decoding": sum(r.state == DECODING for r in active),
            "free_slots": self.cache.free_slots,
            "free_pages": self.cache.free_pages,
            "finished": self._finished,
            "generated_tokens": self._generated,
            "evicted": self._evicted,
        }

    # ------------------------------------------------------------ phases
    def _admit(self) -> None:
        while self._queue and self.cache.free_slots:
            req = self._queue[0]
            try:
                slot = self.cache.alloc_slot(len(req.prompt))
            except CacheOOM:
                return                  # strict FIFO: head waits, no skipping
            self._queue.popleft()
            req.slot = slot
            req.state = PREFILLING
            req.prefilled = 0
            self._slots[slot] = req
            self._admit_order.append(req)

    def _evict(self, req: Request) -> None:
        """Preempt ``req``: release its slot/pages and put it back at the
        head of the queue.  Generation restarts from scratch on re-admission
        — deterministic sampling (greedy, or the per-request RNG, which is
        re-seeded) replays the same tokens, so streams stay consistent."""
        self.cache.free_slot(req.slot)
        self._slots[req.slot] = None
        self._admit_order.remove(req)
        self._rngs.pop(req.rid, None)
        req.slot = -1
        req.prefilled = 0
        req.tokens = []
        req.state = QUEUED
        self._queue.appendleft(req)
        self._evicted += 1
        self._emit("request_evicted", req)

    def _ensure_with_eviction(self, req: Request, n_tokens: int) -> bool:
        """Grow ``req``'s allocation, preempting the youngest
        *later-submitted* request while the pool is short (oversubscribed
        pools only — the default fully-provisioned pool never evicts).

        Age priority is what makes eviction live: if two requests each
        needing more than half the pool could evict each other, they would
        ping-pong forever.  Instead only strictly-younger requests (larger
        ``rid``) are preempted; when every page-holder is older, ``req``
        yields its own slot and retries after they finish.  The eldest
        in-flight request is therefore never evicted and always completes,
        which guarantees global progress.  Returns False when ``req``
        yielded (callers must not touch its slot this tick)."""
        while True:
            try:
                self.cache.ensure_capacity(req.slot, n_tokens)
                return True
            except CacheOOM:
                victim = next((r for r in reversed(self._admit_order)
                               if r is not req and r.rid > req.rid), None)
                if victim is not None:
                    self._evict(victim)
                    continue
                if any(r is not req for r in self._admit_order):
                    self._evict(req)        # yield to the elders, retry later
                    return False
                raise                       # alone and still short: pool is
                                            # too small for this request

    def _prefill_tick(self) -> None:
        req = next((r for r in self._admit_order if r.state == PREFILLING),
                   None)
        if req is None:
            return
        chunk = self.prefill_chunk
        done = req.prefilled
        n = min(chunk, len(req.prompt) - done)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = req.prompt[done:done + n]
        if not self._ensure_with_eviction(req, done + n):
            return                          # yielded its slot to an elder
        bt = jnp.asarray(self.cache.block_tables[req.slot][None])
        logits, self.cache.k_pages, self.cache.v_pages = self._prefill_fn(
            self.params, self.cache.k_pages, self.cache.v_pages,
            jnp.asarray(toks), bt, jnp.int32(done), jnp.int32(n))
        self.cache.advance(req.slot, n)
        req.prefilled = done + n
        if req.prefilled == len(req.prompt):
            self._append_token(req, np.asarray(logits), first=True)

    def _decode_tick(self) -> None:
        live = [r for r in self._admit_order if r.state == DECODING]
        # oldest first: an eviction preempts the youngest, never a request
        # that already reserved its next page this tick
        for r in list(live):
            if r.state != DECODING:
                continue                  # evicted by an earlier iteration
            self._ensure_with_eviction(
                r, int(self.cache.kv_len[r.slot]) + 1)
        live = [r for r in live if r.state == DECODING]
        if not live:
            return
        B = len(self._slots)
        pmax = self.cache.config.max_pages_per_slot
        tokens = np.zeros((B,), np.int32)
        tables = np.zeros((B, pmax), np.int32)        # idle lanes: null page
        lens = np.zeros((B,), np.int32)
        for r in live:
            tokens[r.slot] = r.tokens[-1]
            tables[r.slot] = self.cache.block_tables[r.slot]
            lens[r.slot] = self.cache.kv_len[r.slot]
        logits, self.cache.k_pages, self.cache.v_pages = self._decode_fn(
            self.params, self.cache.k_pages, self.cache.v_pages,
            jnp.asarray(tokens), jnp.asarray(tables), jnp.asarray(lens))
        logits = np.asarray(logits)
        for r in live:
            self.cache.advance(r.slot, 1)
            self._append_token(r, logits[r.slot])

    # ------------------------------------------------------------ helpers
    def _append_token(self, req: Request, logits: np.ndarray,
                      first: bool = False) -> None:
        rng = self._rngs.setdefault(
            req.rid, np.random.default_rng(req.seed + req.rid))
        req.tokens.append(self._sample(logits, req, rng))
        self._generated += 1
        if first:
            req.state = DECODING
            req.t_first = self._clock()
        if len(req.tokens) >= req.max_new:
            self._finish(req)

    def _finish(self, req: Request) -> None:
        req.state = FINISHED
        req.t_end = self._clock()
        self.cache.free_slot(req.slot)
        self._slots[req.slot] = None
        self._admit_order.remove(req)
        self._rngs.pop(req.rid, None)
        self._finished += 1
        self._emit("request_end", req,
                   prompt_tokens=int(len(req.prompt)),
                   generated_tokens=len(req.tokens),
                   ttft_s=req.ttft_s, tpot_s=req.tpot_s,
                   total_s=req.t_end - req.t_submit)

    def _emit(self, event: str, req: Request, **fields) -> None:
        if self.sink is not None:
            self.sink.emit(event, rid=req.rid, queue_depth=len(self._queue),
                           **fields)
