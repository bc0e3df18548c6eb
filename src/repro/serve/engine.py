"""Batched decode engine: continuous batching with per-slot positions.

Serving substrate for the inference-shaped cells (decode_32k, long_500k):
a slot-based scheduler admits requests into a fixed decode batch, runs
the jitted step function (whose FFN is the paper's fused GEMV+AllReduce),
samples greedily via the vocab-sharded argmax, and retires finished
sequences.  Token-level continuous batching — a slot is re-admitted the
step after its sequence finishes.

Every slot carries its *own* position: the engine feeds a ``pos [B]``
vector to the model so a request admitted into a freed slot starts at
position 0 (fresh RoPE phases, fresh causal mask) while its neighbors
keep counting.  The old shared scalar position made slot reuse read the
previous occupant's stale KV rows — the cross-request contamination bug.

Two backends:

:class:`DecodeEngine`
    Dense ``[L, B, S_max]`` cache, one token per slot per step.  Prompt
    replay happens through the decode path token-by-token.
:class:`PagedDecodeEngine`
    Paged/block KV (:mod:`repro.serve.kv_cache` host side,
    :func:`repro.models.attention.paged_attention` device side) with
    *chunked prefill*: prompts are fed ``chunk`` tokens per step through
    the same jitted ``serve_step`` that decodes, so a step mixes prefill
    chunks and decode slots in one schedule (``n_new`` per slot: 0 idle,
    1 decode, >1 prefill).  Exactly two graphs are traced per engine —
    C=chunk and the C=1 decode fast path.  Blocks are freed the moment a
    request retires; pool exhaustion preempts the newest-admitted
    request back to the queue instead of corrupting a neighbor.

The paged engine marks its host work for the profiler: each ``step()`` is
one ``serve.tick`` span holding ``serve.schedule``, ``serve.dispatch``,
``serve.sample`` and ``serve.commit`` (``jax.profiler.TraceAnnotation``,
which records only while a trace is running), and it counts what it did
in :class:`EngineStats`.

Elastic serving: :meth:`reshard` swaps the step function / cache (or
block pool) for a different mesh mid-flight.  In-flight requests go back
to the queue front with their generated tokens intact; on re-admission
the engine replays prompt + generated tokens through the new cache and
generation resumes where it stopped — requests survive a mesh shrink,
they just pay a replay delay.  :func:`serve_with_chaos` drives the
engine under a :class:`~repro.runtime.chaos.FaultPlan`.
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.runtime.chaos import RankLost
from repro.serve.kv_cache import FREE_BLOCK, OutOfBlocks, PagedKVCache

log = logging.getLogger("repro.serve")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list
    max_new: int = 32
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    truncated: bool = False   # retired at the cache bound, not eos/max_new
    # engine-managed: tokens to replay through the cache before sampling
    # resumes (prompt, plus already-generated tokens after a reshard),
    # and how many of them have been fed so far.
    prefix: list = dataclasses.field(default_factory=list)
    consumed: int = 0
    # SLO timestamps (engine clock): submission, first generated token,
    # retirement.  bench_serve derives TTFT / per-token latency from these.
    t_submit: float | None = None
    t_first: float | None = None
    t_done: float | None = None


@dataclasses.dataclass
class EngineStats:
    """What :class:`PagedDecodeEngine` did, counted where each event
    happens.  Pool occupancy is the block cache's own ``kv.stats()``."""
    ticks: int = 0            # steps that ran the serve program
    ticks_wide: int = 0       # of them at C = chunk
    prefill_tokens: int = 0   # prompt (or replayed) tokens fed
    decode_tokens: int = 0    # sampled tokens fed back, one per slot
    admitted: int = 0
    admit_deferred: int = 0   # admissions put back for want of blocks
    preempted: int = 0
    truncated: int = 0        # retired at the cache bound
    kv_blocks_read: int = 0   # live table blocks of the working slots
    kv_blocks_table: int = 0  # working slots x table width


class DrainResult(list):
    """Finished requests, plus whether the engine actually drained.

    ``drained`` is False when :meth:`run_until_drained` stopped at
    ``max_steps`` with work still queued or in flight — previously
    indistinguishable from a clean drain."""

    drained: bool = True


class _EngineBase:
    """Queue/slot bookkeeping shared by the dense and paged engines."""

    batch: int
    slots: list[Request | None]
    queue: collections.deque

    def __init__(self, batch_size: int, eos_id: int, bos_id: int,
                 time_fn: Callable[[], float]):
        self.batch = batch_size
        self.eos = eos_id
        self.bos = bos_id
        self.time_fn = time_fn
        self.slots = [None] * batch_size
        self.queue = collections.deque()

    def submit(self, req: Request):
        if req.t_submit is None:
            req.t_submit = self.time_fn()
        self.queue.append(req)

    def _pending(self) -> bool:
        return any(s is not None for s in self.slots) or bool(self.queue)

    def _retire(self, i: int, req: Request, finished: list):
        req.done = True
        req.t_done = self.time_fn()
        self.slots[i] = None
        finished.append(req)

    def _pop_admittable(self, finished: list) -> Request | None:
        """Next queued request, retiring zero-budget ones on the spot.

        A ``max_new=0`` request must finish with *zero* generated tokens
        — it never touches a slot or the cache (the old engine decoded
        one token before checking the budget)."""
        while self.queue:
            req = self.queue.popleft()
            if req.max_new <= 0:
                req.done = True
                req.t_done = self.time_fn()
                finished.append(req)
                continue
            return req
        return None

    def run_until_drained(self, max_steps: int = 10_000,
                          liveness=None) -> DrainResult:
        """Drain the queue; with ``liveness`` (a :class:`~repro.runtime.
        watchdog.LivenessMonitor`), every tick first checks peer
        heartbeats and the engine step runs guarded — a peer process
        dying mid-decode raises :class:`~repro.runtime.chaos.RankLost`
        from *real* liveness instead of hanging the fleet.  The raise
        leaves host-side bookkeeping at the last completed tick, so
        :func:`request_journal` still snapshots a consistent in-flight
        set for the respawned engine."""
        finished = DrainResult()
        steps = 0
        while self._pending() and steps < max_steps:
            if liveness is not None:
                liveness.check()
                _, fin = liveness.guarded(self.step)
            else:
                _, fin = self.step()
            finished.extend(fin)
            steps += 1
        finished.drained = not self._pending()
        if not finished.drained:
            log.warning(
                "run_until_drained stopped at max_steps=%d with %d queued "
                "and %d in-flight requests — results are TRUNCATED",
                max_steps, len(self.queue),
                sum(s is not None for s in self.slots))
        return finished

    def step(self):  # pragma: no cover - abstract
        raise NotImplementedError


class DecodeEngine(_EngineBase):
    """Dense-cache engine (one token per slot per step, per-slot pos)."""

    def __init__(self, decode_fn: Callable, init_cache_fn: Callable,
                 batch_size: int, eos_id: int = -1, bos_id: int = 0,
                 max_seq: int | None = None,
                 time_fn: Callable[[], float] = time.monotonic):
        """decode_fn(tokens [B,1], cache, pos [B]) -> (logits [B,1,V], cache).

        ``bos_id`` seeds the first decode step for empty-prompt requests
        (unconditional generation).  ``max_seq`` is the cache bound: a
        slot reaching it retires its request with ``truncated=True``
        instead of silently overwriting the last cache row (pass the
        model's ``cfg.max_seq``; ``None`` disables the check for
        cacheless fakes)."""
        super().__init__(batch_size, eos_id, bos_id, time_fn)
        self.decode_fn = decode_fn
        self.init_cache_fn = init_cache_fn
        self.max_seq = max_seq
        self.cache = init_cache_fn(batch_size)
        self.cur_tok = np.zeros((batch_size, 1), np.int32)
        self.pos = np.zeros(batch_size, np.int32)   # per-slot, not shared

    def _admit(self, finished: list):
        for i in range(self.batch):
            if self.slots[i] is None and self.queue:
                req = self._pop_admittable(finished)
                if req is None:
                    return
                self.slots[i] = req
                self.pos[i] = 0
                # prompt (and, after a reshard, the already-generated
                # tokens) is consumed token-by-token — prefill via decode;
                # the paged engine runs the chunked-prefill graph instead.
                req.prefix = list(req.prompt) + list(req.tokens)
                if req.prefix:
                    self.cur_tok[i, 0] = req.prefix[0]
                    req.consumed = 1
                else:  # empty prompt: unconditional generation from BOS
                    self.cur_tok[i, 0] = self.bos
                    req.consumed = 0

    def _retire_at_bound(self, finished: list):
        """The cache holds ``max_seq`` positions; a slot about to write
        past the end retires truncated (the write would be dropped and
        attention would walk garbage) instead of silently clobbering."""
        if self.max_seq is None:
            return
        for i, req in enumerate(self.slots):
            if req is not None and self.pos[i] >= self.max_seq:
                log.warning("request %d hit cache bound max_seq=%d after "
                            "%d generated tokens — retiring truncated",
                            req.uid, self.max_seq, len(req.tokens))
                req.truncated = True
                self._retire(i, req, finished)

    def step(self):
        finished: list[Request] = []
        self._retire_at_bound(finished)
        self._admit(finished)
        logits, self.cache = self.decode_fn(
            jnp.asarray(self.cur_tok), self.cache, jnp.asarray(self.pos))
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.pos[i] += 1
            if req.consumed < len(req.prefix):
                self.cur_tok[i, 0] = req.prefix[req.consumed]
                req.consumed += 1
                continue
            tok = int(nxt[i])
            if req.t_first is None:
                req.t_first = self.time_fn()
            req.tokens.append(tok)
            self.cur_tok[i, 0] = tok
            if tok == self.eos or len(req.tokens) >= req.max_new:
                self._retire(i, req, finished)
        return nxt, finished

    def reshard(self, decode_fn: Callable, init_cache_fn: Callable,
                batch_size: int | None = None) -> int:
        """Swap in a decode function/cache for a new (smaller) mesh.

        In-flight requests are pushed back to the queue *front* in slot
        order — they were admitted first, they re-admit first — keeping
        their generated tokens; re-admission replays them through the
        fresh cache.  Returns how many requests were re-queued."""
        inflight = [r for r in self.slots if r is not None]
        for r in reversed(inflight):
            self.queue.appendleft(r)
        if batch_size is not None:
            self.batch = batch_size
        self.decode_fn = decode_fn
        self.init_cache_fn = init_cache_fn
        self.cache = init_cache_fn(self.batch)
        self.slots = [None] * self.batch
        self.cur_tok = np.zeros((self.batch, 1), np.int32)
        self.pos = np.zeros(self.batch, np.int32)
        return len(inflight)


class PagedDecodeEngine(_EngineBase):
    """Paged-KV engine with chunked prefill in a mixed schedule."""

    def __init__(self, serve_fn: Callable, init_pool_fn: Callable,
                 batch_size: int, *, num_blocks: int, block_size: int,
                 max_seq: int, chunk: int = 8, eos_id: int = -1,
                 bos_id: int = 0, n_stripes: int = 1,
                 time_fn: Callable[[], float] = time.monotonic):
        """serve_fn(tokens [B,C], pool, tables [B,MB], pos [B], n_new [B])
        -> (logits [B,V], pool); init_pool_fn(num_blocks, block_size) ->
        pool pytree.  ``chunk`` is the prefill chunk width C (the second
        traced graph; decode steps use C=1).  ``max_seq`` bounds each
        request's block table; ``n_stripes`` should be the tp size so
        allocation balances across rank stripes."""
        super().__init__(batch_size, eos_id, bos_id, time_fn)
        self.serve_fn = serve_fn
        self.init_pool_fn = init_pool_fn
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_seq = max_seq
        self.chunk = max(1, chunk)
        self.n_stripes = n_stripes
        self.pool = init_pool_fn(num_blocks, block_size)
        self.kv = PagedKVCache(num_blocks, block_size,
                               max_blocks_per_request=-(-max_seq // block_size),
                               n_stripes=n_stripes)
        self.cur_tok = np.zeros(batch_size, np.int32)
        self.pos = np.zeros(batch_size, np.int32)
        # feed list per slot: prefix (or [bos] for empty prompts) still to
        # be pushed through the prefill path; consumed indexes into it.
        self._feed: list[list] = [[] for _ in range(batch_size)]
        self.stats = EngineStats()

    # -- admission / preemption -------------------------------------------
    def _admit(self, finished: list):
        for i in range(self.batch):
            if self.slots[i] is None and self.queue:
                req = self._pop_admittable(finished)
                if req is None:
                    return
                req.prefix = list(req.prompt) + list(req.tokens)
                feed = list(req.prefix) or [self.bos]
                try:
                    self.kv.register(req.uid)
                    self.kv.ensure(req.uid, min(len(feed), self.max_seq))
                except OutOfBlocks:
                    # pool full: defer admission, keep FIFO order
                    self.kv.release(req.uid)
                    self.queue.appendleft(req)
                    self.stats.admit_deferred += 1
                    return
                self.stats.admitted += 1
                self.slots[i] = req
                self.pos[i] = 0
                req.consumed = 0
                self._feed[i] = feed

    def _preempt(self, i: int, req: Request):
        """Pool exhausted mid-flight: push the request back to the queue
        (front — it keeps its admission-order priority) and free its
        blocks.  Re-admission replays prompt + generated tokens through
        the chunked-prefill path."""
        log.warning("preempting request %d (pool exhausted): %d tokens "
                    "generated, will replay on re-admission",
                    req.uid, len(req.tokens))
        self.kv.release(req.uid)
        self.slots[i] = None
        self._feed[i] = []
        self.queue.appendleft(req)
        self.stats.preempted += 1

    def _retire_at_bound(self, finished: list):
        for i, req in enumerate(self.slots):
            if req is not None and self.pos[i] >= self.max_seq:
                log.warning("request %d hit cache bound max_seq=%d after "
                            "%d generated tokens — retiring truncated",
                            req.uid, self.max_seq, len(req.tokens))
                req.truncated = True
                self.stats.truncated += 1
                self.kv.release(req.uid)
                self._retire(i, req, finished)

    # -- the mixed prefill/decode step ------------------------------------
    def step(self):
        finished: list[Request] = []
        st = self.stats
        with TraceAnnotation("serve.tick") as span:
            with TraceAnnotation("serve.schedule"):
                tokens, n_new, remaining, tables, prefill, decode = (
                    self._schedule(finished))
            C = tokens.shape[1]
            blocks_read = int((tables != FREE_BLOCK).sum())
            span.set_metadata(tick=st.ticks, width=C, decode=decode,
                              prefill_tokens=prefill, queued=len(self.queue),
                              blocks_used=self.kv.used_blocks,
                              blocks_read=blocks_read)
            if not n_new.any():
                return np.zeros(self.batch, np.int32), finished
            with TraceAnnotation("serve.dispatch"):
                logits, self.pool = self.serve_fn(
                    jnp.asarray(tokens), self.pool, jnp.asarray(tables),
                    jnp.asarray(self.pos), jnp.asarray(n_new))
            st.ticks += 1
            st.ticks_wide += C == self.chunk
            st.prefill_tokens += prefill
            st.decode_tokens += decode
            st.kv_blocks_read += blocks_read
            st.kv_blocks_table += int((n_new > 0).sum()) * tables.shape[1]
            with TraceAnnotation("serve.sample"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            with TraceAnnotation("serve.commit"):
                self._commit(nxt, n_new, remaining, finished)
        return nxt, finished

    def _schedule(self, finished: list):
        """Retire at the bound, admit, choose the chunk width, grow each
        working slot's blocks (preempting where the pool is out) and
        build the step's inputs.  Returns (tokens [B, C], n_new, remaining
        feed per slot, tables, prefill tokens, decoding slots)."""
        self._retire_at_bound(finished)
        self._admit(finished)
        # chunk width: the wide graph only when some slot is mid-prefill
        remaining = [0 if r is None else len(self._feed[i]) - r.consumed
                     for i, r in enumerate(self.slots)]
        C = self.chunk if any(rem > 1 for rem in remaining) else 1

        tokens = np.zeros((self.batch, C), np.int32)
        n_new = np.zeros(self.batch, np.int32)
        prefill = decode = 0
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            rem = remaining[i]
            if rem > 0:
                n = min(rem, C, self.max_seq - int(self.pos[i]))
                tokens[i, :n] = self._feed[i][req.consumed:req.consumed + n]
            else:
                n = 1
                tokens[i, 0] = self.cur_tok[i]
            try:
                self.kv.ensure(req.uid, int(self.pos[i]) + n)
            except OutOfBlocks:
                self._preempt(i, req)
                continue
            n_new[i] = n
            if rem > 0:
                prefill += n
            else:
                decode += 1
        tables = self.kv.tables_for(
            [r.uid if r is not None and n_new[i] > 0 else None
             for i, r in enumerate(self.slots)])
        return tokens, n_new, remaining, tables, prefill, decode

    def _commit(self, nxt, n_new, remaining, finished: list):
        """Advance each slot that worked this tick: its position, its
        prefill progress or its sampled token, and retirement."""
        for i, req in enumerate(self.slots):
            if req is None or n_new[i] == 0:
                continue
            n = int(n_new[i])
            rem = remaining[i]
            self.pos[i] += n
            if rem > 0:
                req.consumed += n
                if req.consumed < len(self._feed[i]):
                    continue   # still prefilling: logits discarded
            # prefill just finished (its last-valid logits predict the
            # first new token) or plain decode: sample greedily
            tok = int(nxt[i])
            if req.t_first is None:
                req.t_first = self.time_fn()
            req.tokens.append(tok)
            self.cur_tok[i] = tok
            if tok == self.eos or len(req.tokens) >= req.max_new:
                self.kv.release(req.uid)
                self._retire(i, req, finished)

    # -- elasticity --------------------------------------------------------
    def reshard(self, serve_fn: Callable, init_pool_fn: Callable,
                batch_size: int | None = None,
                num_blocks: int | None = None,
                block_size: int | None = None,
                n_stripes: int | None = None) -> int:
        """Swap the serve function/pool for a new mesh, migrating requests.

        Block tables are host-side state, but the pool *contents* live on
        the lost mesh — so migration re-queues in-flight requests (tokens
        intact) and rebuilds their KV through the chunked-prefill path on
        the new pool, exactly like the dense engine's replay.  Returns
        how many requests were re-queued."""
        inflight = [r for r in self.slots if r is not None]
        for r in reversed(inflight):
            self.queue.appendleft(r)
        if batch_size is not None:
            self.batch = batch_size
        self.num_blocks = num_blocks or self.num_blocks
        self.block_size = block_size or self.block_size
        self.n_stripes = n_stripes or self.n_stripes
        self.serve_fn = serve_fn
        self.init_pool_fn = init_pool_fn
        self.pool = init_pool_fn(self.num_blocks, self.block_size)
        self.kv = PagedKVCache(
            self.num_blocks, self.block_size,
            max_blocks_per_request=-(-self.max_seq // self.block_size),
            n_stripes=self.n_stripes)
        self.slots = [None] * self.batch
        self.cur_tok = np.zeros(self.batch, np.int32)
        self.pos = np.zeros(self.batch, np.int32)
        self._feed = [[] for _ in range(self.batch)]
        return len(inflight)


def request_journal(engine) -> list[dict]:
    """JSON-serializable snapshot of every *unfinished* request.

    In-flight slots first (admission order), then the queue — the order
    re-admission should honor.  Generated tokens ride along, so a
    respawned engine (cross-process elastic recovery) resubmits through
    :func:`resubmit_journal` and each request resumes exactly where it
    stopped: the replay path rebuilds its cache from prompt + tokens,
    the same mechanism :meth:`DecodeEngine.reshard` uses in-process."""
    live = [r for r in engine.slots if r is not None] + list(engine.queue)
    return [{"uid": r.uid, "prompt": list(r.prompt), "max_new": r.max_new,
             "tokens": list(r.tokens)} for r in live]


def resubmit_journal(engine, journal: list[dict]) -> int:
    """Re-admit journaled requests (tokens intact) into a fresh engine."""
    for e in journal:
        engine.submit(Request(uid=e["uid"], prompt=list(e["prompt"]),
                              max_new=e["max_new"],
                              tokens=list(e["tokens"])))
    return len(journal)


def serve_with_chaos(engine, plan, *,
                     reshard_fn: Callable | None = None,
                     sleep_fn: Callable[[float], None] = time.sleep,
                     max_steps: int = 10_000):
    """Drain the engine under a :class:`~repro.runtime.chaos.FaultPlan`.

    Per tick: ``slow_link`` sleeps its delay before stepping; ``timeout``
    / ``rank_fail`` / ``nan_wire`` drop the tick entirely (the collective
    failed, nothing was committed — the same decode step retries next
    tick); ``rank_loss`` calls ``reshard_fn(engine)`` — the drain-reshard-
    resume path — or raises :class:`RankLost` if no handler is wired.

    Returns ``(finished, stats)`` where stats counts ticks, dropped
    ticks, and reshards, and carries ``drained`` — False when the loop
    stopped at ``max_steps`` with requests still queued or in flight
    (previously indistinguishable from a clean drain).
    """
    finished = []
    stats = {"ticks": 0, "dropped": 0, "reshards": 0, "drained": True}
    tick = 0
    while engine._pending() and tick < max_steps:
        events = plan.at(tick) if plan is not None else ()
        tick += 1
        stats["ticks"] += 1
        dropped = False
        for ev in events:
            if ev.kind == "slow_link":
                sleep_fn(ev.delay_s)
            elif ev.kind == "rank_loss":
                if reshard_fn is None:
                    raise RankLost(ev.rank)
                reshard_fn(engine)
                stats["reshards"] += 1
            else:  # timeout / rank_fail / nan_wire: the tick is lost
                dropped = True
        if dropped:
            stats["dropped"] += 1
            continue
        _, fin = engine.step()
        finished.extend(fin)
    stats["drained"] = not engine._pending()
    if not stats["drained"]:
        log.warning(
            "serve_with_chaos stopped at max_steps=%d with %d queued and "
            "%d in-flight requests — results are TRUNCATED",
            max_steps, len(engine.queue),
            sum(s is not None for s in engine.slots))
    return finished, stats
