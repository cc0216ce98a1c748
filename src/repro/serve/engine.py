"""Serving engine: paged KV cache + continuous batching + async overlap.

The engine glues three pieces (see repro/serve/README.md):

* :class:`~repro.serve.pool.BlockPool` — host-side lease accounting for
  the paged KV cache (``cache_mode="paged"``, the default for
  attention-only architectures): slots lease fixed-size blocks on
  demand instead of reserving ``slots * max_len`` dense rings.
* :class:`~repro.serve.scheduler.Scheduler` — continuous batching:
  requests are admitted into free slots *between* ticks, and each tick
  is one jitted dispatch (``Model.serve_step`` + in-jit batched
  sampling, cache buffers donated) in which every row independently
  carries a prefill chunk, a decode token, or nothing.
* an async loop — dispatches tick t+1 before processing tick t's
  sampled tokens, so host-side bookkeeping overlaps device work.
  Decode ticks read their input token from a device-resident
  next-token buffer (updated inside the previous dispatch), so no
  host round-trip sits on the critical path.  Length-based completion
  is host-predictable; EOS detection lags one tick — the speculative
  extra token is discarded (epoch-guarded) and the slot released.

Cache modes:

* ``paged``  — batched direct-write prefill + paged full-length
  entries.  Requires an attention-only architecture (no MoE, no
  recurrent state, no cross-attention): padded rows in a shared
  dispatch are provably inert only for the masked-scatter KV path.
* ``dense``  — same batched path over dense rings (the equivalence
  reference for paged, and the right choice when ``max_len`` is small).
* ``legacy`` — isolated batch=1 chunked prefill scattered into the
  slot (the pre-paged path), batched decode.  Automatically selected
  for MoE / recurrent / encoder-decoder architectures, where padded
  prefill rows would corrupt per-slot recurrent state or couple slots
  through expert capacity.

Cross-host: admission goes through a Communicator agg+bcast agreement
round (:func:`~repro.serve.scheduler.agree_admission_count`); load and
drain are Communicator barriers.

Tracing is always on and costs about a microsecond per span with no
profiler running.  Each loop iteration opens ``jax.profiler``
``TraceAnnotation`` spans (``serve.admit``, ``serve.plan``,
``serve.prepare``, ``serve.dispatch``, ``serve.readback``,
``serve.record``, ``serve.wait``) that carry the tick's id (``tick``, the
engine's dispatch counter) and land on the device trace's clock when a
profiler runs; :class:`EngineStats` counts ticks, tokens and host
seconds for every ``run_trace``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.comms import Communicator
from repro.configs.base import ArchConfig
from repro.models import cache as cache_lib
from repro.models.model import Model
from repro.serve.pool import BlockPool
from repro.serve.scheduler import Scheduler, TickPlan, agree_admission_count

_LOAD_MSG = "Engine.load() must be called before admission"


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    eos_id: Optional[int] = None  # stop token (detected one tick late)
    out_tokens: Optional[List[int]] = None


class ServeResult(dict):
    """``{rid: [tokens]}`` for completed requests, plus:

    * ``truncated`` — True when ``max_steps`` hit before the queue
      drained (the old engine silently dropped this);
    * ``unfinished`` — ``{rid: partial tokens}`` for in-flight and
      never-admitted requests at truncation;
    * ``metrics`` — ``{rid: {arrival_s, admit_s, ttft_s, done_s,
      tokens}}`` for every admitted request (host seconds since the run
      began; ``admit_s`` is when it took a slot, 0.0 for one that held
      a slot when the run began; TTFT includes the one-tick pipeline
      lag; ``ttft_s`` is None and ``done_s`` absent until they happen).
    """

    def __init__(self, done, truncated: bool, unfinished, metrics):
        super().__init__(done)
        self.truncated = truncated
        self.unfinished = dict(unfinished)
        self.metrics = dict(metrics)


@dataclasses.dataclass
class EngineStats:
    """Counters of one ``run_trace`` (reset when it starts).

    ``host_s`` is the loop's own time: wall seconds in the loop outside
    blocking read-backs and arrival sleeps.  Every field is a plain host
    number, so a copy taken mid-run is a consistent snapshot of the
    iterations before it."""

    ticks: Dict[str, int] = dataclasses.field(default_factory=dict)
    chunk_tokens: int = 0      # prompt tokens carried by chunk ticks
    chunk_rows: int = 0        # their row capacity: slots x chunk width
    decode_tokens: int = 0
    resets: int = 0            # slot-reset programs dispatched
    btab_uploads: int = 0      # block-table uploads
    loop_s: float = 0.0        # wall seconds of the loop so far
    readback_s: float = 0.0    # blocked reading a tick's tokens back
    wait_s: float = 0.0        # asleep until the next arrival

    @property
    def host_s(self) -> float:
        return self.loop_s - self.readback_s - self.wait_s

    @property
    def dispatched(self) -> int:
        return sum(self.ticks.values())


def _supports_batched(cfg: ArchConfig) -> bool:
    """Archs whose padded rows are inert in a shared prefill dispatch."""
    return not (cfg.num_experts or cfg.xlstm_pattern
                or cfg.family == "hybrid" or cfg.encoder_layers
                or cfg.xattn_every)


def _upload(a: np.ndarray) -> jax.Array:
    """Put a host array that is edited in place later on the device.
    On the CPU backend ``jnp.asarray`` (and even ``jnp.array``) may alias
    a 64-byte-aligned numpy buffer, so an asynchronous dispatch would
    read whatever the host wrote after the call; copying on the host
    first fixes the value that was dispatched."""
    return jnp.asarray(a.copy())


def split_btab(cache) -> Tuple[dict, Optional[jax.Array]]:
    """``(cache without its 'btab' leaves, the block table)``.  Every
    paged entry reads the same host-leased table; the engine keeps that
    one array beside the donated cache, since a buffer that appears
    twice in a donated argument is refused by the runtime."""
    btab, out = None, {}
    for name, ent in cache.items():
        btab = ent.get("btab", btab)
        out[name] = {k: v for k, v in ent.items() if k != "btab"}
    return out, btab


class Engine:
    def __init__(self, cfg: ArchConfig, mesh: Mesh, slots: int,
                 max_len: int, seed: int = 0, cache_mode: str = "auto",
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 policy: str = "conservative", overlap: bool = True):
        self.cfg = cfg
        self.model = Model(cfg, mesh)
        self.comm = Communicator.for_mesh(mesh)
        self.slots = slots
        self.max_len = max_len
        self.key = jax.random.PRNGKey(seed)
        self.overlap = overlap
        batched_ok = _supports_batched(cfg)
        if cache_mode == "auto":
            cache_mode = "paged" if batched_ok else "legacy"
        if cache_mode in ("paged", "dense") and not batched_ok:
            raise ValueError(
                f"cache_mode={cache_mode!r} needs the batched prefill "
                f"path, unavailable for arch {cfg.name!r} (recurrent/"
                f"MoE/enc-dec); use cache_mode='legacy'")
        if cache_mode not in ("paged", "dense", "legacy"):
            raise ValueError(f"unknown cache_mode {cache_mode!r}")
        self.cache_mode = cache_mode
        self.block_size = block_size
        m_blocks = -(-max_len // block_size)
        self.num_blocks = slots * m_blocks if num_blocks is None \
            else num_blocks
        self.page_spec = cache_lib.PageSpec(block_size, self.num_blocks)
        #: names of the paged cache entries: they share one block table,
        #: held apart from the donated cache (see ``split_btab``)
        self.paged_entries = tuple(
            name for name, ent in self.model.cache_specs(
                slots, max_len, paged=self.page_spec).items()
            if "btab" in ent) if cache_mode == "paged" else ()
        # a prompt is shorter than max_len, so a wider chunk would only
        # carry padding
        self.sched = Scheduler(slots, min(cfg.prefill_chunk, max_len),
                               policy)
        self.pool: Optional[BlockPool] = None
        self.params = None
        self.cache = None
        self.next_buf = None
        self.temps = np.zeros((slots,), np.float32)
        self.requests: Dict[int, Request] = {}
        self._done: Dict[int, List[int]] = {}
        self._metrics: Dict[int, dict] = {}
        self.stats = EngineStats()
        self._tick = 0                    # id of the next dispatched tick
        self._reset_mask = np.zeros((slots,), bool)
        self.btab = None
        self._dispatch_fn = jax.jit(self._dispatch_body,
                                    donate_argnums=(7, 8))
        self._reset_fn = jax.jit(self._reset_body, donate_argnums=(0,))
        self._extend = jax.jit(self.model.extend)
        self._scatter = jax.jit(self._scatter_body)
        self._sample1 = jax.jit(self._sample1_body)

    # ------------------------------------------------------------------ load
    def load(self, params) -> None:
        self.params = params
        if self.cache_mode == "paged":
            self.cache, self.btab = split_btab(self.model.init_cache(
                self.slots, self.max_len, paged=self.page_spec))
            self.pool = BlockPool(self.num_blocks, self.block_size,
                                  self.slots, self.max_len)
        else:
            self.cache = self.model.init_cache(self.slots, self.max_len)
            self.pool = None
        self.next_buf = jnp.zeros((self.slots,), jnp.int32)
        # every rank must hold weights + cache before admission starts
        self.comm.sync()

    # ----------------------------------------------------------- jit bodies
    def _join_btab(self, cache, btab):
        """Inverse of :func:`split_btab` for this engine's cache."""
        return {name: dict(ent, btab=btab) if name in self.paged_entries
                else ent for name, ent in cache.items()}

    def _dispatch_body(self, params, tokens, use_next, starts, lengths,
                      temps, key, next_buf, cache, btab):
        """One tick: serve_step + batched sampling, all in one dispatch.
        Rows with ``use_next`` read their (single) token from the device
        next-token buffer; idle rows (length 0) touch nothing."""
        first = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None] == 0
        tok = jnp.where(use_next[:, None] & first, next_buf[:, None],
                        tokens)
        logits, cache = self.model.serve_step(
            params, tok, starts, lengths, self._join_btab(cache, btab))
        cache, _ = split_btab(cache)
        lg = logits[:, -1].astype(jnp.float32)                    # (B, V)
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        drawn = jax.random.categorical(
            key, lg / jnp.maximum(temps, 1e-6)[:, None]).astype(jnp.int32)
        nxt = jnp.where(temps > 0, drawn, greedy)
        next_buf = jnp.where(lengths > 0, nxt, next_buf)
        return nxt, next_buf, cache

    def _reset_body(self, cache, mask, btab):
        cache, _ = split_btab(self.model.reset_cache_slots(
            self._join_btab(cache, btab), mask))
        return cache

    def _sample1_body(self, lg, temp, key):
        """Single-row sampler for the legacy path's prefill logits —
        same formula as the batched tick sampler."""
        lg = lg.reshape(-1).astype(jnp.float32)
        greedy = jnp.argmax(lg).astype(jnp.int32)
        drawn = jax.random.categorical(
            key, lg / jnp.maximum(temp, 1e-6)).astype(jnp.int32)
        return jnp.where(temp > 0, drawn, greedy)

    def _scatter_body(self, big, one, slot):
        """Write a batch=1 dense cache into batch row ``slot``.  'pos'
        leaves carry batch at dim 0, tensor leaves at dim 1."""
        out = {}
        for name, ent in big.items():
            out[name] = {}
            for k, v in ent.items():
                o = one[name][k]
                if k == "pos":
                    out[name][k] = v.at[slot].set(o[0])
                else:
                    out[name][k] = v.at[:, slot].set(o[:, 0])
        return out

    # ------------------------------------------------------------ admission
    def _cap_for(self, req: Request) -> int:
        p = int(len(req.prompt))
        if p + 1 > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {p} does not fit "
                f"max_len {self.max_len} (need prompt + 1)")
        return min(req.max_new_tokens, self.max_len - p)

    def _admittable(self, reqs: List[Request]) -> int:
        """How many of ``reqs`` (in order) this rank can admit now."""
        free = len(self.sched.free_slots())
        n, extra = 0, 0
        for req in reqs[:free]:
            if self.pool is not None:
                worst = min(self.pool.blocks_for(len(req.prompt)
                                                 + self._cap_for(req)),
                            self.pool.max_blocks_per_slot)
                if self.pool.committed + extra + worst > self.pool.num_blocks:
                    break
                extra += worst
            n += 1
        return n

    def admit(self, req: Request, arrival_s: float = 0.0) -> bool:
        """Admit one request into a free slot; False when full.  Part of
        the old per-request API — run_to_completion/run_trace admit
        through the same path with cross-host agreement."""
        if self.params is None:
            raise RuntimeError(_LOAD_MSG)
        if self._admittable([req]) < 1:
            return False
        self._admit_one(req, arrival_s)
        return True

    def _admit_one(self, req: Request, arrival_s: float) -> None:
        slot = self.sched.free_slots()[0]
        cap = self._cap_for(req)
        req.out_tokens = []
        self.requests[req.rid] = req
        self._metrics[req.rid] = {"arrival_s": arrival_s,
                                  "admit_s": self._now(), "ttft_s": None}
        if cap <= 0:                      # nothing to generate
            self._finalize(req.rid, arrival_s)
            return
        st = self.sched.assign(slot, req.rid, np.asarray(req.prompt),
                               cap, req.temperature, req.eos_id)
        self.temps[slot] = req.temperature
        if self.pool is not None:
            self.pool.reserve(slot, st.prompt_len + cap)
        if self.cache_mode == "legacy":
            self._legacy_prefill(slot, st)

    def _legacy_prefill(self, slot: int, st) -> None:
        """Isolated batch=1 chunked prefill, scattered into the slot —
        blocking, but safe for recurrent/MoE archs where padded rows in
        a shared dispatch are not inert."""
        prompt = st.prompt
        chunk = self.cfg.prefill_chunk
        cache1 = self.model.init_cache(1, self.max_len)
        pos, logits = 0, None
        while pos < len(prompt):
            n = chunk if len(prompt) - pos >= chunk else 1
            tok = jnp.asarray(prompt[pos:pos + n][None])
            start = jnp.asarray([pos], jnp.int32)
            logits, cache1 = self._extend(self.params, tok, start, cache1,
                                          {})
            pos += n
        self.cache = self._scatter(self.cache, cache1,
                                   jnp.asarray(slot, jnp.int32))
        self.key, sub = jax.random.split(self.key)
        tok0 = self._sample1(logits, jnp.asarray(st.temperature), sub)
        self.next_buf = self.next_buf.at[slot].set(tok0)
        st.fed = st.prompt_len
        st.sampled = 1
        self._record(slot, st.epoch, 0, int(tok0), self._now())

    def _admit_arrived(self, queue: List[Tuple[float, Request]],
                       now: float) -> None:
        """Admit as many arrived requests as the whole fleet agrees on."""
        arrived = [r for (t, r) in queue if t <= now]
        if not arrived:
            return
        with jax.profiler.TraceAnnotation("serve.admit", tick=self._tick):
            n = self._admittable(arrived)
            n = agree_admission_count(self.comm, n)
            for req in arrived[:n]:
                idx = next(i for i, (_, r) in enumerate(queue) if r is req)
                arr, _ = queue.pop(idx)
                self._admit_one(req, arr)

    # ----------------------------------------------------------------- ticks
    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def _pre_dispatch(self, plan: TickPlan) -> None:
        if self._reset_mask.any():
            self.cache = self._reset_fn(self.cache,
                                        _upload(self._reset_mask),
                                        self.btab)
            self._reset_mask[:] = False
            self.stats.resets += 1
        if self.pool is not None:
            for i in range(self.slots):
                if plan.lengths[i] > 0:
                    self.pool.ensure(i, int(plan.starts[i])
                                     + int(plan.lengths[i]))
            if self.pool.dirty:
                self.btab = _upload(self.pool.table)
                self.pool.dirty = False
                self.stats.btab_uploads += 1

    def _count(self, plan: TickPlan) -> None:
        """Add ``plan`` to the counters."""
        st = self.stats
        st.ticks[plan.kind] = st.ticks.get(plan.kind, 0) + 1
        decode = int(plan.use_next.sum())      # a decode row feeds 1 token
        st.decode_tokens += decode
        if plan.kind == "chunk":
            st.chunk_tokens += int(plan.lengths.sum()) - decode
            st.chunk_rows += plan.tokens.size

    def _dispatch(self, plan: TickPlan):
        plan.tick = tick = self._tick
        self._tick += 1
        with jax.profiler.TraceAnnotation("serve.prepare", tick=tick):
            self._pre_dispatch(plan)
        self._count(plan)
        with jax.profiler.TraceAnnotation("serve.dispatch", tick=tick,
                                          kind=plan.kind):
            self.key, sub = jax.random.split(self.key)
            nxt, self.next_buf, self.cache = self._dispatch_fn(
                self.params, jnp.asarray(plan.tokens),
                jnp.asarray(plan.use_next), jnp.asarray(plan.starts),
                jnp.asarray(plan.lengths), _upload(self.temps), sub,
                self.next_buf, self.cache, self.btab)
        return nxt

    def _finish(self, plan: TickPlan, nxt) -> Dict[int, int]:
        """Host bookkeeping for a completed tick (blocks on the device)."""
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.readback", tick=plan.tick):
            toks = np.asarray(nxt)
        self.stats.readback_s += time.perf_counter() - t
        with jax.profiler.TraceAnnotation("serve.record", tick=plan.tick):
            now = self._now()
            out: Dict[int, int] = {}
            for slot, epoch, gidx in plan.samples:
                st = self.sched.states[slot]
                if st is None or st.epoch != epoch:
                    continue          # slot released mid-flight (EOS)
                tok = int(toks[slot])
                out[st.rid] = tok
                self._record(slot, epoch, gidx, tok, now)
        return out

    def _record(self, slot: int, epoch: int, gidx: int, tok: int,
                now: float) -> None:
        st = self.sched.states[slot]
        req = self.requests[st.rid]
        req.out_tokens.append(tok)
        st.recorded = gidx + 1
        if gidx == 0:
            m = self._metrics[st.rid]
            m["ttft_s"] = now - m["arrival_s"]
        hit_eos = st.eos_id is not None and tok == st.eos_id
        if hit_eos:
            st.done = True
        if hit_eos or st.recorded >= st.cap:
            self._release(slot)
            self._finalize(st.rid, now)

    def _release(self, slot: int) -> None:
        if self.pool is not None:
            self.pool.release(slot)
        self._reset_mask[slot] = True
        self.temps[slot] = 0.0
        self.sched.release(slot)

    def _finalize(self, rid: int, now: float) -> None:
        req = self.requests.pop(rid)
        self._done[rid] = req.out_tokens
        m = self._metrics[rid]
        m["done_s"] = now
        m["tokens"] = len(req.out_tokens)

    def step(self) -> Dict[int, int]:
        """Plan + dispatch + finish one tick synchronously; returns
        ``{rid: sampled token}`` for the rows that sampled this tick."""
        if self.params is None:
            raise RuntimeError(_LOAD_MSG)
        plan = self.sched.plan()
        if plan is None:
            return {}
        return self._finish(plan, self._dispatch(plan))

    # ------------------------------------------------------------ run loops
    def run_to_completion(self, reqs: List[Request],
                          max_steps: int = 10_000) -> ServeResult:
        """Serve ``reqs`` (all available immediately) to completion."""
        return self.run_trace(reqs, [0.0] * len(reqs), max_steps=max_steps)

    def run_trace(self, reqs: List[Request], arrivals_s: List[float],
                  max_steps: int = 10_000) -> ServeResult:
        """Serve a timed trace: request i becomes admittable once
        ``arrivals_s[i]`` seconds have elapsed.  Overlapped loop: tick
        t+1 is dispatched before tick t's tokens are read back."""
        if self.params is None:
            raise RuntimeError(_LOAD_MSG)
        if len(reqs) != len(arrivals_s):
            raise ValueError("one arrival time per request")
        if self.pool is not None:
            for r in reqs:   # reject never-admittable requests up front
                worst = self.pool.blocks_for(len(r.prompt)
                                             + self._cap_for(r))
                worst = min(worst, self.pool.max_blocks_per_slot)
                if worst > self.pool.num_blocks:
                    raise ValueError(
                        f"request {r.rid} needs {worst} blocks but the "
                        f"pool holds {self.pool.num_blocks}")
        self._t0 = time.perf_counter()
        self._done = {}
        self._metrics = {rid: m for rid, m in self._metrics.items()
                         if rid in self.requests}      # still in flight
        for m in self._metrics.values():   # took its slot before this run
            m["admit_s"] = 0.0
        self.stats = stats = EngineStats()
        queue = sorted(zip(arrivals_s, reqs), key=lambda p: p[0])
        inflight = None
        steps = 0
        try:
            while steps < max_steps:
                stats.loop_s = self._now()
                self._admit_arrived(queue, stats.loop_s)
                with jax.profiler.TraceAnnotation("serve.plan",
                                                  tick=self._tick):
                    plan = self.sched.plan()
                if plan is None:
                    if inflight is not None:
                        self._finish(*inflight)     # may free slots
                        inflight = None
                        continue
                    if queue:
                        wait = queue[0][0] - self._now()
                        if wait > 0:
                            t = time.perf_counter()
                            with jax.profiler.TraceAnnotation("serve.wait"):
                                time.sleep(min(wait, 1e-3))
                            stats.wait_s += time.perf_counter() - t
                        continue
                    break
                nxt = self._dispatch(plan)
                steps += 1
                if inflight is not None:
                    self._finish(*inflight)
                inflight = (plan, nxt)
                if not self.overlap:
                    self._finish(*inflight)
                    inflight = None
            if inflight is not None:
                self._finish(*inflight)
        finally:
            stats.loop_s = self._now()
        self.comm.sync()       # drain: all ranks idle before returning
        unfinished = {st.rid: list(self.requests[st.rid].out_tokens)
                      for _, st in self.sched.active()}
        unfinished.update({r.rid: [] for _, r in queue})
        truncated = bool(unfinished) and steps >= max_steps
        return ServeResult(self._done, truncated, unfinished,
                           self._metrics)

    _t0 = 0.0
