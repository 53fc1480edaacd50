"""Batched serving driver: continuous-batching loop over prefill +
single-token decode with a pre-allocated, shardable KV cache.

Serving model (the decode_32k / long_500k cells' runtime twin):
  * requests enter an admission queue; a free batch slot is assigned;
  * prefill ingests the prompt and splices the slot's cache region;
  * every engine tick decodes ONE token for ALL slots at their OWN
    per-slot positions (the jit'd cell from serve_step.make_engine_tick)
    — slots admitted at different ticks attend, rotate and write their
    KV rows at different absolute positions;
  * per-slot active/EOS/length lifecycle masking happens in-graph; the
    host reads back only small (B,) vectors per tick, never the logits;
  * finished slots are recycled for queued requests.

A staggered batch therefore produces token-for-token the same outputs
as serving each request alone (tests/test_serve_consistency.py).

On real hardware the tick is jit'd once against the full-capacity cache
and slots are swapped in place; this CPU-scale driver runs the same
code paths with smoke configs (examples/serve_batched.py).
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, get_smoke
from repro.configs.base import execution_policy_for
from repro.core import ops
from repro.core.ops import paged as paged_kv
from repro.core.precision import PrecisionPolicy
from repro.models import api
from repro.runtime import serve_step
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.monitor import span

__all__ = ["ServeEngine", "Request", "QueueFull", "RecoveryMismatch",
           "main"]


class _PageAllocator:
    """Host-side free list over ONE paged-pool capacity class.

    Physical page 0 is the reserved trash page (freed table entries
    point there) and is never handed out; allocation starts at page 1.
    ``alloc`` is all-or-nothing — a partially satisfiable request
    returns None so admission can keep the request queued instead of
    holding pages it cannot use (backpressure, not deadlock: frees are
    whole-request too, so a blocked head request always fits once
    enough slots recycle)."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))

    @property
    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        if n > len(self._free):
            return None
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: list[int]) -> None:
        self._free.extend(pages)


class QueueFull(RuntimeError):
    """Admission queue at capacity: the engine refuses the request
    instead of buffering unbounded work.  The gateway maps this to
    backpressure (HTTP 429 + Retry-After); batch drivers either retry
    or count the rejection."""

    def __init__(self, rid: int, depth: int, max_queue: int):
        super().__init__(
            f"request {rid}: admission queue full "
            f"({depth}/{max_queue} queued)")
        self.rid = rid
        self.depth = depth
        self.max_queue = max_queue


class RecoveryMismatch(RuntimeError):
    """Token-exact recovery failed: re-prefilling ``prompt +
    out_tokens[:-1]`` on the new replica predicted a different token
    than the one the dead replica had already emitted.  Under greedy
    decode and a deterministic policy this must never happen — it means
    the two replicas disagree numerically (e.g. a policy mismatch), so
    recovery refuses to silently fork the stream."""

    def __init__(self, rid: int, index: int, expected: int, got: int):
        super().__init__(
            f"request {rid}: recovery re-prefill predicted token {got} "
            f"at output index {index} but the original stream emitted "
            f"{expected} — replicas are not bit-identical under this "
            f"policy")
        self.rid = rid
        self.index = index
        self.expected = expected
        self.got = got


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    session: str | None = None   # pool-level affinity key (multi-turn)
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    # fault-tolerance surface: a deadline in ENGINE ticks (virtual
    # time, so it is deterministic and survives rehoming — ticks_used
    # rides on the request, not on any one engine's counter), and
    # terminal disposition flags.  ``recoveries`` counts how many times
    # the request was rehomed after a replica death.
    deadline_ticks: int | None = None
    ticks_used: int = 0
    cancelled: bool = False
    expired: bool = False
    recoveries: int = 0
    # latency accounting — MONOTONIC clock, seconds (a wall-clock step
    # under NTP would corrupt latency_s/queue_s); wall_time is the one
    # wall timestamp, kept for log attribution only.
    t_submit: float | None = None
    t_admit: float | None = None
    t_first: float | None = None   # first token emitted (TTFT end)
    t_last_token: float | None = None   # latest token emitted
    t_done: float | None = None
    wall_time: float | None = None

    @property
    def latency_s(self) -> float | None:
        """Submit-to-completion latency (None until done)."""
        if self.t_submit is None or self.t_done is None:
            return None
        return self.t_done - self.t_submit

    @property
    def queue_s(self) -> float | None:
        """Time spent waiting for a free slot (None until admitted)."""
        if self.t_submit is None or self.t_admit is None:
            return None
        return self.t_admit - self.t_submit

    @property
    def ttft_s(self) -> float | None:
        """Submit-to-first-token latency (None until the prefill's
        sampled token lands)."""
        if self.t_submit is None or self.t_first is None:
            return None
        return self.t_first - self.t_submit


class ServeEngine:
    """Slot-based continuous-batching engine with per-slot positions.

    Slot state lives on device as (B,) vectors — last token, position,
    active mask, remaining-token budget — and the decode tick advances
    all of it inside one jit'd call. The host only touches per-slot
    state at admission (prefill + cache splice) and when draining the
    small per-tick token/finished vectors into Request objects.

    ``policy`` may be a plain ``PrecisionPolicy`` (XLA matmuls) or a
    ``core.ops.ExecutionPolicy`` (or legacy ``MatmulPolicy``) whose
    ``backends`` mapping routes every model matmul to a registered
    op-registry impl (pallas / pallas_fused / pallas_grouped / ...).
    """

    def __init__(self, cfg, *, batch_size: int, max_ctx: int,
                 policy: PrecisionPolicy | None = None, eos_id: int = 1,
                 max_queue: int | None = None, metrics=None,
                 replica: str = "0", kv_layout: str = "dense",
                 kv_page_size: int = 8, kv_quant: str | None = None,
                 kv_pages: int | None = None):
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}; "
                             f"one of ('dense', 'paged')")
        if kv_quant is not None and kv_layout != "paged":
            raise ValueError("kv_quant requires kv_layout='paged'")
        self.cfg = cfg
        self.batch = batch_size
        self.max_ctx = max_ctx
        self.policy = policy or PrecisionPolicy.uniform("bf16")
        self.eos_id = eos_id
        # paged-KV mode: attention caches become shared page pools; the
        # engine owns the per-class host-side free lists (set by load())
        # and the per-slot page allocations.
        self.kv_layout = kv_layout
        self.kv_page_size = kv_page_size
        self.kv_quant = kv_quant
        self.kv_pages = kv_pages
        self._allocators: dict[int, _PageAllocator] = {}
        self._slot_pages: list[dict[int, list[int]] | None] = \
            [None] * batch_size
        # None = unbounded (legacy batch drivers); serving fronts set a
        # watermark so a stalled engine rejects instead of OOMing.
        self.max_queue = max_queue
        # duck-typed MetricsRegistry (counter/gauge/histogram methods);
        # None keeps the hot path metrics-free.
        self.metrics = metrics
        self.replica = replica
        self.params = None
        # the tick and the splice rewrite the slot state: both donate
        # the cache and the four slot vectors, so XLA updates them in
        # place (never params, which every program reads)
        self._tick = jax.jit(serve_step.make_engine_tick(
            cfg, self.policy, eos_id=eos_id, max_ctx=max_ctx),
            donate_argnums=(1, 2, 3, 4, 5))
        self._prefill = jax.jit(
            serve_step.make_prefill(cfg, self.policy, s_ctx=max_ctx))
        self._splice = jax.jit(serve_step.make_slot_splice(),
                               donate_argnums=(0, 3, 4, 5, 6))
        # slot state (device-resident between ticks)
        self.cache = None
        self.slot_req: list[Request | None] = [None] * batch_size
        self.last_tok = jnp.zeros(batch_size, jnp.int32)
        self.pos = jnp.zeros(batch_size, jnp.int32)
        self.active = jnp.zeros(batch_size, bool)
        self.remaining = jnp.zeros(batch_size, jnp.int32)
        # admission queue + engine counters
        self.queue: collections.deque[Request] = collections.deque()
        self.ticks = 0
        self.tokens_generated = 0

    def load(self, params) -> None:
        """Hold ``params`` for serving and build the empty cache.

        Each float32 leaf that every read in the tick and the prefill
        rounds to bf16 first (under this engine's route) is rounded
        once here, so the programs read half the bytes and compute the
        same numbers.  The engine holds its own tree: bf16 copies of
        those leaves and the caller's arrays for the rest, which are
        left as they are.
        """
        # cache in the activation dtype: decode writes splice activation
        # rows in, and a dtype mismatch would silently round-trip keys
        # through a narrower type only on the batched path
        dtype = jnp.dtype(self.cfg.activation_dtype)
        if self.kv_layout == "paged":
            self.cache = serve_step.init_paged_cache(
                self.cfg, self.batch, self.max_ctx,
                page_size=self.kv_page_size, quant=self.kv_quant,
                num_pages=self.kv_pages, dtype=dtype)
            classes = serve_step.paged_classes(
                self.cfg, self.batch, self.max_ctx,
                page_size=self.kv_page_size, num_pages=self.kv_pages)
            self._allocators = {cap: _PageAllocator(n)
                                for cap, n in classes.items()}
        else:
            self.cache = api.init_cache(
                self.cfg, self.batch, self.max_ctx, dtype)
        with span("engine.load") as sp:
            n_img = (self.cfg.num_image_tokens
                     if self.cfg.family == "vlm" else 0)
            prompt = np.zeros(max(1, min(16, self.max_ctx - 1 - n_img)),
                              np.int32)
            which = serve_step.bf16_read_leaves(params, [
                (self._tick, (self.cache, self.last_tok, self.pos,
                              self.active, self.remaining)),
                (self._prefill, (self._prefill_batch(prompt),))])
            self.params = serve_step.round_leaves(params, which)
            held = jax.tree.leaves(self.params)
            rounded = [x for x, w in zip(held, jax.tree.leaves(which))
                       if w]
            sp.attrs.update(
                rounded_leaves=len(rounded),
                rounded_bytes=sum(x.nbytes for x in rounded),
                kept_f32_bytes=sum(x.nbytes for x in held
                                   if x.dtype == jnp.float32))

    def _prefill_batch(self, toks: np.ndarray) -> dict:
        """The prefill program's batch for one prompt ``toks`` (S,)."""
        batch = {"tokens": jnp.asarray(toks)[None]}          # (1, S)
        if self.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (1, self.cfg.encoder_seq, self.cfg.d_model), jnp.float32)
        if self.cfg.family == "vlm":
            batch["image_embeds"] = jnp.zeros(
                (1, self.cfg.num_image_tokens, self.cfg.d_model),
                jnp.float32)
        return batch

    # ------------------------------------------------------------ slots

    def _free_slot(self) -> int | None:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    def _validate(self, req: Request) -> None:
        n_img = (self.cfg.num_image_tokens
                 if self.cfg.family == "vlm" else 0)
        # a recovered request re-prefills prompt + out_tokens[:-1], so
        # THAT is the length that must fit the prefill context
        plen = len(req.prompt) + max(0, len(req.out_tokens) - 1)
        if n_img + plen >= self.max_ctx:
            raise ValueError(
                f"request {req.rid}: prompt length {plen}"
                f"{f' (+{n_img} image tokens)' if n_img else ''} does not "
                f"fit the engine context (max_ctx={self.max_ctx})")

    # -------------------------------------------------------- paged KV

    def _pages_needed(self, req: Request, cap: int) -> int:
        """Worst-case page demand of one request in a capacity class.

        Linear layers touch rows [0, prompt+budget); ring layers wrap
        into at most ``cap`` slots — ``min(cap, total)`` covers both."""
        n_img = (self.cfg.num_image_tokens
                 if self.cfg.family == "vlm" else 0)
        total = n_img + len(req.prompt) + req.max_new_tokens
        return paged_kv.num_logical_pages(min(cap, total),
                                          self.kv_page_size)

    def _alloc_pages(self, req: Request) -> dict[int, list[int]] | None:
        """All-or-nothing allocation across every capacity class."""
        got: dict[int, list[int]] = {}
        for cap, alloc in self._allocators.items():
            pages = alloc.alloc(self._pages_needed(req, cap))
            if pages is None:
                for c, p in got.items():
                    self._allocators[c].free(p)
                return None
            got[cap] = pages
        return got

    def _free_pages(self, alloc_map: dict[int, list[int]], *,
                    slot: int | None = None) -> None:
        """Return a request's pages to the free lists; when the slot's
        tables were written (it decoded), zero them too, so the freed
        pages can never be corrupted by the stale slot's continuing
        in-graph writes (inactive rows then write the trash page)."""
        for cap, pages in alloc_map.items():
            self._allocators[cap].free(pages)
        if slot is not None:
            for seg_key, pos_key, _, _ in serve_step.attn_cache_walk(
                    self.cfg, self.max_ctx):
                leaf = self.cache[seg_key][pos_key]
                self.cache[seg_key][pos_key] = dataclasses.replace(
                    leaf, page_table=leaf.page_table.at[:, slot].set(0))

    def _splice_paged(self, cache1, slot: int,
                      alloc_map: dict[int, list[int]]) -> None:
        """Write the slot's page-table rows and scatter its padded dense
        prefill KV into the allocated pages (quantizing when the pool is
        quantized).  Every layer of a capacity class shares the same
        page ids — each layer has its OWN pool array, so equal ids never
        collide across layers."""
        ps = self.kv_page_size
        for seg_key, pos_key, _, cap in serve_step.attn_cache_walk(
                self.cfg, self.max_ctx):
            leaf = self.cache[seg_key][pos_key]
            dense = cache1[seg_key][pos_key]   # AttnCache (count,1,cap,..)
            n_log = leaf.page_table.shape[-1]
            row = np.zeros(n_log, np.int32)
            pages = alloc_map[cap]
            row[:len(pages)] = pages           # tail stays on trash (0)
            row_arr = jnp.asarray(row)

            def to_pages(x):
                # (count, 1, cap, Kv, hd) -> (count, n_log, ps, Kv, hd)
                x = x[:, 0].astype(jnp.float32)
                pad = [(0, 0)] * x.ndim
                pad[1] = (0, n_log * ps - x.shape[1])
                x = jnp.pad(x, pad)
                return x.reshape(x.shape[0], n_log, ps, *x.shape[2:])

            kp, vp = to_pages(dense.k), to_pages(dense.v)
            if leaf.quantized:
                qk, sk = paged_kv.quantize_rows(kp)
                qv, sv = paged_kv.quantize_rows(vp)
                leaf = dataclasses.replace(
                    leaf,
                    k_pages=leaf.k_pages.at[:, row_arr].set(qk),
                    v_pages=leaf.v_pages.at[:, row_arr].set(qv),
                    k_scale=leaf.k_scale.at[:, row_arr].set(sk),
                    v_scale=leaf.v_scale.at[:, row_arr].set(sv),
                    page_table=leaf.page_table.at[:, slot].set(row_arr))
            else:
                leaf = dataclasses.replace(
                    leaf,
                    k_pages=leaf.k_pages.at[:, row_arr].set(
                        kp.astype(leaf.k_pages.dtype)),
                    v_pages=leaf.v_pages.at[:, row_arr].set(
                        vp.astype(leaf.v_pages.dtype)),
                    page_table=leaf.page_table.at[:, slot].set(row_arr))
            self.cache[seg_key][pos_key] = leaf

    # -------------------------------------------------------- metrics
    # All no-ops when self.metrics is None: the registry is duck-typed
    # so launch/ never imports the serve package (pool/gateway import
    # THIS module).

    def _m_queue_depth(self) -> None:
        if self.metrics is not None:
            self.metrics.gauge(
                "serve_queue_depth",
                "requests awaiting a free slot").set(
                    len(self.queue), replica=self.replica)

    def _m_occupancy(self) -> None:
        if self.metrics is not None:
            occupied = sum(r is not None for r in self.slot_req)
            self.metrics.gauge(
                "serve_slot_occupancy",
                "fraction of decode slots holding a request").set(
                    occupied / self.batch, replica=self.replica)

    def submit(self, req: Request) -> None:
        """Queue a request for admission at the next free slot.

        Raises ValueError up front for prompts that cannot fit the
        engine context (so an oversized request never poisons the
        queue) and QueueFull when the admission queue is at its
        ``max_queue`` watermark — bounded admission is what lets the
        gateway translate overload into backpressure instead of
        unbounded memory growth.
        """
        self._validate(req)
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            if self.metrics is not None:
                self.metrics.counter(
                    "serve_requests_rejected",
                    "submissions refused at the queue watermark").inc(
                        replica=self.replica)
            raise QueueFull(req.rid, len(self.queue), self.max_queue)
        if req.t_submit is None:
            req.t_submit = time.monotonic()
            req.wall_time = time.time()
        self.queue.append(req)
        if self.metrics is not None:
            self.metrics.counter(
                "serve_requests_submitted",
                "requests accepted into the admission queue").inc(
                    replica=self.replica)
            self._m_queue_depth()

    def admit(self, req: Request) -> bool:
        """Prefill `req` into a free slot. Returns False if none free.

        Single-request prefill: runs the prompt through the prefill path
        and splices the resulting caches into the batch cache at the
        slot index with one compiled program, which also sets the
        slot's four state vectors and donates the cache and those
        vectors, so the slot is written in place. The prompt's first
        sampled token counts against max_new_tokens and may itself be
        EOS — then the request completes without ever occupying a
        decode slot.

        A request arriving with ``out_tokens`` already populated is a
        RECOVERY re-admission (its previous replica died mid-decode):
        the engine re-prefills ``prompt + out_tokens[:-1]`` and checks
        that the prefill's greedy next token equals the last token the
        dead replica emitted — under greedy decode this pins the resumed
        stream bit-identical to an undisturbed run (the same invariant
        that makes staggered admission token-exact).  A disagreement
        raises ``RecoveryMismatch`` rather than silently forking the
        stream.  No token is appended and nothing is re-counted: the
        recovered tokens were already generated once.
        """
        slot = self._free_slot()
        if slot is None:
            return False
        with span("engine.admit", rid=req.rid, prompt_len=len(req.prompt)):
            return self._admit_to(req, slot)

    def _admit_to(self, req: Request, slot: int) -> bool:
        self._validate(req)
        if req.t_submit is None:
            req.t_submit = time.monotonic()
            req.wall_time = time.time()
        alloc_map = None
        if self.kv_layout == "paged":
            # Reserve pages BEFORE the prefill: worst-case demand is a
            # pure function of prompt length + token budget, so a
            # pool-pressure refusal costs nothing — the request stays
            # queued with no speculative first token to roll back.
            # (Recovery demand is identical: prompt + budget is
            # unchanged by rehoming.)
            alloc_map = self._alloc_pages(req)
            if alloc_map is None:
                return False
        n_img = (self.cfg.num_image_tokens
                 if self.cfg.family == "vlm" else 0)
        resume = len(req.out_tokens) > 0
        toks = (np.concatenate([np.asarray(req.prompt, np.int32),
                                np.asarray(req.out_tokens[:-1], np.int32)])
                if resume else np.asarray(req.prompt, np.int32))
        batch = self._prefill_batch(toks)                  # (1, S[+k-1])
        with span("engine.prefill"):
            logits, cache1 = self._prefill(self.params, batch)
        with span("engine.sync", what="first_token"):
            first = int(jnp.argmax(logits[0, -1]))
        if resume:
            if first != req.out_tokens[-1]:
                if alloc_map is not None:
                    self._free_pages(alloc_map)
                raise RecoveryMismatch(
                    req.rid, len(req.out_tokens) - 1,
                    req.out_tokens[-1], first)
        else:
            req.t_admit = time.monotonic()
            req.out_tokens.append(first)
            req.t_first = req.t_last_token = time.monotonic()
            self.tokens_generated += 1
            if self.metrics is not None:
                self.metrics.histogram(
                    "serve_queue_wait_seconds",
                    "submit-to-admission wait").observe(
                        req.queue_s, replica=self.replica)
                self.metrics.histogram(
                    "serve_ttft_seconds",
                    "submit-to-first-token latency").observe(
                        req.ttft_s, replica=self.replica)
                # the prefill-sampled first token is generated HERE,
                # before the slot ever ticks — count it where it happens
                self.metrics.counter(
                    "serve_tokens", "decoded tokens").inc(
                        1, replica=self.replica)
        if (req.out_tokens[-1] == self.eos_id
                or len(req.out_tokens) >= req.max_new_tokens):
            # EOS (or an exhausted budget) straight out of prefill: the
            # request is done; the slot stays free for the next one
            # (its reserved pages go straight back — tables were never
            # written, so no zeroing is needed).
            req.done = True
            req.t_done = time.monotonic()
            if alloc_map is not None:
                self._free_pages(alloc_map)
            return True

        # The slot will actually decode: commit its prefill state into
        # the batch state (splice runs after the early-done check, so
        # requests that finish in prefill never touch the cache).
        # invariant (fresh k=1 and resumed k>1 alike): after k emitted
        # tokens the cache holds prompt + out[:k-1], the next input is
        # out[k-1] at position n_img + S + k - 1, and k counted against
        # the budget — so a resumed slot ticks exactly like the dead one
        # would have.
        with span("engine.splice") as sp:
            # device int32 scalars: one splice program serves every slot
            scalars = jax.device_put(tuple(np.int32(v) for v in (
                slot, req.out_tokens[-1], n_img + len(toks),
                req.max_new_tokens - len(req.out_tokens))))
            # paged leaves take the page scatter below; every other
            # leaf (dense and cross-attn KV, recurrent state) goes
            # through the splice program
            dense = {sk: {pk: full for pk, full in seg.items()
                          if not isinstance(full, paged_kv.PagedKVCache)}
                     for sk, seg in self.cache.items()}
            dense1 = {sk: {pk: cache1[sk][pk] for pk in seg}
                      for sk, seg in dense.items()}
            # whether the first buffer passed in is deleted after the
            # call says whether the donation took or fell back to a copy
            donor = jax.tree.leaves((dense, self.last_tok))[0]
            (dense, self.last_tok, self.pos, self.active,
             self.remaining) = self._splice(
                dense, dense1, scalars[0], self.last_tok, self.pos,
                self.active, self.remaining, *scalars[1:])
            sp.attrs["donated"] = donor.is_deleted()
            for sk, seg in dense.items():
                self.cache[sk].update(seg)
            if self.kv_layout == "paged":
                self._splice_paged(cache1, slot, alloc_map)
                self._slot_pages[slot] = alloc_map
            self.slot_req[slot] = req
        return True

    # ------------------------------------------------------------- tick

    def tick(self) -> int:
        """One engine step: decode one token for every active slot.

        Every slot decodes at its OWN position (pos is a (B,) vector);
        lifecycle masking (inactive freeze, EOS, token budget, context
        bound) happens inside the jit'd tick. Returns the number of
        tokens decoded this tick (= active slots at entry).
        """
        with span("engine.tick") as sp:
            with span("engine.sync", what="active"):
                # a host copy, never a view of the buffer the launch
                # donates (np.asarray shares it on a CPU backend)
                active_before = np.array(self.active)
            n_active = int(active_before.sum())
            sp.attrs["active"] = n_active
            if n_active == 0:
                self._m_occupancy()
                return 0
            t0 = time.monotonic()
            with span("engine.launch") as launch:
                # active_before is on the host already: nothing reads
                # the donated buffers after the launch
                donor = jax.tree.leaves(self.cache)[0]
                (self.cache, self.last_tok, self.pos, self.remaining,
                 self.active, finished) = self._tick(
                    self.params, self.cache, self.last_tok, self.pos,
                    self.active, self.remaining)
                launch.attrs["donated"] = donor.is_deleted()
            with span("engine.sync", what="tokens"):
                nxt = np.asarray(self.last_tok)
            with span("engine.sync", what="finished"):
                fin = np.asarray(finished)
            now = time.monotonic()
            with span("engine.drain"):
                gaps = []   # each request's time since its own last token
                for i in np.flatnonzero(active_before):
                    r = self.slot_req[i]
                    r.out_tokens.append(int(nxt[i]))
                    if r.t_last_token is not None:
                        gaps.append(now - r.t_last_token)
                    r.t_last_token = now
                    if fin[i]:
                        r.done = True
                        r.t_done = now
                        self.slot_req[i] = None
                        if (self.kv_layout == "paged"
                                and self._slot_pages[i]):
                            self._free_pages(self._slot_pages[i],
                                             slot=int(i))
                            self._slot_pages[i] = None
        self.ticks += 1
        self.tokens_generated += n_active
        if self.metrics is not None:
            dt = now - t0
            self.metrics.histogram(
                "serve_tick_seconds",
                "one engine decode tick (all active slots)").observe(
                    dt, replica=self.replica)
            itl = self.metrics.histogram(
                "serve_inter_token_seconds",
                "per-request gap since that request's previous token")
            for gap in gaps:
                itl.observe(gap, replica=self.replica)
            self.metrics.counter(
                "serve_tokens", "decoded tokens").inc(
                    n_active, replica=self.replica)
            self.metrics.gauge(
                "serve_tokens_per_s",
                "decode throughput over the last tick").set(
                    n_active / max(dt, 1e-9), replica=self.replica)
            self._m_occupancy()
        return n_active

    def step(self) -> int:
        """Expire overdue work, admit as many queued requests as slots
        allow, tick, then age every request still in flight (deadlines
        count engine steps of ownership, so they are deterministic in
        virtual time and survive rehoming to another replica)."""
        with span("engine.step"):
            self._expire_due()
            while self.queue and self.admit(self.queue[0]):
                self.queue.popleft()
            self._m_queue_depth()
            n = self.tick()
            for r in self.queue:
                r.ticks_used += 1
            for r in self.slot_req:
                if r is not None:
                    r.ticks_used += 1
        return n

    @property
    def idle(self) -> bool:
        return not self.queue and all(r is None for r in self.slot_req)

    # ------------------------------------------------- fault tolerance

    def _release_slot(self, slot: int) -> None:
        """Host-side slot teardown outside the normal finish path
        (cancellation, expiry, evacuation): unmask the slot from the
        jit'd tick and reclaim its pages.  The cache rows themselves
        need no scrubbing — an inactive slot is frozen in-graph and its
        region is overwritten by the next admission's splice."""
        self.slot_req[slot] = None
        self.active = self.active.at[slot].set(False)
        self.remaining = self.remaining.at[slot].set(0)
        if self.kv_layout == "paged" and self._slot_pages[slot]:
            self._free_pages(self._slot_pages[slot], slot=slot)
            self._slot_pages[slot] = None

    def _finish(self, req: Request, *, cancelled: bool = False,
                expired: bool = False) -> None:
        req.done = True
        req.cancelled = cancelled
        req.expired = expired
        req.t_done = time.monotonic()

    def _expire_due(self) -> list[Request]:
        """Terminate every request whose tick deadline has passed —
        queued or mid-decode — freeing its slot and pages."""
        expired: list[Request] = []
        for r in [r for r in self.queue
                  if r.deadline_ticks is not None
                  and r.ticks_used >= r.deadline_ticks]:
            self.queue.remove(r)
            self._finish(r, expired=True)
            expired.append(r)
        for i, r in enumerate(self.slot_req):
            if (r is not None and r.deadline_ticks is not None
                    and r.ticks_used >= r.deadline_ticks):
                self._finish(r, expired=True)
                self._release_slot(i)
                expired.append(r)
        if expired and self.metrics is not None:
            self.metrics.counter(
                "serve_requests_expired",
                "requests terminated at their tick deadline").inc(
                    len(expired), replica=self.replica)
        return expired

    def cancel(self, rid: int) -> bool:
        """Abort a request by id (client disconnect): drop it from the
        queue or free its decode slot + KV pages.  Returns False when
        the request is unknown or already done."""
        for i, r in enumerate(self.slot_req):
            if r is not None and r.rid == rid:
                self._finish(r, cancelled=True)
                self._release_slot(i)
                break
        else:
            for r in self.queue:
                if r.rid == rid:
                    self.queue.remove(r)
                    self._finish(r, cancelled=True)
                    break
            else:
                return False
        if self.metrics is not None:
            self.metrics.counter(
                "serve_requests_cancelled",
                "requests aborted before completion "
                "(client disconnect)").inc(replica=self.replica)
        return True

    def evacuate(self) -> list[Request]:
        """Strip every unfinished request off this engine, freeing all
        slots and pages, and return them (decoding slots in slot order
        with their partial ``out_tokens``, then the queue in FIFO
        order) so the pool can rehome them.  Purely host-side
        bookkeeping — safe to run on a crashed replica whose device
        state is unreachable."""
        orphans: list[Request] = []
        for i, r in enumerate(self.slot_req):
            if r is not None:
                self._release_slot(i)
                if not r.done:
                    orphans.append(r)
        while self.queue:
            r = self.queue.popleft()
            if not r.done:
                orphans.append(r)
        return orphans

    def pages_outstanding(self) -> int:
        """KV pages currently held by slots (leak audit: must be 0 on
        an idle engine; dense engines report 0)."""
        return sum(a.num_pages - 1 - a.available
                   for a in self._allocators.values())

    def stats(self, requests: list[Request], wall_s: float) -> dict:
        lat = [r.latency_s for r in requests if r.latency_s is not None]
        qs = [r.queue_s for r in requests if r.queue_s is not None]
        return {
            "requests": len(requests),
            "ticks": self.ticks,
            "tokens": self.tokens_generated,
            "wall_s": wall_s,
            "tok_per_s": self.tokens_generated / max(wall_s, 1e-9),
            "latency_mean_s": float(np.mean(lat)) if lat else 0.0,
            "latency_max_s": float(np.max(lat)) if lat else 0.0,
            "queue_mean_s": float(np.mean(qs)) if qs else 0.0,
        }

    def run(self, requests: list[Request]) -> dict:
        """Serve all requests to completion; returns throughput stats.

        Token accounting happens inside tick()/admit() — counted at
        decode time, BEFORE finished slots are recycled, so the final
        token of every request (and the prefill-sampled first token) is
        included.
        """
        t0 = time.monotonic()
        ticks0, tokens0 = self.ticks, self.tokens_generated
        for req in requests:
            self.submit(req)
        guard = 0
        while not self.idle:
            self.step()
            guard += 1
            if guard > 10_000:
                raise RuntimeError("serve loop did not converge")
        stats = self.stats(requests, time.monotonic() - t0)
        # per-RUN deltas: the engine counters are lifetime-cumulative
        stats["ticks"] -= ticks0
        stats["tokens"] -= tokens0
        stats["tok_per_s"] = stats["tokens"] / max(stats["wall_s"], 1e-9)
        return stats


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="gemma3-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-ctx", type=int, default=64)
    ap.add_argument("--policy", default="bf16",
                    help="default precision policy for every matmul")
    ap.add_argument("--kv-layout", choices=("dense", "paged"),
                    default="dense",
                    help="attention KV cache layout: 'dense' per-slot "
                         "ring buffers, or 'paged' fixed-size pages "
                         "behind a per-slot page table (allocate on "
                         "admit, free on slot recycle)")
    ap.add_argument("--kv-page-size", type=int, default=8,
                    help="rows per KV page (paged layout only)")
    ap.add_argument("--kv-quant", choices=("none", "int8"),
                    default="none",
                    help="paged-page payload quantization: int8 pages "
                         "+ per-(row, kv-head) fp32 scales, dequantized "
                         "at read time")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="pages per pool class (default: full capacity "
                         "+ trash page — lossless; smaller pools trade "
                         "admission backpressure for memory)")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="per-request deadline in engine ticks: work "
                         "still queued or decoding after this many "
                         "ticks of ownership is expired in-engine "
                         "(slot + KV pages freed). Default: none")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind a least-loaded router "
                         "with session affinity (repro.serve.pool); 1 "
                         "= the single in-process engine")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="per-replica admission-queue watermark; past "
                         "it submissions raise QueueFull (the gateway "
                         "maps this to HTTP 429 + Retry-After). "
                         "Default: unbounded")
    ap.add_argument("--gateway-port", type=int, default=None,
                    help="serve an asyncio HTTP/JSON gateway (token "
                         "streaming, /metrics, backpressure) on this "
                         "port instead of running the synthetic batch")
    ap.add_argument("--backend", action="append", default=None,
                    metavar="[FAMILY=]IMPL",
                    help="op-registry routing, repeatable: "
                         "'family=impl' per kernel family "
                         f"(families: {', '.join(ops.families())}; "
                         "see `python -m benchmarks.run --list`). A "
                         "bare impl name means gemm=IMPL (deprecated). "
                         "Defaults: the arch's backends mapping")
    ap.add_argument("--attn-backend", default=None,
                    help="DEPRECATED: alias for --backend "
                         "attention=IMPL")
    ap.add_argument("--grouped-backend", default=None,
                    help="DEPRECATED: alias for --backend grouped=IMPL")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="device mesh: 'dp=2,tp=2,ep=2' (any subset), "
                         "'auto' (fit the visible device count), or "
                         "'none' (default, single-device). Composes "
                         "with --backend: every routed impl must "
                         "declare a Partitioning capability")
    ap.add_argument("--tile-cache", default=None, metavar="PATH",
                    help="JSON tile-autotune cache: loaded at startup "
                         "so restarts skip re-tuning hot shapes, and "
                         "the persistence target for new autotune "
                         "results (also via REPRO_TILE_CACHE)")
    args = ap.parse_args()

    if args.tile_cache:
        # The flag is both load source and persistence target — it must
        # override any inherited REPRO_TILE_CACHE, or autotune results
        # would save to a different file than the one just loaded.
        os.environ["REPRO_TILE_CACHE"] = args.tile_cache
    n = ops.load_tile_cache()         # flag or inherited REPRO_TILE_CACHE
    if n:
        print(f"tile cache: {n} shape(s) loaded from {ops.tile_cache_path()}")

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    backends = ops.parse_backend_flags(
        args.backend, attn_backend=args.attn_backend,
        grouped_backend=args.grouped_backend)
    from repro.runtime import mesh as meshlib
    from repro.runtime.monitor import run_header
    mesh_spec = meshlib.resolve_mesh_spec(args.mesh, cfg)
    # Route-build validation: the engine tick decodes against the KV
    # cache every step, so demand the attention impl's decode capability
    # up front instead of failing on the first tick (and paged_decode
    # too when the engine runs the paged layout).
    attn_caps = (("decode", "paged_decode")
                 if args.kv_layout == "paged" else ("decode",))
    policy = execution_policy_for(
        cfg, default=args.policy, backends=backends,
        require={"attention": attn_caps}, mesh=mesh_spec)
    kv_kwargs = dict(
        kv_layout=args.kv_layout, kv_page_size=args.kv_page_size,
        kv_quant=None if args.kv_quant == "none" else args.kv_quant,
        kv_pages=args.kv_pages)
    print(run_header(args.arch, policy=policy, mesh=policy.mesh), flush=True)
    params = api.init_params(jax.random.PRNGKey(0), cfg)

    if args.replicas > 1 or args.gateway_port is not None:
        # serve-stack path: replica pool (least-loaded routing, session
        # affinity), optionally fronted by the HTTP gateway. Imported
        # lazily — repro.serve imports THIS module.
        from repro.serve.metrics import MetricsRegistry
        from repro.serve.pool import ReplicaPool
        registry = MetricsRegistry()

        def factory(idx, pol):
            eng = ServeEngine(cfg, batch_size=args.batch,
                              max_ctx=args.max_ctx, policy=pol,
                              max_queue=args.max_queue, metrics=registry,
                              replica=str(idx), **kv_kwargs)
            eng.load(params)
            return eng

        pool = ReplicaPool(
            cfg, params, replicas=args.replicas,
            batch_size=args.batch, max_ctx=args.max_ctx,
            policy=policy, max_queue=args.max_queue, metrics=registry,
            engine_factory=(factory if args.kv_layout == "paged"
                            else None))
        if args.gateway_port is not None:
            import asyncio

            from repro.serve.gateway import Gateway
            gw = Gateway(pool, host="0.0.0.0", port=args.gateway_port,
                         metrics=registry)
            print(f"gateway: listening on :{args.gateway_port} "
                  f"({args.replicas} replica(s), "
                  f"max_queue={args.max_queue})", flush=True)
            asyncio.run(gw.serve_forever())
            return
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i,
                        prompt=rng.integers(
                            2, cfg.vocab_size,
                            args.prompt_len).astype(np.int32),
                        max_new_tokens=args.max_new,
                        deadline_ticks=args.deadline_ticks)
                for i in range(args.requests)]
        stats = pool.run(reqs)
        print(f"pool served {stats['requests']} requests across "
              f"{stats['replicas']} replicas ({stats['wall_s']:.2f}s, "
              f"{stats['tok_per_s']:.1f} tok/s)")
        for r in reqs[:3]:
            print(f"  req {r.rid}: {len(r.out_tokens)} tokens "
                  f"{r.out_tokens[:8]}...")
        return

    eng = ServeEngine(cfg, batch_size=args.batch, max_ctx=args.max_ctx,
                      policy=policy, max_queue=args.max_queue,
                      **kv_kwargs)
    eng.load(params)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(2, cfg.vocab_size,
                                        args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new,
                    deadline_ticks=args.deadline_ticks)
            for i in range(args.requests)]
    stats = eng.run(reqs)
    print(f"served {stats['requests']} requests in {stats['ticks']} ticks "
          f"({stats['wall_s']:.2f}s, {stats['tok_per_s']:.1f} tok/s, "
          f"mean latency {stats['latency_mean_s'] * 1e3:.0f}ms)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: {len(r.out_tokens)} tokens "
              f"{r.out_tokens[:8]}...")


if __name__ == "__main__":
    main()
