"""Continuous-batching serving engine for (quantized) LM models.

One engine step interleaves three phases over a slot-based KV-cache pool:

  1. **admit** — while a slot is free and the FIFO head has arrived, claim a
     slot (bookkeeping reset only; stale K/V is masked out exactly).
  2. **chunked prefill** — every admitted-but-unfinished request advances by
     one fixed-size prompt chunk, written into its slot of the pooled cache.
     The final chunk is zero-padded; pad writes are invalidated (kpos → -1)
     before the cache is committed, and the first generated token is read
     from the last *valid* position's logits.
  3. **batched decode** — ``decode_step`` over the full slot batch with
     per-slot positions/masks. Finished requests retire and their slots are
     immediately reusable; free slots ride along as masked garbage rows
     (classic padding), which keeps every decode the same compiled shape.

Two executions of that loop share the bookkeeping above:

  * the **fast path** (default) is device-resident: all currently-prefilling
    slots advance in ONE ``[P, C]`` dispatch (scattered into the pooled
    cache), decode runs K steps fused in a jitted ``lax.scan`` that returns
    a ``[B, K]`` token buffer (one dispatch, one host sync per horizon), the
    cache argument is donated in every jit so the KV pool updates in place,
    and slot-reset bookkeeping is folded into the first prefill chunk. The
    host picks K adaptively — ``min(decode_horizon, min remaining budget,
    ceil(next scheduled arrival - clock))``, K=1 while any prefill is in
    flight — so retirement, admission, and prefill cadence land on exactly
    the same clock ticks as the stepwise path.
  * the **stepwise reference** (``fast=False``) dispatches one batch-1
    prefill chunk per slot and one decode step per engine step, syncing
    after every step — the PR-2 behavior, kept as the parity oracle.

Because each slot's computation is row-independent (masked keys contribute
exact zeros), a request's tokens are bit-identical whether it is served solo
or inside a mixed batch, and whether decode steps run one-at-a-time or fused
— the batch-invariance and fused-vs-stepwise parity tests pin this down.

**Paged mode** (``page_size=...``): the pool stores KV state as fixed-size
pages + per-slot page tables (see cache_pool.py), and each of the four jits
becomes a thin wrapper around the SAME contiguous impl: gather the slot
rings out of the page pool into a dense ``[L, B, S, ...]`` view, run the
unchanged impl on the view, then scatter back ONLY the ring positions this
dispatch actually wrote (host-known write windows; out-of-range / unmapped
positions drop). Gathered garbage beyond a slot's mapped pages is finite
and masked by ``kpos = -1`` / scale 0 — exactly the recycled-slot
invariant — so paged serving is token-for-token identical to the
contiguous pool. Admission maps shared prefix pages from the scheduler's
``PrefixIndex`` (reuse length aligned DOWN to a prefill-chunk boundary,
which makes the donor's cached K/V bit-identical to recomputing them) and
costs one fused bookkeeping dispatch; prefill completion publishes the
request's fully-covered prompt pages for later requests to share.

**Fault tolerance**: requests carry optional ``deadline``/``priority``; the
engine reaps expired or client-cancelled requests at step/horizon
boundaries and reclaims their pages atomically. When paged admission runs
out of pages it climbs an exhaustion ladder — evict LRU prefix-index
entries, then preempt strictly-lower-priority in-flight requests (pages
released, prompt + generated-so-far parked host-side; the victim's
computed KV pages are published to the prefix index first, so a prompt
resume can remap them instead of recomputing) — before head-of-line
blocking. Every jitted path additionally returns a per-row "bad" flag
(non-finite logits); a poisoned row is quarantined at its next host sync
instead of poisoning the batch (row independence keeps every other slot
bit-identical). ``serving/chaos.py`` drives all of this deterministically.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import os
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..runtime.fault_tolerance import StragglerMonitor
from .cache_pool import KNOWN_BOOKKEEPING, CachePool
from .errors import QueueFull, RequestTooLarge
from .scheduler import FIFOScheduler, PrefixIndex, Request

def required_cache_len(prompt_len: int, max_new_tokens: int,
                       prefill_chunk: int) -> int:
    """Ring positions a request needs: the zero-padded prefill chunks (pad
    writes land before invalidation) and the full decoded context."""
    padded = -(-prompt_len // prefill_chunk) * prefill_chunk
    return max(padded, prompt_len + max_new_tokens - 1)


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, n).bit_length() - 1)


def _pow2_ceil(n: int) -> int:
    return 1 << (max(1, n) - 1).bit_length()


def _take_window(leaf, win):
    """Gather ring positions ``win`` [B, C] along the S axis of a payload
    leaf [L, B, S, ...] → [L, B, C, ...]."""
    idx = win.astype(jnp.int32).reshape(
        (1,) + win.shape + (1,) * (leaf.ndim - 3))
    return jnp.take_along_axis(leaf, idx, axis=2)


def _put_window(leaf, win, vals):
    """Scatter ``vals`` [L, B, C, ...] back into ring positions ``win``
    [B, C] along the S axis of a payload leaf [L, B, S, ...]."""
    b = jnp.arange(leaf.shape[1])[:, None]
    return leaf.at[:, b, win].set(vals.astype(leaf.dtype))


def _paged_view(cache: dict, page_size: int, max_len: int) -> dict:
    """Gather every slot's mapped pages into the dense contiguous layout
    ``[L, B, S, ...]`` the slot impls were written against. Unmapped table
    entries (-1) clamp to page 0: the gathered rows are garbage, but finite
    garbage at positions the bookkeeping marks dead (``kpos = -1`` / scale
    0) — the same invariant that makes recycled contiguous slots exact.
    ``kpos``/``pos`` are dense in both layouts and pass straight through."""
    pt = jnp.maximum(cache["page_table"], 0)             # [B, S/pg]
    dense = {"kpos": cache["kpos"], "pos": cache["pos"]}
    for name, leaf in cache.items():                     # leaf [L, NP, pg, ...]
        if name in KNOWN_BOOKKEEPING:
            continue
        g = jnp.take(leaf, pt, axis=1)                   # [L, B, S/pg, pg, ...]
        g = g.reshape(g.shape[:2] + (-1,) + leaf.shape[3:])
        dense[name] = jax.lax.slice_in_dim(g, 0, max_len, axis=2)
    return dense


def _paged_commit(cache: dict, dense: dict, rows, page_size: int) -> dict:
    """Scatter the ring positions a dispatch wrote (``rows`` [B, W], -1 for
    rows that wrote nothing) from the dense view back into the page pool.
    The write window is host bookkeeping the engine already tracks — pos
    before the call plus the chunk/horizon extent — so the scatter is a
    fixed [B, W] shape per compiled dispatch, not a data-dependent one.
    Positions mapping to no page (or rows = -1) route to one-past-the-end
    flat indices, which scatter-drop. Pages shared between slots are never
    in any write window (admission copies the one COW boundary page), so
    the non-dropped flat indices are unique and the scatter deterministic.
    ``kpos``/``pos`` come back dense from the impl; the page table is
    read-only inside every dispatch."""
    pg = page_size
    idx = jnp.maximum(rows, 0)                           # [B, W]
    page = jnp.take_along_axis(cache["page_table"], idx // pg, axis=1)
    out = {"kpos": dense["kpos"], "pos": dense["pos"],
           "page_table": cache["page_table"]}
    for name, leaf in cache.items():                     # leaf [L, NP, pg, ...]
        if name in KNOWN_BOOKKEEPING:
            continue
        flat_n = leaf.shape[1] * pg
        flat = jnp.where((rows >= 0) & (page >= 0),
                         page * pg + idx % pg, flat_n)   # [B, W]
        flatleaf = leaf.reshape((leaf.shape[0], flat_n) + leaf.shape[3:])
        tidx = idx.reshape((1,) + idx.shape + (1,) * (dense[name].ndim - 3))
        vals = jnp.take_along_axis(dense[name], tidx, axis=2)  # [L, B, W, ...]
        out[name] = flatleaf.at[:, flat].set(
            vals.astype(leaf.dtype), mode="drop"
        ).reshape(leaf.shape)
    return out


@dataclasses.dataclass
class _InFlight:
    req: Request
    slot: int
    admitted_at: float
    prefilled: int = 0
    generated: list = dataclasses.field(default_factory=list)
    cur_token: int = 0
    # fast path: slot bookkeeping reset deferred to the first prefill chunk
    fresh: bool = False
    # preemption bookkeeping: a resumed request runs as an internal Request
    # whose prompt is (original prompt + tokens generated before the
    # preemption); ``prior`` holds those already-generated tokens and
    # ``orig_req`` the original request, so retirement merges them back into
    # ONE result under the original rid/prompt_len
    prior: list = dataclasses.field(default_factory=list)
    orig_req: Optional[Request] = None

    @property
    def prefill_done(self) -> bool:
        return self.prefilled >= len(self.req.prompt)

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.req.max_new_tokens

    @property
    def remaining(self) -> int:
        return self.req.max_new_tokens - len(self.generated)


@dataclasses.dataclass
class _Parked:
    """A preempted request waiting host-side for re-admission: the ORIGINAL
    request plus everything generated before the preemption. Resumption
    re-enters the normal admission path as an internal request whose prompt
    is ``req.prompt + generated`` — the prefix index then remaps whatever
    published pages survived, and re-prefills the rest (bit-identical either
    way: prefill and decode agree on every cached position)."""

    req: Request
    generated: list
    admitted_at: float


@dataclasses.dataclass
class RequestResult:
    rid: int
    prompt_len: int
    tokens: list  # generated token ids
    arrival: float
    admitted_at: float
    finished_at: float
    # "ok" | "expired" | "cancelled" | "quarantined" — non-ok results carry
    # the tokens generated before the fault (possibly none)
    status: str = "ok"


class ServingEngine:
    """Serve requests against one model + params with continuous batching.

    num_slots: decode batch width (cache pool size).
    max_len: per-slot ring-buffer capacity; a request needs
        max(ceil(P/chunk)*chunk, P + G - 1) <= max_len.
    prefill_chunk: fixed prompt-chunk length (one chunk per prefilling
        request per engine step — bounds prefill's latency impact on
        in-flight decodes).
    decode_horizon: max decode steps fused into one device dispatch (fast
        path). Each distinct adaptive horizon K <= decode_horizon compiles
        its own scan, so keep it modest (compile count is bounded by it).
    fast: use the device-resident path (default). ``fast=False`` selects the
        stepwise reference implementation — same tokens bit-for-bit, one
        host sync per generated token; prefer it when debugging bookkeeping
        or when holding external references to ``pool.cache`` (the fast and
        slow paths both DONATE the cache buffer to the jitted step, so the
        pre-call cache object is invalidated after every dispatch).
    cache_dtype: fp payload dtype of the pooled cache; None (default) uses
        the model's activation compute dtype.
    kv_bits: 8 → int8 pooled KV cache (int8 payload + per-token/per-head
        scales; decode attends through the kv_attention op), 16 → fp, None
        → follow ``cfg.kv_cache_bits`` (so a ``*-kv8`` quantize recipe
        carries its KV precision into the engine).
    mesh: a jax ``Mesh`` ("data", "model" [, leading "pod"]) for sharded
        serving. Params are placed under the serve-mode partition specs
        (Megatron TP on "model", int8 QTensor scales co-sharded with their
        payload columns, no FSDP factor — weights stay resident) and the
        pooled cache under the serve cache specs (slots over "data", KV
        heads over "model"). All four jitted paths pin the cache's
        NamedShardings as out_shardings — with donation preserved, so the
        sharded pool still updates in place — and GSPMD partitions the
        step. Per-slot computation is row-independent, so slot sharding is
        exact; TP's row-parallel psum reorders reductions (float-level
        wobble vs single-device; the parity tests pin the tolerance).
    page_size: switch the pool to the paged layout (fixed pages + per-slot
        page tables + refcounted shared-prefix reuse; see the module and
        cache_pool docstrings). Tokens are bit-identical to the contiguous
        pool. None (default) keeps the contiguous layout.
    num_pages: page-pool size (paged mode only); default gives every slot
        a full ring. Admission blocks head-of-line when the pool can't
        cover the head request's pages, after evicting prefix-index
        entries LRU.
    prefix_reuse: enable the scheduler's PrefixIndex (paged mode only):
        prefill completion publishes fully-covered prompt pages, and later
        admissions map them (copy-on-write) instead of recomputing the
        shared prefix.
    max_queue: bound on the admission queue; ``submit`` beyond it raises the
        retryable ``QueueFull`` (back-pressure) and counts a shed. None
        (default) = unbounded.
    straggler: a ``runtime.fault_tolerance.StragglerMonitor`` observing
        per-engine-step wall time (steps slower than ``threshold ×`` the
        EMA count into ``stats["straggler_steps"]``); None = defaults. The
        monitor's threshold is surfaced as ``stats["straggler_threshold"]``
        so serve reports can show what "slow" meant.

    **Streaming** (``set_stream_callbacks``): the engine exposes a
    step-boundary token surface for the async front-end (serving/server.py)
    — ``on_token(rid, tokens, tick)`` fires at every host sync that
    materializes new tokens for a request (token ``i`` of the batch landed
    at engine tick ``tick + i``; a fused horizon delivers its K tokens in
    one call), and ``on_result(result)`` fires exactly once per request at
    the moment its ``RequestResult`` is recorded, for EVERY terminal status
    (ok / expired / cancelled / quarantined — including requests shed from
    the queue or reaped while parked). A preempted-then-resumed request
    streams each token exactly once: tokens generated before the preemption
    were already delivered, and resumption streams only the continuation.
    Callbacks run synchronously inside ``step()`` at syncs that happen
    anyway, so streaming adds zero extra host round trips.
    """

    def __init__(self, model, params, cfg, *, num_slots: int = 4,
                 max_len: int = 128, prefill_chunk: int = 16,
                 cache_dtype=None, decode_horizon: int = 8,
                 fast: bool = True, kv_bits: Optional[int] = None,
                 mesh=None, page_size: Optional[int] = None,
                 num_pages: Optional[int] = None, prefix_reuse: bool = True,
                 max_queue: Optional[int] = None,
                 straggler: Optional[StragglerMonitor] = None):
        if cfg.family in ("ssm", "hybrid") or cfg.is_encdec:
            raise ValueError(
                f"the serving engine supports attention-family decoder-only "
                f"models (got {cfg.name!r}, family {cfg.family!r})"
            )
        if decode_horizon < 1:
            raise ValueError(f"decode_horizon must be >= 1, got {decode_horizon}")
        self.model = model
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.prefill_chunk = prefill_chunk
        self.decode_horizon = decode_horizon
        self.fast = fast
        self.mesh = mesh
        if mesh is not None:
            from ..sharding import named_shardings, params_pspecs

            heads = {"n_q": cfg.n_heads, "n_kv": cfg.n_kv_heads}
            p_shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params
            )
            specs = params_pspecs(p_shapes, mesh, heads, mode="serve")
            self.params = jax.device_put(params, named_shardings(specs, mesh))
        self.pool = CachePool(model, num_slots, max_len, dtype=cache_dtype,
                              kv_bits=kv_bits, mesh=mesh,
                              page_size=page_size, num_pages=num_pages)
        self.kv_bits = self.pool.kv_bits
        self.page_size = self.pool.page_size
        self.paged = self.pool.paged
        self.prefix_index = (PrefixIndex(self.page_size)
                             if self.paged and prefix_reuse else None)
        # may be < the requested max_len (sliding-window ring); admission is
        # capped at the real ring so wrap-around never clobbers live keys
        self.max_len = self.pool.max_len
        self.scheduler = FIFOScheduler(max_queue=max_queue)
        self.straggler = straggler or StragglerMonitor()
        self.clock = 0.0
        # streaming surface (set_stream_callbacks): fired at existing host
        # syncs — None (default) keeps the batch submit/run contract alone
        self._on_token = None
        self._on_result = None
        self._inflight: dict[int, _InFlight] = {}
        self._parked: collections.deque[_Parked] = collections.deque()
        # rids marked for cancellation while in flight (takes effect at the
        # next step boundary) and for NaN injection (chaos: the row is
        # treated as non-finite at its next host sync)
        self._cancelled: set[int] = set()
        self._inject_bad: set[int] = set()
        self._draining = False
        self._waited = 0.0          # this step's seconds in _fetch
        # REPRO_POOL_CHECK=1: audit pool bookkeeping after every step
        self._pool_check = os.environ.get("REPRO_POOL_CHECK") == "1"
        self.results: dict[int, RequestResult] = {}
        self.stats = {
            "decode_steps": 0,        # token-level steps (fast: += K/horizon)
            "decode_dispatches": 0,   # jitted decode calls
            "prefill_chunks": 0,      # chunk-level prefill advances
            "prefill_dispatches": 0,  # jitted prefill calls
            "host_syncs": 0,          # device→host materializations
            "generated_tokens": 0,
            # running aggregate, not a per-step list: a long-lived engine
            # must not grow memory with uptime
            "occupancy_sum": 0.0,
            "engine_steps": 0,
            # the host's own time in step(): the seconds the calls took
            # less their waits on device results (``_fetch``), and the
            # number of calls
            "step_host_s": 0.0,
            "step_calls": 0,
            # fault-tolerance counters (the serve report's fault table)
            "preempted": 0,           # in-flight requests parked for pages
            "resumed": 0,             # parked requests re-admitted
            "shed": 0,                # submissions rejected (QueueFull)
            "cancelled": 0,           # client cancellations honored
            "expired": 0,             # deadline reaps (queued or in flight)
            "quarantined": 0,         # non-finite rows retired
            "straggler_steps": 0,     # engine steps flagged by the monitor
            # what "slow" means for the monitor above (a config echo, not a
            # counter — serve reports print it next to the flagged count)
            "straggler_threshold": float(getattr(self.straggler,
                                                 "threshold", 0.0)),
        }
        # every jit donates the pooled cache (argnum 2): the KV pool is
        # updated in place instead of being copied on each call, mirroring
        # launch/steps.py / dryrun.py. The buffer passed in is INVALID after
        # the call — the engine immediately rebinds pool.cache to the output.
        # Under a mesh the cache's NamedShardings are additionally pinned as
        # out_shardings (tokens replicate — they're host-bound anyway): the
        # in/out shardings then match leaf-for-leaf, which is what keeps
        # donation's in-place buffer reuse valid for the sharded pool, and
        # GSPMD can't drift the pool's layout between steps (a drift would
        # force a recompile per step).
        kw: dict = {"donate_argnums": (2,)}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            # outputs are (tokens, bad-row mask, cache): tokens and the bad
            # mask replicate (both host-bound), the cache keeps its specs
            rep = NamedSharding(mesh, PartitionSpec())
            kw["out_shardings"] = (rep, rep, self.pool.shardings)
        # paged mode jits the thin gather/commit wrappers around the SAME
        # impls (identical signatures), so everything downstream — the
        # serving loop, warmup, the lint layer's lowering — is layout-blind
        self._impls = {
            "prefill": (self._paged_prefill_chunk_impl if self.paged
                        else self._prefill_chunk_impl),
            "decode": (self._paged_decode_impl if self.paged
                       else self._decode_impl),
            "prefill_multi": (self._paged_prefill_multi_impl if self.paged
                              else self._prefill_multi_impl),
            "decode_horizon": (self._paged_decode_horizon_impl if self.paged
                               else self._decode_horizon_impl),
        }
        if mesh is not None:
            # arm the serve-mesh context while each impl TRACES, so the
            # decode hot path can shard_map its fused attention kernel over
            # ("data", "model") — see models.layers.set_serve_mesh
            from ..models.layers import set_serve_mesh
            from ..sharding.partition import _dp_world

            dp_axes, _ = _dp_world(mesh)
            if isinstance(dp_axes, str):
                dp_axes = (dp_axes,)

            def _armed(fn):
                @functools.wraps(fn)
                def wrapped(*a, **k):
                    prev = set_serve_mesh(mesh, dp=dp_axes)
                    try:
                        return fn(*a, **k)
                    finally:
                        set_serve_mesh(prev["mesh"], dp=prev["dp"],
                                       model=prev["model"])
                return wrapped

            self._impls = {n: _armed(f) for n, f in self._impls.items()}
        self._prefill_fn = jax.jit(self._impls["prefill"], **kw)
        self._decode_fn = jax.jit(self._impls["decode"], **kw)
        self._prefill_multi_fn = jax.jit(self._impls["prefill_multi"], **kw)
        self._decode_horizon_fn = jax.jit(self._impls["decode_horizon"],
                                          static_argnames=("k",), **kw)

    @classmethod
    def from_quantized(cls, qm, **kwargs) -> "ServingEngine":
        """Build an engine over a pipeline ``QuantizedModel`` artifact."""
        return cls(qm.model, qm.params, qm.cfg, **kwargs)

    # -------------------------------------------------------- jitted kernels
    def _prefill_masked(self, params, tokens, cache, n_valid, fresh, is_real):
        """Full-width masked prefill: EVERY pool slot advances one chunk in
        slot position — no gather/scatter, each slot's rows never move.

        This is what keeps the pool's slot sharding alive under TP: the old
        pooled gather (``jnp.take`` over dynamic slot ids) forced GSPMD to
        all-gather whole cache leaves around every prefill dispatch — the
        collective-budget ``known_debt`` the -tp serving contracts used to
        carry. In slot position the batch axis IS the pool axis, so every
        row stays on its owning shard and the prefill emits no pool-sized
        collectives at all.

        tokens: [B, C] in slot position (zero rows for slots not
        prefilling); n_valid: [B] (pads 1 — they select position 0's
        logits); fresh: [B] rows whose bookkeeping reset (kpos → -1, pos →
        0) was deferred from ``CachePool.allocate(reset=False)``; is_real:
        [B] marks rows that are actually prefilling. Pad rows run the model
        for shape stability; their bookkeeping rolls back wholesale and
        their C-wide ring write window — saved before the model's in-place
        appends — is restored after, so a pad row's cache bytes are
        bit-identical before/after (live keys of decoding slots riding
        along are never clobbered, even across a ring wrap). Returns
        per-row greedy tokens from each row's last valid position, the
        per-row non-finite flag masked to real rows, and the updated pool.
        """
        C = tokens.shape[1]
        S = cache["kpos"].shape[1]
        start = jnp.where(fresh, 0, cache["pos"])            # [B]
        win = (start[:, None]
               + jnp.arange(C, dtype=jnp.int32)[None, :]) % S  # [B, C]
        payload = [k for k in cache if k not in KNOWN_BOOKKEEPING]
        saved = {k: _take_window(cache[k], win) for k in payload}
        sub = {
            **cache,
            "kpos": jnp.where(fresh[:, None], -1, cache["kpos"]),
            "pos": start,
        }
        logits, sub = self.model.prefill(
            params, tokens, sub, logits_at=n_valid - 1
        )
        end = start + n_valid
        kpos = jnp.where(sub["kpos"] >= end[:, None], -1, sub["kpos"])
        out = {
            **sub,
            "kpos": jnp.where(is_real[:, None], kpos, cache["kpos"]),
            "pos": jnp.where(is_real, end, cache["pos"]),
        }
        for k in payload:
            keep = is_real.reshape((1, -1) + (1,) * (saved[k].ndim - 2))
            vals = jnp.where(keep, _take_window(out[k], win), saved[k])
            out[k] = _put_window(out[k], win, vals)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)       # [B]
        bad = ~jnp.all(jnp.isfinite(logits), -1) & is_real   # [B]
        return tok, bad, out

    def _prefill_chunk_impl(self, params, tokens, cache, slot, n_valid):
        """One prompt chunk into `slot` of the pooled cache (the stepwise
        reference path). tokens: [1, C] (zero-padded past n_valid); the row
        is placed at its slot of a full-width masked prefill, so the pool
        is addressed in slot position here too (no dynamic slice under TP).
        Returns the greedy token from the last valid position and the
        per-row non-finite flag, both [1].
        """
        B = cache["kpos"].shape[0]
        is_real = jnp.arange(B) == slot
        tok, bad, cache = self._prefill_masked(
            params,
            jnp.where(is_real[:, None], jnp.broadcast_to(tokens, (B,) + tokens.shape[1:]), 0),
            cache,
            jnp.where(is_real, n_valid, 1).astype(jnp.int32),
            jnp.zeros((B,), bool),
            is_real,
        )
        return (jax.lax.dynamic_slice_in_dim(tok, slot, 1),
                jax.lax.dynamic_slice_in_dim(bad, slot, 1), cache)

    def _prefill_multi_impl(self, params, tokens, cache, n_valid, fresh,
                            is_real):
        """All currently-prefilling slots advance one chunk in ONE
        full-width dispatch (see ``_prefill_masked``). One compiled shape —
        [num_slots, C] — covers every prefill step; row-independent compute
        keeps each row bit-identical to its batch-1 dispatch."""
        return self._prefill_masked(params, tokens, cache, n_valid, fresh,
                                    is_real)

    def _decode_masked(self, params, tokens, cache, active):
        """One full-slot-batch decode step. ``active`` [B] marks rows that
        are really decoding; the rest (free, or mid-prefill) ride along for
        shape stability, so their bookkeeping write this step — one kpos
        entry and the pos advance — is rolled back before commit. (Their K/V
        payload write is harmless: masked by kpos=-1 and overwritten by the
        slot's next real token at the same ring index.) Also returns the
        per-row non-finite-logits flag, masked to active rows (inactive rows
        legitimately carry garbage)."""
        prev_pos = cache["pos"]                              # [B]
        logits, cache = self.model.decode_step(params, tokens, cache)
        S = cache["kpos"].shape[1]
        wrote = jnp.arange(S)[None, :] == (prev_pos % S)[:, None]
        kpos = jnp.where((~active)[:, None] & wrote, -1, cache["kpos"])
        pos = jnp.where(active, cache["pos"], prev_pos)
        cache = {**cache, "kpos": kpos, "pos": pos}
        bad = ~jnp.all(jnp.isfinite(logits), -1) & active    # [B]
        return jnp.argmax(logits, -1).astype(jnp.int32), bad, cache

    def _decode_impl(self, params, tokens, cache, active):
        """Stepwise reference: one decode step, one host round trip."""
        return self._decode_masked(params, tokens, cache, active)

    def _decode_horizon_impl(self, params, tokens, cache, remaining, *, k):
        """K decode steps fused on device: one dispatch, one host sync.

        tokens: [B, 1] current token per slot (garbage for inactive rows);
        remaining: [B] tokens still owed per slot (0 = free / mid-prefill).
        Each scan step applies exactly the stepwise masked decode with
        ``active = remaining > 0``; a row whose budget runs out freezes in
        place (its token stops being fed forward and its bookkeeping rolls
        back), so callers that pick ``k <= min(remaining[active])`` retire
        rows exactly at the horizon boundary. Returns the [B, k] token
        buffer, the per-row bad flag OR-ed across the row's active steps,
        and the updated pooled cache.
        """
        def body(carry, _):
            tokens, cache, remaining, badacc = carry
            active = remaining > 0
            nxt, bad, cache = self._decode_masked(params, tokens, cache,
                                                  active)
            tokens = jnp.where(active[:, None], nxt[:, None], tokens)
            remaining = jnp.where(active, remaining - 1, remaining)
            return (tokens, cache, remaining, badacc | bad), nxt

        badacc = jnp.zeros(remaining.shape, bool)
        (_, cache, _, badacc), toks = jax.lax.scan(
            body, (tokens, cache, remaining, badacc), None, length=k
        )
        return toks.T, badacc, cache                         # [B, k], [B]

    # ------------------------------------------------- paged jit wrappers
    # Same signatures as the contiguous impls: gather the page pool into the
    # dense slot view, run the unchanged impl, commit the host-known write
    # window back into the pages (see _paged_view/_paged_commit).

    def _paged_prefill_chunk_impl(self, params, tokens, cache, slot, n_valid):
        dense = _paged_view(cache, self.page_size, self.max_len)
        start = jax.lax.dynamic_index_in_dim(cache["pos"], slot,
                                             keepdims=False)
        tok, bad, dense = self._prefill_chunk_impl(params, tokens, dense,
                                                   slot, n_valid)
        C = tokens.shape[1]
        B, S = cache["kpos"].shape
        row = (start + jnp.arange(C, dtype=jnp.int32)) % S
        rows = jnp.full((B, C), -1, jnp.int32).at[slot].set(row)
        return tok, bad, _paged_commit(cache, dense, rows, self.page_size)

    def _paged_prefill_multi_impl(self, params, tokens, cache, n_valid,
                                  fresh, is_real):
        dense = _paged_view(cache, self.page_size, self.max_len)
        start = jnp.where(fresh, 0, cache["pos"])            # [B]
        tok, bad, dense = self._prefill_multi_impl(params, tokens, dense,
                                                   n_valid, fresh, is_real)
        C = tokens.shape[1]
        S = cache["kpos"].shape[1]
        rows = (start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]) % S
        rows = jnp.where(is_real[:, None], rows, -1)     # pad rows: no write
        return tok, bad, _paged_commit(cache, dense, rows, self.page_size)

    def _paged_decode_impl(self, params, tokens, cache, active):
        dense = _paged_view(cache, self.page_size, self.max_len)
        prev = cache["pos"]
        tok, bad, dense = self._decode_masked(params, tokens, dense, active)
        S = cache["kpos"].shape[1]
        rows = jnp.where(active, prev % S, -1)[:, None]  # [B, 1]
        return tok, bad, _paged_commit(cache, dense, rows, self.page_size)

    def _paged_decode_horizon_impl(self, params, tokens, cache, remaining,
                                   *, k):
        # ONE gather before the scan and one commit after it: the k fused
        # steps read/write the dense view, so the horizon's page traffic is
        # amortized exactly like its host syncs
        dense = _paged_view(cache, self.page_size, self.max_len)
        prev = cache["pos"]
        toks, bad, dense = self._decode_horizon_impl(params, tokens, dense,
                                                     remaining, k=k)
        S = cache["kpos"].shape[1]
        t = jnp.arange(k, dtype=jnp.int32)[None, :]
        rows = jnp.where(t < remaining[:, None],
                         (prev[:, None] + t) % S, -1)    # [B, k]
        return toks, bad, _paged_commit(cache, dense, rows, self.page_size)

    # ------------------------------------------------------------ lifecycle
    def submit(self, request: Request) -> None:
        P, G = len(request.prompt), request.max_new_tokens
        need = required_cache_len(P, G, self.prefill_chunk)
        if need > self.max_len:
            raise RequestTooLarge(
                f"request {request.rid}: needs {need} cache positions "
                f"(prompt {P}, gen {G}, chunk {self.prefill_chunk}) "
                f"but max_len={self.max_len}"
            )
        if self.paged:
            n_pages = -(-need // self.page_size)
            if n_pages > self.pool.num_pages:
                # would head-of-line block forever — even an empty pool
                # could never map it
                raise RequestTooLarge(
                    f"request {request.rid}: needs {n_pages} pages "
                    f"(page_size {self.page_size}) but the pool only has "
                    f"{self.pool.num_pages}"
                )
        if self._draining:
            self.stats["shed"] += 1
            raise QueueFull(
                f"request {request.rid}: engine is draining — admission "
                f"is closed"
            )
        t = time.perf_counter()
        try:
            self.scheduler.submit(request)
        except QueueFull:
            self.stats["shed"] += 1
            raise
        request.t_submit = t

    def set_stream_callbacks(self, on_token=None, on_result=None) -> None:
        """Wire the step-boundary streaming surface (see the class
        docstring): ``on_token(rid, tokens, tick)`` per host sync that
        materialized tokens, ``on_result(result)`` once per recorded
        ``RequestResult``. Pass None to detach either."""
        self._on_token = on_token
        self._on_result = on_result

    def _emit_tokens(self, fl: _InFlight, tokens: Sequence[int],
                     tick: float) -> None:
        if self._on_token is not None:
            # a resumed request keeps its original rid (_resume_request), so
            # the stream is continuous across preemption
            self._on_token(fl.req.rid, list(tokens), tick)

    def _emit_result(self, result: RequestResult) -> None:
        if self._on_result is not None:
            self._on_result(result)

    def _drop_result(self, req: Request, status: str,
                     tokens: Sequence[int] = (),
                     admitted_at: Optional[float] = None) -> None:
        """Record a result for a request dropped OUTSIDE a slot (shed from
        the queue, or reaped while parked)."""
        self.results[req.rid] = RequestResult(
            rid=req.rid, prompt_len=len(req.prompt), tokens=list(tokens),
            arrival=req.arrival,
            admitted_at=self.clock if admitted_at is None else admitted_at,
            finished_at=self.clock, status=status,
        )
        self._emit_result(self.results[req.rid])

    def _next_admission(self) -> Optional[Request]:
        """The next admission candidate: the head of the queue once it has
        arrived — after reaping cancelled/expired heads (they shed here, at
        exactly the tick a free slot would otherwise have admitted them)."""
        while True:
            req = self.scheduler.peek_ready(self.clock)
            if req is None:
                return None
            if req.rid in self._cancelled:
                self.scheduler.drop_head()
                self._cancelled.discard(req.rid)
                self._drop_result(req, "cancelled")
                self.stats["cancelled"] += 1
                continue
            if req.deadline is not None and req.deadline <= self.clock:
                self.scheduler.drop_head()
                self._drop_result(req, "expired")
                self.stats["expired"] += 1
                continue
            return req

    def _next_parked(self) -> Optional[_Parked]:
        """The parked head due for resumption, reaping cancelled/expired
        parked entries (their partial tokens are returned)."""
        while self._parked:
            parked = self._parked[0]
            req = parked.req
            if req.rid in self._cancelled:
                self._parked.popleft()
                self._cancelled.discard(req.rid)
                self._drop_result(req, "cancelled", tokens=parked.generated,
                                  admitted_at=parked.admitted_at)
                self.stats["cancelled"] += 1
                continue
            if req.deadline is not None and req.deadline <= self.clock:
                self._parked.popleft()
                self._drop_result(req, "expired", tokens=parked.generated,
                                  admitted_at=parked.admitted_at)
                self.stats["expired"] += 1
                continue
            return parked
        return None

    def _resume_request(self, parked: _Parked) -> Request:
        """The internal request a parked entry resumes as: original prompt
        plus everything generated before the preemption, owing the
        remainder of the budget. Re-prefilling that prompt reproduces the
        victim's cache state exactly (prefill and decode agree on every
        cached position — the naive-oracle parity), and the prefix index
        remaps whatever published victim pages survived instead."""
        req = parked.req
        return Request(
            rid=req.rid,
            prompt=list(req.prompt) + [int(t) for t in parked.generated],
            max_new_tokens=req.max_new_tokens - len(parked.generated),
            arrival=req.arrival,
            deadline=req.deadline,
            priority=req.priority,
        )

    def _admit(self) -> None:
        """Admission: parked (preempted) requests resume first — they were
        already admitted once, so a drain still serves them — then the FIFO
        queue (closed while draining)."""
        if self.paged:
            return self._admit_paged()
        pool = self.pool
        while pool.n_free:
            parked = self._next_parked()
            if parked is not None:
                self._parked.popleft()
                req = self._resume_request(parked)
                # fast path: defer the slot's bookkeeping reset into the
                # first jitted prefill chunk, like any fresh admission
                slot = pool.allocate(reset=not self.fast)
                self._inflight[slot] = _InFlight(
                    req=req, slot=slot, admitted_at=parked.admitted_at,
                    fresh=self.fast, prior=list(parked.generated),
                    orig_req=parked.req,
                )
                self.stats["resumed"] += 1
                continue
            if self._draining:
                return
            req = self._next_admission()
            if req is None:
                return
            self.scheduler.pop_ready(self.clock)
            req.t_admit = time.perf_counter()
            # fast path: defer the slot's bookkeeping reset into the first
            # jitted prefill chunk (fresh mask) — admission costs 0 dispatches
            slot = pool.allocate(reset=not self.fast)
            self._inflight[slot] = _InFlight(
                req=req, slot=slot, admitted_at=self.clock, fresh=self.fast
            )

    def _admit_paged(self) -> None:
        """Page-aware FIFO admission: peek the candidate (parked resumes
        first), map its shared prefix pages from the index, and admit only
        when the pool can cover the rest — climbing the exhaustion ladder
        first: (1) evict LRU prefix-index entries, (2) preempt
        strictly-lower-priority in-flight requests (most recently admitted
        first), and finally (3) block head-of-line, exactly like a missing
        slot would."""
        pool = self.pool
        while pool.n_free:
            parked = self._next_parked()
            if parked is not None:
                req = self._resume_request(parked)
            else:
                if self._draining:
                    return
                req = self._next_admission()
                if req is None:
                    return
            P, G = len(req.prompt), req.max_new_tokens
            need = required_cache_len(P, G, self.prefill_chunk)
            shared: list = []
            reuse = 0
            if self.prefix_index is not None:
                pages = self.prefix_index.lookup(req.prompt)
                pg, C = self.page_size, self.prefill_chunk
                # reuse ends on a prefill-chunk boundary — the donor's
                # chunks started there too, which is what makes its cached
                # K/V bit-identical to recomputing them — and leaves >= 1
                # prompt token to prefill, so the first generated token
                # comes from THIS request's own logits
                reuse = (min(len(pages) * pg, P - 1) // C) * C
                shared = pages[: -(-reuse // pg)]
            fresh_needed = pool.pages_needed(need, reuse)
            if not self._cover_pages(fresh_needed, shared, req.priority):
                return                      # head-of-line blocks on pages
            if parked is not None:
                self._parked.popleft()
            else:
                self.scheduler.pop_ready(self.clock)
                req.t_admit = time.perf_counter()
            slot = pool.allocate_pages(need, shared=shared, reuse_len=reuse)
            self._inflight[slot] = _InFlight(
                req=req, slot=slot,
                admitted_at=(self.clock if parked is None
                             else parked.admitted_at),
                prefilled=reuse,
                prior=(list(parked.generated) if parked is not None else []),
                orig_req=(parked.req if parked is not None else None),
            )
            if parked is not None:
                self.stats["resumed"] += 1

    def _cover_pages(self, fresh_needed: int, shared: Sequence[int],
                     priority: int) -> bool:
        """Climb the exhaustion ladder until ``fresh_needed`` pages are
        free: evict LRU index entries, then preempt strictly-lower-priority
        victims (each preemption publishes the victim's computed pages, so
        eviction runs again behind it). Returns False when the ladder is
        exhausted and the candidate must block head-of-line."""
        pool = self.pool

        def evict():
            if self.prefix_index is None:
                return
            protect = set(shared)
            while (fresh_needed > pool.n_free_pages
                   and self.prefix_index.evict_lru(pool, protect)):
                pass

        evict()
        while fresh_needed > pool.n_free_pages:
            victim = self._select_victim(priority)
            if victim is None:
                return False
            self._preempt_one(victim)
            evict()
        return True

    def _retire(self, fl: _InFlight, at: Optional[float] = None,
                status: str = "ok") -> None:
        req = fl.orig_req or fl.req
        self.results[req.rid] = RequestResult(
            rid=req.rid,
            prompt_len=len(req.prompt),
            tokens=fl.prior + list(fl.generated),
            arrival=req.arrival,
            admitted_at=fl.admitted_at,
            finished_at=self.clock if at is None else at,
            status=status,
        )
        del self._inflight[fl.slot]
        self.pool.release(fl.slot)
        self._emit_result(self.results[req.rid])

    def _quarantine(self, fl: _InFlight, at: Optional[float] = None) -> None:
        """Retire a row whose dispatch produced non-finite logits: its slot
        (and pages) are reclaimed, the tokens of the poisoned dispatch are
        dropped, and the tokens generated before it are returned with
        status "quarantined". Row independence means no other slot saw the
        poison. The row's pages are NOT published to the prefix index
        (nothing after the last finite sync can be trusted)."""
        self._inject_bad.discard(fl.req.rid)
        self._retire(fl, at=at, status="quarantined")
        self.stats["quarantined"] += 1

    def _select_victim(self, priority: int) -> Optional[_InFlight]:
        """Preemption victim for an admission at ``priority``: a
        strictly-lower-priority in-flight request, most recently admitted
        first (it has the least sunk work; ties broken by slot id for
        determinism), skipping victims whose resume request could never be
        re-admitted (prompt + generated can outgrow the ring: prefill
        re-pads to chunk multiples)."""
        cands = [fl for fl in self._inflight.values()
                 if fl.req.priority < priority and self._resumable(fl)]
        if not cands:
            return None
        return max(cands, key=lambda fl: (fl.admitted_at, fl.slot))

    def _resumable(self, fl: _InFlight) -> bool:
        """Whether a preempted ``fl`` could be admitted again: its resume
        prompt (original prompt + everything generated) must still fit the
        ring and the page pool after prefill-chunk padding."""
        P = len(fl.req.prompt) + len(fl.generated)
        G = fl.remaining
        if G < 1:
            return False
        need = required_cache_len(P, G, self.prefill_chunk)
        if need > self.max_len:
            return False
        if self.paged and -(-need // self.page_size) > self.pool.num_pages:
            return False
        return True

    def _preempt_one(self, fl: _InFlight) -> None:
        """Preempt ``fl``: publish its computed pages to the prefix index
        (page remapping — a resume maps them back instead of recomputing;
        if pool pressure evicts them first, resume re-prefills, still
        bit-identical), park the request host-side, and release the slot.

        The cache's valid positions cover the prompt plus all generated
        tokens EXCEPT the last (its KV lands with the next decode feed), so
        that is exactly the token prefix published."""
        if self.prefix_index is not None:
            if fl.prefill_done:
                covered = list(fl.req.prompt) + fl.generated[:-1]
            else:
                # mid-prefill: the committed chunks cover prompt[:prefilled]
                covered = list(fl.req.prompt[:fl.prefilled])
            if len(covered) >= self.page_size:
                self.prefix_index.publish(covered, self.pool, fl.slot)
        self._parked.append(_Parked(
            req=fl.orig_req or fl.req,
            generated=fl.prior + list(fl.generated),
            admitted_at=fl.admitted_at,
        ))
        del self._inflight[fl.slot]
        self.pool.release(fl.slot)
        self.stats["preempted"] += 1

    def preempt(self, rid: int) -> None:
        """Manually preempt an in-flight request by id: its slot and pages
        are released and the request parks host-side, resuming through
        normal admission (before any queued request) with bit-identical
        final tokens. Raises KeyError for a request not in flight and
        ValueError when the resume could never fit (see ``_resumable``)."""
        for fl in self._inflight.values():
            if fl.req.rid == rid:
                if not self._resumable(fl):
                    raise ValueError(
                        f"request {rid} cannot be preempted: its resume "
                        f"prompt would exceed the engine's capacity"
                    )
                self._preempt_one(fl)
                return
        raise KeyError(f"request {rid} is not in flight")

    def cancel(self, rid: int) -> bool:
        """Client cancellation. Queued and parked requests are dropped at
        the next step boundary; an in-flight request is removed at its next
        step/horizon boundary, returning the tokens generated so far with
        status "cancelled". Returns False when the rid is unknown (already
        finished, or never submitted)."""
        if any(fl.req.rid == rid for fl in self._inflight.values()):
            self._cancelled.add(rid)
            return True
        if any(p.req.rid == rid for p in self._parked):
            self._cancelled.add(rid)
            return True
        req = self.scheduler.remove(rid)
        if req is not None:
            # dropped from the queue immediately; the result is stamped
            # with the current clock, same as a boundary reap
            self._drop_result(req, "cancelled")
            self.stats["cancelled"] += 1
            return True
        return False

    def request_drain(self) -> None:
        """Graceful drain (the SIGTERM contract): close admission — new
        ``submit`` calls shed with ``QueueFull``, queued requests stay
        unserved — but finish everything in flight INCLUDING parked
        (preempted) requests, which were already admitted once."""
        self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    def _reap(self) -> None:
        """Step-boundary reaping: cancel and expire in-flight requests
        (their partial tokens are returned; pages reclaimed atomically via
        the normal release path). Queued/parked reaping happens in
        admission, at the tick a slot would have considered them."""
        for slot in sorted(self._inflight):
            fl = self._inflight[slot]
            rid = fl.req.rid
            if rid in self._cancelled:
                self._cancelled.discard(rid)
                self._retire(fl, status="cancelled")
                self.stats["cancelled"] += 1
            elif (fl.req.deadline is not None
                    and fl.req.deadline <= self.clock):
                self._retire(fl, status="expired")
                self.stats["expired"] += 1

    def check_invariants(self) -> None:
        """Audit the pool against every external page pin the engine knows
        about (the prefix index); raises AssertionError on violation. The
        chaos harness calls this after every step; ``REPRO_POOL_CHECK=1``
        turns it on per-step everywhere."""
        ext: dict[int, int] = {}
        if self.prefix_index is not None:
            for page in self.prefix_index.pages():
                ext[page] = ext.get(page, 0) + 1
        self.pool.check_invariants(external_refs=ext)

    def inject_bad(self, rid: int) -> None:
        """Chaos hook: treat ``rid``'s row as non-finite at its next host
        sync (prefill completion or decode boundary) — exercises the
        quarantine path deterministically without poisoning device state."""
        self._inject_bad.add(rid)

    def _finish_prefill(self, fl: _InFlight, first: int) -> None:
        if self.prefix_index is not None:
            # publish at prefill COMPLETION (not retirement) so concurrent
            # requests right behind the donor already share its pages
            self.prefix_index.publish(fl.req.prompt, self.pool, fl.slot)
        fl.generated.append(first)
        fl.cur_token = first
        self.stats["generated_tokens"] += 1
        self._emit_tokens(fl, [first], self.clock)
        if fl.done:
            self._retire(fl)

    def _prefill_phase(self) -> None:
        C = self.prefill_chunk
        for slot in sorted(self._inflight):
            fl = self._inflight[slot]
            if fl.prefill_done:
                continue
            prompt = np.asarray(fl.req.prompt, np.int32)
            n = min(C, len(prompt) - fl.prefilled)
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :n] = prompt[fl.prefilled:fl.prefilled + n]
            tok, bad, self.pool.cache = self._prefill_fn(
                self.params, jnp.asarray(chunk), self.pool.cache,
                jnp.int32(slot), jnp.int32(n),
            )
            fl.prefilled += n
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_dispatches"] += 1
            if fl.prefill_done:
                # bad is examined only at syncs that happen anyway (here:
                # prefill completion) — NaN quarantine costs zero extra
                # host round trips
                self.stats["host_syncs"] += 1
                if bool(bad[0]) or fl.req.rid in self._inject_bad:
                    self._quarantine(fl)
                else:
                    self._finish_prefill(fl, int(tok[0]))

    def _prefill_phase_fast(self) -> None:
        """One full-width [B, C] dispatch covering every prefilling slot in
        slot position (non-prefilling slots ride along masked — see
        ``_prefill_masked``); syncs only when some row consumed its final
        prompt chunk this step."""
        C = self.prefill_chunk
        pending = [self._inflight[s] for s in sorted(self._inflight)
                   if not self._inflight[s].prefill_done]
        if not pending:
            return
        with TraceAnnotation("engine.prefill.prepare"):
            B = self.num_slots
            tokens = np.zeros((B, C), np.int32)
            n_valid = np.ones((B,), np.int32)   # pads select position 0
            fresh = np.zeros((B,), bool)
            is_real = np.zeros((B,), bool)
            for fl in pending:
                s = fl.slot
                prompt = np.asarray(fl.req.prompt, np.int32)
                n = min(C, len(prompt) - fl.prefilled)
                tokens[s, :n] = prompt[fl.prefilled:fl.prefilled + n]
                n_valid[s], fresh[s], is_real[s] = n, fl.fresh, True
            args = (jnp.asarray(tokens), jnp.asarray(n_valid),
                    jnp.asarray(fresh), jnp.asarray(is_real))
        with TraceAnnotation("engine.prefill.dispatch"):
            tok, bad, self.pool.cache = self._prefill_multi_fn(
                self.params, args[0], self.pool.cache, *args[1:])
        self.stats["prefill_chunks"] += len(pending)
        self.stats["prefill_dispatches"] += 1
        finishers = []
        for fl in pending:
            if fl.fresh:
                fl.fresh = False
                # the deferred fresh-mask reset just committed inside the
                # jitted prefill — the pool stops tracking it as pending
                self.pool.note_reset_committed(fl.slot)
            fl.prefilled += int(n_valid[fl.slot])
            if fl.prefill_done:
                finishers.append(fl)
        if finishers:
            # materialize once for all rows
            tok_np, bad_np = self._fetch("engine.prefill.sync", tok, bad)
            self.stats["host_syncs"] += 1
            with TraceAnnotation("engine.prefill.emit"):
                for fl in finishers:
                    if (bool(bad_np[fl.slot])
                            or fl.req.rid in self._inject_bad):
                        self._quarantine(fl)
                    else:
                        self._finish_prefill(fl, int(tok_np[fl.slot]))

    def _fetch(self, span: str, *arrays) -> list:
        """The device ``arrays`` on the host, read inside profiler span
        ``span``: the step's wait on the device, which
        ``stats["step_host_s"]`` leaves out."""
        t = time.perf_counter()
        with TraceAnnotation(span):
            out = [np.asarray(a) for a in arrays]
        self._waited += time.perf_counter() - t
        return out

    def _decode_phase(self) -> None:
        active = [fl for fl in self._inflight.values()
                  if fl.prefill_done and not fl.done]
        if not active:
            return
        tokens = np.zeros((self.num_slots, 1), np.int32)
        active_mask = np.zeros((self.num_slots,), bool)
        for fl in active:
            tokens[fl.slot, 0] = fl.cur_token
            active_mask[fl.slot] = True
        next_tok, bad, self.pool.cache = self._decode_fn(
            self.params, jnp.asarray(tokens), self.pool.cache,
            jnp.asarray(active_mask),
        )
        next_np = np.asarray(next_tok)
        bad_np = np.asarray(bad)
        self.stats["decode_steps"] += 1
        self.stats["decode_dispatches"] += 1
        self.stats["host_syncs"] += 1
        for fl in active:
            if bool(bad_np[fl.slot]) or fl.req.rid in self._inject_bad:
                self._quarantine(fl)
                continue
            tok = int(next_np[fl.slot])
            fl.generated.append(tok)
            fl.cur_token = tok
            self.stats["generated_tokens"] += 1
            self._emit_tokens(fl, [tok], self.clock)
            if fl.done:
                self._retire(fl)

    def _choose_horizon(self, active) -> int:
        """Adaptive K: fuse as many decode steps as possible without moving
        any retire/admit/prefill event off its stepwise-path clock tick.
        The result is rounded DOWN to a power of two — every cap below is an
        upper bound, so the tick-exact schedule is preserved while the
        number of distinct compiled scans stays log2(decode_horizon)+1."""
        k = min(self.decode_horizon, min(fl.remaining for fl in active))
        if any(not fl.prefill_done for fl in self._inflight.values()):
            # a prefilling slot advances one chunk per engine tick; a long
            # horizon would starve it, so fall back to stepwise cadence
            return 1
        deadlines = [fl.req.deadline for fl in self._inflight.values()
                     if fl.req.deadline is not None]
        if deadlines:
            # expiry is reaped at step starts (clock >= deadline); the
            # horizon must not coast past the earliest one, so the reap
            # lands on the same tick as the stepwise path (the deadline
            # twin of the arrival cap below)
            k = min(k, max(1, int(math.ceil(min(deadlines) - self.clock))))
        if self.pool.n_free:
            nxt = self.scheduler.peek_arrival()
            if nxt is not None:
                if nxt <= self.clock:
                    # head is ready and a slot freed mid-step (prefill
                    # retire): admit on the very next tick, like stepwise
                    return 1
                # a free slot is waiting on the FIFO head's arrival:
                # admission must not be delayed past it by a long horizon
                k = min(k, int(math.ceil(nxt - self.clock)))
        return _pow2_floor(k)

    def _decode_phase_fast(self) -> int:
        """Fused decode horizon; returns the number of decode steps run (the
        engine-clock ticks this phase consumed)."""
        active = [fl for fl in self._inflight.values()
                  if fl.prefill_done and not fl.done]
        if not active:
            return 1
        with TraceAnnotation("engine.decode.prepare"):
            k = self._choose_horizon(active)
            tokens = np.zeros((self.num_slots, 1), np.int32)
            remaining = np.zeros((self.num_slots,), np.int32)
            for fl in active:
                tokens[fl.slot, 0] = fl.cur_token
                # cap at k: the scan must not generate past this horizon
                # even if bookkeeping and the device view of the budget ever
                # diverged
                remaining[fl.slot] = min(fl.remaining, k)
            tokens, remaining = jnp.asarray(tokens), jnp.asarray(remaining)
        with TraceAnnotation("engine.decode.dispatch", k=k, rows=len(active)):
            toks, bad, self.pool.cache = self._decode_horizon_fn(
                self.params, tokens, self.pool.cache, remaining, k=k)
        # the horizon's single host sync
        toks_np, bad_np = self._fetch("engine.decode.sync", toks, bad)
        self.stats["decode_steps"] += k
        self.stats["decode_dispatches"] += 1
        self.stats["host_syncs"] += 1
        with TraceAnnotation("engine.decode.emit"):
            for fl in active:
                if bool(bad_np[fl.slot]) or fl.req.rid in self._inject_bad:
                    # the bad flag is OR-ed across the horizon: the whole
                    # horizon's tokens for this row are untrusted and
                    # dropped (other rows are untouched — row independence)
                    self._quarantine(fl, at=self.clock + k - 1)
                    continue
                new = [int(t) for t in toks_np[fl.slot, :k]]
                fl.generated.extend(new)
                fl.cur_token = new[-1]
                self.stats["generated_tokens"] += k
                self._emit_tokens(fl, new, self.clock)
                if fl.done:
                    # the last token landed on the horizon's final tick —
                    # stamp completion with that tick, matching the
                    # stepwise timeline
                    self._retire(fl, at=self.clock + k - 1)
        return k

    def step(self) -> None:
        """One engine iteration: reap (deadlines/cancellations) → admit →
        chunked prefill → batched decode. On the fast path a fused decode
        horizon advances the engine clock by K ticks (one tick per
        generated-token step, matching the stepwise path's timeline).

        Each phase runs inside a profiler span (``engine.step`` over the
        whole step; ``engine.reap``, ``engine.admit`` and, on the fast
        path, ``engine.{prefill,decode}.{prepare,dispatch,sync,emit}``
        inside it), recorded only while a JAX profiler session runs. The
        stepwise reference path has only the step, reap and admit spans,
        and its waits on the device count in ``stats["step_host_s"]``."""
        with TraceAnnotation("engine.step"):
            t0 = time.perf_counter()
            self._waited = 0.0
            with TraceAnnotation("engine.reap"):
                self._reap()
            with TraceAnnotation("engine.admit"):
                self._admit()
            occ_pre = len(self._inflight) / self.num_slots
            if self.fast:
                self._prefill_phase_fast()
                # a gen-at-prefill request may have retired above; ticks
                # 2..K of the horizon see that state (no admissions can land
                # mid-horizon — the arrival cap ends the horizon at the next
                # arrival — and decode retires only on the final tick), so
                # the occupancy accounting stays tick-identical to the
                # stepwise path
                occ_post = len(self._inflight) / self.num_slots
                ticks = self._decode_phase_fast()
                self.stats["occupancy_sum"] += (occ_pre
                                                + occ_post * (ticks - 1))
            else:
                self._prefill_phase()
                self._decode_phase()
                ticks = 1
                self.stats["occupancy_sum"] += occ_pre
            self.stats["engine_steps"] += ticks
            self.clock += float(ticks)
            took = time.perf_counter() - t0
            self.stats["step_host_s"] += took - self._waited
            self.stats["step_calls"] += 1
            if self.straggler.observe(self.stats["engine_steps"], took):
                self.stats["straggler_steps"] += 1
            if self._pool_check:
                self.check_invariants()

    def run(self, requests: Optional[Sequence[Request]] = None
            ) -> dict[int, RequestResult]:
        """Submit ``requests`` (if given), step until fully drained, and
        return — draining ``self.results`` so a long-lived engine doesn't
        retain every request it ever served. While ``request_drain()`` is
        in effect queued requests are NOT served (admitted + parked work
        still finishes)."""
        for r in requests or ():
            self.submit(r)
        while (self._inflight or self._parked
               or (not self._draining and self.scheduler.pending())):
            self.step()
        out, self.results = self.results, {}
        return out

    # ------------------------------------------------- static introspection
    # The lint layer (analysis/lint) reasons about the serve paths WITHOUT
    # running them: which jits exist, what shapes they can be dispatched at,
    # and what warmup() compiles. warmup() itself is driven off the same
    # enumeration so the two can never drift apart.

    def warmup_shapes(self) -> set:
        """The (jit, dim) pairs ``warmup()`` compiles: the single full-width
        prefill shape and every power-of-two decode-scan horizon on the fast
        path; the batch-1 stepwise shapes otherwise."""
        if not self.fast:
            return {("prefill", 1), ("decode", 1)}
        horizons = {1 << i for i in range(self.decode_horizon.bit_length())
                    if 1 << i <= self.decode_horizon}
        return ({("prefill_multi", self.num_slots)}
                | {("decode_horizon", k) for k in horizons})

    def dispatch_shapes(self) -> set:
        """Every (jit, dim) the serving loop can actually dispatch: the
        full-width masked prefill is ONE compiled shape ([num_slots, C] in
        slot position), horizons ``pow2_floor(k)`` for 1 <= k <=
        decode_horizon. The recompilation-guard lint rule checks this set is
        CLOSED under ``warmup_shapes()`` — a live step never compiles."""
        if not self.fast:
            return {("prefill", 1), ("decode", 1)}
        horizons = {_pow2_floor(k)
                    for k in range(1, self.decode_horizon + 1)}
        return ({("prefill_multi", self.num_slots)}
                | {("decode_horizon", k) for k in horizons})

    def serve_jit_specs(self) -> dict:
        """{name: (jit_fn, impl_fn, args, static_kwargs)} for every jitted
        serve path, with representative arguments at the widest warmed shape
        (prefill_multi at P=num_slots, decode_horizon at k=decode_horizon).
        ``params``/``cache`` are the engine's live (possibly sharded) arrays
        so lowering sees the real placements; tracing/lowering never
        executes, so donation does not invalidate the pool."""
        B, C = self.num_slots, self.prefill_chunk
        cache = self.pool.cache
        return {
            "prefill": (
                self._prefill_fn, self._impls["prefill"],
                (self.params, jnp.zeros((1, C), jnp.int32), cache,
                 jnp.int32(0), jnp.int32(C)),
                {},
            ),
            "decode": (
                self._decode_fn, self._impls["decode"],
                (self.params, jnp.zeros((B, 1), jnp.int32), cache,
                 jnp.ones((B,), bool)),
                {},
            ),
            "prefill_multi": (
                self._prefill_multi_fn, self._impls["prefill_multi"],
                (self.params, jnp.zeros((B, C), jnp.int32), cache,
                 jnp.ones((B,), jnp.int32), jnp.zeros((B,), bool),
                 jnp.ones((B,), bool)),
                {},
            ),
            "decode_horizon": (
                self._decode_horizon_fn, self._impls["decode_horizon"],
                (self.params, jnp.zeros((B, 1), jnp.int32), cache,
                 jnp.full((B,), self.decode_horizon, jnp.int32)),
                {"k": self.decode_horizon},
            ),
        }

    def lowered_serve_jits(self) -> dict:
        """{name: jax.stages.Lowered} for the four serve jits — traced and
        lowered (StableHLO), NOT compiled or run."""
        return {
            name: fn.lower(*args, **kw)
            for name, (fn, _, args, kw) in self.serve_jit_specs().items()
        }

    def warmup(self) -> None:
        """Compile every serving shape ahead of traffic — exactly the
        ``warmup_shapes()`` set: the power-of-two prefill widths and decode
        horizons this engine can dispatch (the stepwise shapes when
        ``fast=False``). Runs tiny throwaway requests through the real loop
        so a production engine (or a benchmark) serves steady state instead
        of hitting XLA compiles mid-traffic.

        Warmup is side-effect-free: stats, clock, results, straggler EMA,
        the prefix index (warmup publishes throwaway ``[0]`` prompts into a
        TEMPORARY index, never the live one) and the pool — cache contents
        AND bookkeeping, down to free-list order — are all bit-identical
        before/after (the warmup-pollution regression test pins this)."""
        if self.scheduler.pending() or self._inflight or self._parked:
            raise RuntimeError(
                "warmup() needs an idle engine — it runs (and discards) "
                "throwaway requests through the serving loop"
            )
        pool = self.pool
        snap_stats, snap_clock = dict(self.stats), self.clock
        snap_order = list(self.scheduler.admitted_order)
        snap_results = dict(self.results)
        snap_straggler, self.straggler = self.straggler, StragglerMonitor()
        # throwaway warmup traffic must not stream into a wired front-end
        snap_cbs = (self._on_token, self._on_result)
        self._on_token = self._on_result = None
        # deep-copy the cache: every jit donates it, so warmup traffic would
        # otherwise overwrite the pre-warmup buffers in place
        snap_cache = jax.tree.map(jnp.copy, pool.cache)
        snap_free, snap_alloc = set(pool._free), set(pool._allocated)
        snap_pending = set(pool._pending_reset)
        if pool.paged:
            snap_pages = list(pool._free_pages)
            snap_ref = list(pool._page_ref)
            snap_slot_pages = {s: list(p) for s, p in
                               pool._slot_pages.items()}
            snap_cow = pool.cow_copies
        snap_index = self.prefix_index
        if snap_index is not None:
            self.prefix_index = PrefixIndex(self.page_size)
        try:
            shapes = self.warmup_shapes()
            rid = -1
            widths = sorted(w for j, w in shapes if j.startswith("prefill"))
            for w in widths:             # prefill widths (no decode: gen 1)
                self.run([Request(rid=rid - j, prompt=[0], max_new_tokens=1)
                          for j in range(w)])
                rid -= w
            horizons = sorted(k for j, k in shapes if j.startswith("decode"))
            for k in horizons:           # decode horizons
                self.run([Request(rid=rid, prompt=[0],
                                  max_new_tokens=min(k + 1, self.max_len))])
                rid -= 1
        finally:
            if snap_index is not None:
                # release the temporary index's page pins, then restore the
                # live index untouched
                self.prefix_index.clear(pool)
                self.prefix_index = snap_index
            pool.cache = (snap_cache if pool.shardings is None
                          else jax.device_put(snap_cache, pool.shardings))
            pool._free, pool._allocated = snap_free, snap_alloc
            pool._pending_reset = snap_pending
            if pool.paged:
                pool._free_pages = snap_pages
                pool._page_ref = snap_ref
                pool._slot_pages = snap_slot_pages
                pool.cow_copies = snap_cow
            self.stats, self.clock = snap_stats, snap_clock
            self.results = snap_results
            self.straggler = snap_straggler
            self._on_token, self._on_result = snap_cbs
            self.scheduler.admitted_order.clear()
            self.scheduler.admitted_order.extend(snap_order)

    # ------------------------------------------------------------- metrics
    def mean_occupancy(self) -> float:
        steps = self.stats["engine_steps"]
        return self.stats["occupancy_sum"] / steps if steps else 0.0

    def syncs_per_token(self) -> float:
        gen = self.stats["generated_tokens"]
        return self.stats["host_syncs"] / gen if gen else 0.0
