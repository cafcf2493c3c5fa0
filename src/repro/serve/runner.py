"""Batched execution: chunked prefill waves interleaved with decode ticks.

The runner owns the device side of serving — the (possibly
offload-transformed) prefill-chunk and decode programs, the KV cache
pytree, and the host mirror of per-slot lengths.  It knows nothing
about queues or request lifecycles; the engine hands it admitted
requests and asks for one prefill wave or one decode tick at a time.

Chunked prefill
---------------
Prompts are ingested in *pieces* of at most ``chunk_tokens``, packed
FIFO into waves of at most ``chunk_token_budget`` total tokens — so a
4k-token prompt costs several short waves with decode ticks in
between instead of one monolithic stall.  A wave's width is the
largest piece in it (no power-of-two rounding: right-padding is pure
waste, and the packing satellite asserts we emit fewer padded tokens
than the pad-to-wave-max scheme).  Pieces whose slot rectangle cannot
absorb the wave width stop the wave early (head-of-line, order
preserved) — only relevant for the dense layout, whose chunk padding
is written in-rectangle; the paged layout routes padding to the trash
block.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import offload
from repro.models import Model

__all__ = ["Runner", "WaveResult"]


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


#: Shared no-op context for the metrics-off path (contextlib.
#: nullcontext allocates per use; the engine ticks in a hot loop).
_NULL_SPAN = _NullSpan()


def _round_up(n: int, mult: int = 8) -> int:
    return ((n + mult - 1) // mult) * mult


class _Prefill:
    """One slot's in-flight prompt ingestion."""

    __slots__ = ("req", "tokens", "pos")

    def __init__(self, req):
        self.req = req
        self.tokens = np.asarray(req.prompt, np.int32)
        self.pos = 0

    @property
    def remaining(self) -> int:
        return len(self.tokens) - self.pos


@dataclasses.dataclass
class WaveResult:
    """What one prefill wave did (the engine's telemetry input)."""

    pieces: list          # (slot, req, take) in wave-row order
    completed: list       # (slot, req, sampled first token)
    rows: int             # device rows incl. dp padding
    width: int            # wave width (largest piece)
    padded_tokens: int    # rows * width actually computed
    real_tokens: int      # sum of piece lengths
    duration_s: float


class Runner:
    """Executes prefill waves and decode ticks over one KV cache."""

    def __init__(self, model: Model, params, kv, *, max_len: int,
                 mesh=None, dp_size: int = 1, slot_sharding=None,
                 kv_sharding=None, policy=None, plan=None,
                 metrics=None, chunk_tokens: Optional[int] = None,
                 chunk_token_budget: Optional[int] = None):
        self.model = model
        self.params = params
        self.kv = kv
        self.max_len = int(max_len)
        self.mesh = mesh
        self._dp_size = int(dp_size)
        self._slot_sharding = slot_sharding
        self._kv_sharding = kv_sharding
        self.policy = policy
        self.plan = plan
        self.metrics = metrics
        self.layout = kv.stats()["layout"]
        self.chunk_tokens = (int(chunk_tokens) if chunk_tokens
                             else self.max_len)
        self.chunk_token_budget = (int(chunk_token_budget)
                                   if chunk_token_budget else None)
        self.batch_slots = kv.batch_slots
        self._declared = False

        if self.layout == "paged":
            prefill_fn = model.prefill_chunk_paged
            decode_fn = model.decode_step_paged
        else:
            prefill_fn = model.prefill_chunk
            decode_fn = model.decode_step
        self._prefill_wrapped, self._prefill_call = self._wrap(prefill_fn)
        self._decode_wrapped, self._decode_call = self._wrap(decode_fn)

        self.cache = self._pin(kv.init_cache())
        self._len = np.zeros(self.batch_slots, np.int64)
        self._pending: dict = {}      # slot -> _Prefill (admission order)
        # Lifetime totals (prefill cost accounting: computed prefill
        # FLOPs scale with padded tokens, useful ones with real).
        self.waves_total = 0
        self.padded_tokens_total = 0
        self.real_tokens_total = 0

    # -- program wiring ----------------------------------------------

    def _wrap(self, fn):
        """(inspectable wrapper, callable) for one serve program."""
        if self.policy is None:
            return None, jax.jit(fn)
        hook = (self.metrics.site_event_handler()
                if self.metrics is not None else None)
        wrapped = offload(fn, self.policy, plan=self.plan,
                          plan_match="subset", on_site_event=hook)
        return wrapped, jax.jit(wrapped)

    def _pin(self, cache: dict) -> dict:
        """Re-assert slot/kv shardings on the cache pytree (no-op
        off-mesh, no-copy when the layout already matches)."""
        if self.mesh is None:
            return cache
        out = {"k": jax.device_put(cache["k"], self._kv_sharding),
               "v": jax.device_put(cache["v"], self._kv_sharding),
               "length": jax.device_put(cache["length"],
                                        self._slot_sharding)}
        if "block_table" in cache:
            out["block_table"] = jax.device_put(cache["block_table"],
                                                self._slot_sharding)
        return out

    def _shard(self, *arrays):
        if self.mesh is None:
            return arrays
        return tuple(jax.device_put(a, self._slot_sharding)
                     for a in arrays)

    def _span(self, name, **kw):
        if self.metrics is None:
            return _NULL_SPAN
        return self.metrics.tracer.span(name, **kw)

    # -- site telemetry ----------------------------------------------

    def _declare_once(self, args) -> None:
        if (self.metrics is None or self._prefill_wrapped is None
                or self._declared):
            return
        # First wave: record the site decisions (same records
        # ``site_report`` would produce) so ``repro.obs report --check``
        # can hold execution counts against them.  Warms the exact
        # transform-cache entry the call below hits.
        self.metrics.declare_sites(self._prefill_wrapped.sites(*args))
        self._declared = True

    def sites_for(self, rows: int, width: int):
        """Site decisions of the prefill-chunk program for a wave shape
        (introspection; does not execute anything)."""
        if self._prefill_wrapped is None:
            return []
        return self._prefill_wrapped.sites(
            *self._abstract_wave_args(rows, width))

    def _abstract_wave_args(self, rows: int, width: int):
        sds = jax.ShapeDtypeStruct
        i32 = jnp.int32
        spec = jax.tree_util.tree_map(
            lambda a: sds(jnp.shape(a), jnp.result_type(a)),
            self.params)
        tokens = sds((rows, width), i32)
        vec = sds((rows,), i32)
        if self.layout == "paged":
            k = sds(self.cache["k"].shape, self.cache["k"].dtype)
            table = sds((rows, self.kv.blocks_per_slot + 1), i32)
            return (spec, k, k, table, tokens, vec, vec)
        cfg = self.model.cfg
        sub = sds((cfg.num_layers, rows, cfg.num_kv_heads,
                   self.max_len, cfg.head_dim), self.model.dtype)
        return (spec, sub, sub, tokens, vec, vec)

    # -- sampling ----------------------------------------------------

    def _sample(self, logits_dev, reqs: List) -> np.ndarray:
        """Greedy on device; temperature>0 rows re-sampled host-side
        from a per-request deterministic stream (seeded by the request
        seed and the emission index, so batching never changes a
        sampled request's tokens)."""
        toks = np.array(self.model.greedy(logits_dev))  # writable copy
        keep = [i for i, r in enumerate(reqs)
                if r is not None and r.logits is not None]
        if keep:
            lg = np.asarray(logits_dev)
            for i in keep:
                reqs[i].logits.append(lg[i].copy())
        hot = [i for i, r in enumerate(reqs)
               if r is not None and r.temperature > 0]
        if hot:
            lg = np.asarray(logits_dev).astype(np.float64)
            for i in hot:
                r = reqs[i]
                z = lg[i] / r.temperature
                z -= z.max()
                p = np.exp(z)
                p /= p.sum()
                rng = np.random.default_rng(
                    [r.seed & 0xFFFFFFFF, len(r.out)])
                toks[i] = rng.choice(p.size, p=p)
        return toks

    # -- prefill -----------------------------------------------------

    def enqueue_prefill(self, slot: int, req) -> None:
        self._pending[slot] = _Prefill(req)

    def is_prefilling(self, slot: int) -> bool:
        return slot in self._pending

    @property
    def prefilling(self) -> bool:
        return bool(self._pending)

    def _pack(self) -> List[tuple]:
        """Pick this wave's pieces: FIFO, chunk-capped, budget-capped.

        The wave width is the largest accepted piece; a piece is only
        accepted if every already-accepted piece's rectangle can absorb
        that width (``pos + width <= max_len``) — a solo piece always
        fits (``pos + take <= prompt_len <= max_len``), so the wave is
        never empty and head-of-line order holds.
        """
        budget = self.chunk_token_budget or float("inf")
        pieces, width = [], 0
        for slot, st in self._pending.items():
            if budget <= 0:
                break
            take = int(min(self.chunk_tokens, st.remaining, budget))
            if take <= 0:
                break
            new_width = max(width, take)
            ok = all(p.pos + new_width <= self.max_len
                     for _, p, _ in pieces + [(slot, st, take)])
            if not ok:
                break
            pieces.append((slot, st, take))
            width = new_width
            budget -= take
        return pieces

    def prefill_wave(self) -> Optional[WaveResult]:
        """Run one packed prefill wave; returns None when idle."""
        if not self._pending:
            return None
        pieces = self._pack()
        t0 = time.perf_counter()
        width = max(take for _, _, take in pieces)
        n = len(pieces)
        rows = (n if self.mesh is None
                else _round_up(n, self._dp_size))
        tokens = np.zeros((rows, width), np.int32)
        start = np.zeros((rows,), np.int32)
        piece = np.ones((rows,), np.int32)
        for i, (slot, st, take) in enumerate(pieces):
            tokens[i, :take] = st.tokens[st.pos:st.pos + take]
            start[i] = st.pos
            piece[i] = take
        if self.layout == "paged":
            # Dummy rows: no writes at all (their reads hit trash).
            piece[n:] = 0
        span = self._span("prefill", rows=rows, padded_len=width,
                          chunks=n)
        with span:
            if self.layout == "paged":
                logits = self._wave_paged(pieces, tokens, start, piece,
                                          rows, n)
            else:
                logits = self._wave_dense(pieces, tokens, start, piece,
                                          rows, n)
            # Scatter the new per-slot lengths (host-known): decoding
            # neighbours keep theirs, wave slots move to their chunk
            # end — which also parks the dense layout's masked decode
            # writes at a position the next chunk overwrites first.
            ends = np.array([st.pos + take for _, st, take in pieces],
                            np.int32)
            jslots = jnp.asarray(
                np.array([s for s, _, _ in pieces]))
            self.cache = self._pin(dict(
                self.cache,
                length=self.cache["length"].at[jslots].set(
                    jnp.asarray(ends))))
            completed = []
            done_rows = []
            reqs_rows = [None] * n
            for i, (slot, st, take) in enumerate(pieces):
                self._len[slot] = st.pos + take
                st.pos += take
                if st.remaining == 0:
                    del self._pending[slot]
                    done_rows.append(i)
                    reqs_rows[i] = st.req
            # np.asarray inside _sample blocks on the device work, so
            # the span (and prefill_s) covers the wave, not dispatch.
            toks = self._sample(logits[:n], reqs_rows)
            for i in done_rows:
                slot, st, _ = pieces[i]
                completed.append((slot, st.req, int(toks[i])))
        self.waves_total += 1
        self.padded_tokens_total += rows * width
        self.real_tokens_total += int(sum(t for _, _, t in pieces))
        return WaveResult(
            pieces=[(s, st.req, t) for s, st, t in pieces],
            completed=completed, rows=rows, width=width,
            padded_tokens=rows * width,
            real_tokens=int(sum(t for _, _, t in pieces)),
            duration_s=time.perf_counter() - t0)

    def _wave_paged(self, pieces, tokens, start, piece, rows, n):
        for slot, st, take in pieces:
            self.kv.ensure(slot, st.pos + take)
        self.cache = self.kv.sync_table(self.cache)
        table = np.empty((rows, self.kv.blocks_per_slot + 1), np.int32)
        for i, (slot, _, _) in enumerate(pieces):
            table[i] = self.kv._table[slot]
        for i in range(n, rows):
            g = 0 if self.mesh is None else i // (rows // self._dp_size)
            table[i] = self.kv._trash[g]
        tok_d, start_d, piece_d, table_d = self._shard(
            jnp.asarray(tokens), jnp.asarray(start),
            jnp.asarray(piece), jnp.asarray(table))
        args = (self.params, self.cache["k"], self.cache["v"],
                table_d, tok_d, start_d, piece_d)
        self._declare_once(args)
        k_new, v_new, logits = self._prefill_call(*args)
        self.cache = self._pin(dict(self.cache, k=k_new, v=v_new))
        return logits

    def _wave_dense(self, pieces, tokens, start, piece, rows, n):
        slots = np.array([s for s, _, _ in pieces])
        jidx = jnp.asarray(np.concatenate(
            [slots, np.zeros(rows - n, np.int64)]))
        sub_k = self.cache["k"][:, jidx]
        sub_v = self.cache["v"][:, jidx]
        tok_d, start_d, piece_d = self._shard(
            jnp.asarray(tokens), jnp.asarray(start),
            jnp.asarray(piece))
        args = (self.params, sub_k, sub_v, tok_d, start_d, piece_d)
        self._declare_once(args)
        k_new, v_new, logits = self._prefill_call(*args)
        jreal = jnp.asarray(slots)
        self.cache = self._pin(dict(
            self.cache,
            k=self.cache["k"].at[:, jreal].set(k_new[:, :n]),
            v=self.cache["v"].at[:, jreal].set(v_new[:, :n])))
        return logits

    # -- decode ------------------------------------------------------

    def decode_tick(self, next_token: np.ndarray, active: np.ndarray,
                    reqs: List) -> np.ndarray:
        """One masked decode step across all slots; returns sampled
        tokens for the active ones (others carry garbage)."""
        reqs = [r if a else None for r, a in zip(reqs, active)]
        if self.layout == "paged":
            for slot in np.flatnonzero(active):
                self.kv.ensure(int(slot), int(self._len[slot]) + 1)
            self.cache = self.kv.sync_table(self.cache)
        tokens, act = self._shard(jnp.asarray(next_token),
                                  jnp.asarray(active))
        span = self._span("decode_tick", active=int(active.sum()))
        with span:
            args = (self.params, self.cache, tokens, act)
            cache, logits = self._decode_call(*args)
            self.cache = self._pin(cache)
            # Blocks, so the span covers the device step.
            toks = self._sample(logits, reqs)
        self._len[active] += 1
        return toks
