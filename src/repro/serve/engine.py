"""Continuous-batching inference engine (facade over the serve layers).

The engine wires four single-purpose layers together and drives the
serve loop; each layer is independently testable and none reaches into
another's state:

- :class:`repro.serve.scheduler.Scheduler` — request validation,
  queueing, slot assignment (FIFO default, optional EDF).
- :class:`repro.serve.kvcache.PagedKVCache` /
  :class:`~repro.serve.kvcache.DenseKVCache` — cache layout and block
  allocation, behind one manager API.
- :class:`repro.serve.runner.Runner` — the device programs: packed
  chunked-prefill waves interleaved with masked decode ticks.
- this facade — slot lifecycle, per-request telemetry, the public
  ``run()`` API (unchanged from the monolithic engine it replaced, and
  token-identical to it for greedy requests).

Numerics note: each slot's computation is independent of its batch
neighbours (attention is masked per slot, matmuls are batched but not
mixed), so a prompt decoded in a busy batch yields the same greedy
tokens as the same prompt decoded alone — the serve tests assert this.
The paged layout is additionally bit-identical to the dense rectangle
(its attention gathers reproduce the dense buffer layout exactly), so
the default ``kv_layout="paged"`` changes allocation, not tokens.

Tunable-precision serving: pass ``plan=`` (a
:class:`repro.tune.PrecisionPlan`) or ``policy=`` to run the prefill
and decode GEMMs through the automatic offload transform — the same
plan artifact the training loop consumes, applied in subset mode
because serving traces only the forward sites.

Multi-device serving: pass ``mesh=`` to shard the engine across the
slot (batch) axis — parameters replicated, the KV cache and every
prefill/decode batch partitioned over the data-parallel axis, so each
dp group owns ``batch_slots / dp`` slots.  A 2-D ``dp×tp`` mesh
additionally shards the *parameters* per the LM axis rules
(:mod:`repro.shard.rules`) and the KV cache (dense rectangle or paged
pool alike) over ``tp`` on its kv-head axis.  The paged pool's block
axis is partitioned over dp — each dp group owns a contiguous block
range, including its own trash block, so allocation never crosses
shards.
"""

from __future__ import annotations

import time
from typing import List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import PrecisionPolicy
from repro.models import Model
from repro.obs import MetricsServer, SLOTracker, get_logger
from repro.serve.kvcache import DenseKVCache, PagedKVCache
from repro.serve.runner import Runner
from repro.serve.scheduler import (Request, SamplingParamError,
                                   Scheduler)
from repro.shard import (TP_AXIS, data_parallel_sharding,
                         lm_param_specs, state_shardings, validate_tp)

__all__ = ["Engine", "Request", "SamplingParamError"]

log = get_logger("serve")


class Engine:
    """Continuous-batching engine (greedy by default, per-request
    temperature sampling on top).

    Args:
      model: the :class:`~repro.models.Model` (its config fixes the
        vocabulary and ``eos_id``).
      params: parameter pytree (trained or fresh).
      batch_slots: decode batch width = number of concurrent requests.
      max_len: KV-cache capacity per slot; a request finishes early if
        ``prompt + generated`` would outgrow it.
      mesh: optional :class:`jax.sharding.Mesh`; shards the slot axis
        over the data-parallel axis (``batch_slots`` must divide by
        the dp extent).  A 2-D ``dp×tp`` mesh also tp-shards the
        parameters and the KV cache's kv-head axis per the LM axis
        rules (``tp`` must divide ``num_kv_heads``).
      plan: optional :class:`repro.tune.PrecisionPlan` loaded at
        startup — the prefill and decode programs run through the
        automatic offload transform under the plan's policy, in subset
        mode (train-calibrated plans carry backward-pass sites that
        never appear here).
      policy: optional :class:`~repro.core.PrecisionPolicy` — same
        effect, explicit policy instead of a plan artifact (wins over
        ``plan`` for the transform configuration if both are given).
      metrics: optional :class:`repro.obs.MetricsRun` — per-request
        latency telemetry, queue-depth / block-utilization gauges,
        prefill/decode tracer spans, and (under a plan/policy) per-site
        GEMM execution counts, all streamed into the run's JSONL file.
      kv_layout: ``"paged"`` (default) or ``"dense"``.  Paged carves
        the cache into ``block_size``-token blocks allocated on demand
        through a per-slot block table; dense keeps the original
        per-slot ``max_len`` rectangle.  Both emit identical tokens.
      block_size: paged block granularity; ``max_len`` must divide by
        it.
      num_blocks: paged pool size in usable blocks (default: the dense
        equivalent, so admission never waits on blocks).  Smaller pools
        oversubscribe slots; admission then reserves worst-case growth
        so decoding requests cannot deadlock.
      chunk_tokens: prefill chunk length.  ``None`` (default) ingests
        each prompt in one piece (the pre-refactor behavior); set to
        e.g. 64 to interleave decode ticks into long-prompt ingestion.
      chunk_token_budget: cap on total real tokens per prefill wave
        (packing budget); ``None`` = unlimited.
      scheduler_policy: ``"fifo"`` (default, the pre-refactor order)
        or ``"edf"`` (earliest ``t_enqueue + latency_target_s`` first).
      metrics_port: start a live :class:`repro.obs.MetricsServer` on
        this port (0 = ephemeral; read it back from
        ``engine.metrics_server.port``) serving the run's registry at
        ``/metrics`` while the engine runs.  Requires ``metrics=``.
      slo_objective / slo_window_s: the serve SLO — per-request TTFT
        vs ``latency_target_s`` feeds a rolling burn-rate gauge
        (``slo_burn_rate``) via :class:`repro.obs.SLOTracker`; only
        active with ``metrics=``.
    """

    def __init__(self, model: Model, params, batch_slots: int = 4,
                 max_len: int = 512, mesh=None, plan=None,
                 policy: Optional[PrecisionPolicy] = None,
                 metrics=None, *, kv_layout: str = "paged",
                 block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 chunk_tokens: Optional[int] = None,
                 chunk_token_budget: Optional[int] = None,
                 scheduler_policy: str = "fifo",
                 metrics_port: Optional[int] = None,
                 slo_objective: float = 0.99,
                 slo_window_s: float = 60.0):
        if kv_layout not in ("paged", "dense"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}; "
                             "have ('paged', 'dense')")
        self.model = model
        self.metrics = metrics
        self.batch_slots = int(batch_slots)
        self.max_len = int(max_len)
        self.mesh = mesh
        self._dp_size = 1
        slot_sharding = kv_sharding = None
        if mesh is not None:
            shape = dict(mesh.shape)
            tp = shape.get(TP_AXIS, 1)
            dp_axis = next((a for a in mesh.axis_names
                            if a != TP_AXIS), mesh.axis_names[0])
            self._dp_size = shape[dp_axis]
            if self.batch_slots % self._dp_size:
                raise ValueError(
                    f"batch_slots={self.batch_slots} is not divisible "
                    f"by the data-parallel extent {dp_axis}="
                    f"{self._dp_size}")
            # The canonical placements come from repro.shard; only the
            # KV layout is serve-specific, and the paged pool reuses
            # the dense spec: dim 1 is blocks instead of slots (the
            # per-dp-group block ranges keep it evenly divisible) and
            # dim 2 is still kv-heads for tp.
            if tp > 1:
                validate_tp(model.cfg, tp)
                params = jax.device_put(
                    params,
                    state_shardings(mesh, lm_param_specs(model.cfg)))
                slot_sharding = NamedSharding(
                    mesh, PartitionSpec(dp_axis))
                kv_sharding = NamedSharding(
                    mesh, PartitionSpec(None, dp_axis, TP_AXIS))
            else:
                replicated, slot_sharding = \
                    data_parallel_sharding(mesh, dp_axis)
                kv_sharding = NamedSharding(
                    mesh, PartitionSpec(None, dp_axis))
                params = jax.device_put(params, replicated)
        self.params = params
        if policy is None and plan is not None:
            # Unmatched-site handling must be silent: a train-
            # calibrated plan legitimately carries backward-pass
            # entries that no serve program contains.
            policy = PrecisionPolicy.from_plan(
                plan, on_unmatched_site="ignore")
        self.plan = plan
        self.policy = policy

        registry = metrics.registry if metrics is not None else None
        if kv_layout == "paged":
            self.kv = PagedKVCache(
                model, self.batch_slots, self.max_len,
                block_size=block_size, num_blocks=num_blocks,
                dp_groups=self._dp_size, registry=registry)
        else:
            self.kv = DenseKVCache(model, self.batch_slots,
                                   self.max_len, registry=registry)
        self.runner = Runner(
            model, params, self.kv, max_len=self.max_len, mesh=mesh,
            dp_size=self._dp_size, slot_sharding=slot_sharding,
            kv_sharding=kv_sharding, policy=policy, plan=plan,
            metrics=metrics, chunk_tokens=chunk_tokens,
            chunk_token_budget=chunk_token_budget)
        self.slo = None
        if metrics is not None:
            self.slo = SLOTracker(registry=metrics.registry,
                                  objective=slo_objective,
                                  window_s=slo_window_s,
                                  sink=metrics.sink)
        self.scheduler = Scheduler(self.max_len,
                                   policy=scheduler_policy,
                                   metrics=metrics, slo=self.slo)
        self.metrics_server = None
        if metrics_port is not None:
            if metrics is None:
                raise ValueError("metrics_port requires metrics= (the "
                                 "server exposes that run's registry)")
            self.metrics_server = MetricsServer(
                metrics.registry, port=metrics_port,
                runs_dir=metrics.directory).start()
        self.slots: List[Optional[Request]] = [None] * self.batch_slots
        self._next_token = np.zeros(self.batch_slots, np.int32)
        # Per-request latency bookkeeping, keyed by request identity
        # (Request is a plain mutable dataclass, not hashable by value).
        self._rstats: dict = {}

    # -- introspection -----------------------------------------------

    @property
    def cache(self) -> dict:
        """The live KV-cache pytree (owned by the runner)."""
        return self.runner.cache

    def prefill_sites(self, rows: int, width: int):
        """Site decisions of the prefill program for a wave of shape
        ``(rows, width)`` — what the offload transform would do, without
        executing anything.  Empty without a policy/plan."""
        return self.runner.sites_for(rows, width)

    # -- lifecycle ---------------------------------------------------

    def _admit(self) -> None:
        free = [i for i, r in enumerate(self.slots) if r is None]
        if not free or not self.scheduler.pending:
            return
        placed = self.scheduler.admit(
            free, lambda slot, req: self.kv.can_reserve(
                slot, len(req.prompt), req.max_new_tokens))
        if not placed and not any(r is not None for r in self.slots):
            # Idle engine, head of queue still unplaceable: its worst
            # case exceeds what an *empty* pool can book — waiting
            # cannot fix that.
            raise RuntimeError(
                "request can never be admitted: its worst-case cache "
                f"(prompt + max_new_tokens) outgrows the configured "
                f"pool ({self.kv.stats()}) — raise num_blocks")
        for slot, req in placed:
            self.kv.reserve(slot, len(req.prompt), req.max_new_tokens)
            self.slots[slot] = req
            self.runner.enqueue_prefill(slot, req)

    def _prefill_tick(self) -> None:
        res = self.runner.prefill_wave()
        if res is None:
            return
        if self.metrics is not None:
            log.debug(f"prefill wave: {len(res.pieces)} chunks, "
                      f"{res.real_tokens} tokens "
                      f"(padded {res.rows}x{res.width}) in "
                      f"{res.duration_s * 1e3:.1f} ms")
            t_wave = time.perf_counter() - res.duration_s
            for _, req, _ in res.pieces:
                st = self._rstats.get(id(req))
                if st is None:
                    continue
                if "t_admit" not in st:
                    # First chunk of this request to reach a device.
                    st["t_admit"] = t_wave
                    st["admission_wait_s"] = t_wave - st["t_enqueue"]
                    self.metrics.registry.histogram(
                        "serve_admission_wait_s").observe(
                        st["admission_wait_s"])
                st["prefill_s"] = (st.get("prefill_s", 0.0)
                                   + res.duration_s)
        for slot, req, token in res.completed:
            st = self._rstats.get(id(req))
            if st is not None:
                self.metrics.registry.histogram(
                    "serve_prefill_s").observe(st["prefill_s"])
            self._emit(slot, req, token)

    def _decode_tick(self) -> None:
        active = np.array([
            req is not None and not self.runner.is_prefilling(slot)
            for slot, req in enumerate(self.slots)])
        if not active.any():
            return
        if self.metrics is not None:
            self.metrics.registry.gauge("serve_slot_occupancy").set(
                int(active.sum()))
            for slot in np.flatnonzero(active):
                st = self._rstats.get(id(self.slots[slot]))
                if st is not None:
                    st["decode_ticks"] = st.get("decode_ticks", 0) + 1
        nxt = self.runner.decode_tick(self._next_token, active,
                                      self.slots)
        for slot in np.flatnonzero(active):
            self._emit(int(slot), self.slots[slot], int(nxt[slot]))

    def _emit(self, slot: int, req: Request, token: int) -> None:
        req.out.append(token)
        st = self._rstats.get(id(req))
        if st is not None and "ttft_s" not in st:
            # First emitted token (from the final prefill chunk).
            st["ttft_s"] = time.perf_counter() - st["t_enqueue"]
            self.metrics.registry.histogram(
                "serve_ttft_s").observe(st["ttft_s"])
            if req.latency_target_s is not None:
                slack = req.latency_target_s - st["ttft_s"]
                self.metrics.registry.histogram(
                    "serve_latency_slack_s").observe(slack)
                if slack < 0:
                    self.metrics.registry.counter(
                        "serve_latency_miss").inc()
            if self.slo is not None:
                self.slo.observe(st["ttft_s"], req.latency_target_s)
        self._next_token[slot] = token
        eos = self.model.cfg.eos_id
        length_next = len(req.prompt) + len(req.out)
        if (len(req.out) >= req.max_new_tokens
                or (eos is not None and token == eos)
                or length_next >= self.max_len):
            req.done = True
            self.slots[slot] = None
            self.kv.release(slot)
            self.scheduler.forget(req)
            if st is not None:
                self._finish(req, st)

    def _finish(self, req: Request, st: dict) -> None:
        """Finalize one request's telemetry: the ``request`` event."""
        gen_s = time.perf_counter() - st.get("t_admit",
                                             st["t_enqueue"])
        tokens_per_s = len(req.out) / max(gen_s, 1e-9)
        self.metrics.registry.counter("serve_tokens").inc(len(req.out))
        self.metrics.event(
            "request", prompt_len=len(req.prompt),
            new_tokens=len(req.out),
            admission_wait_s=st.get("admission_wait_s"),
            prefill_s=st.get("prefill_s"), ttft_s=st.get("ttft_s"),
            decode_ticks=st.get("decode_ticks", 0),
            tokens_per_s=tokens_per_s,
            latency_target_s=req.latency_target_s)
        log.debug(f"request done: {len(req.prompt)} prompt + "
                  f"{len(req.out)} new tokens, "
                  f"ttft {st.get('ttft_s', 0) * 1e3:.1f} ms, "
                  f"{tokens_per_s:.1f} tok/s")
        self._rstats.pop(id(req), None)

    # -- public API --------------------------------------------------

    def run(self, requests: List[Request]) -> List[Request]:
        """Drive all ``requests`` to completion; returns them in order.

        Requests are validated up front (:class:`SamplingParamError`,
        a ``ValueError``); admission follows the scheduler policy, and
        more requests than slots simply queue and are admitted as
        earlier ones finish.
        """
        self.scheduler.submit(requests)
        if self.metrics is not None:
            for req in requests:
                self._rstats[id(req)] = {
                    "t_enqueue": self.scheduler.t_enqueue(req)}
        while (self.scheduler.pending or
               any(r is not None for r in self.slots)):
            self._admit()
            self._prefill_tick()
            self._decode_tick()
        if self.metrics is not None:
            self.metrics.registry.gauge("serve_slot_occupancy").set(0)
            # Site-event callbacks (plan/policy runs) are async; drain
            # them so execution counters are complete at flush time.
            jax.effects_barrier()
        return requests

    def close(self) -> None:
        """Stop the live metrics server, if one was started."""
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None
