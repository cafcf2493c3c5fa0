"""Layered serve stack: paged == dense, chunked == unchunked, packing,
sampling validation, and restart determinism of the offloaded programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import LMConfig
from repro.core import PrecisionPolicy
from repro.models import Model
from repro.obs import MetricsRun, load_runs
from repro.obs.cli import main as obs_main
from repro.serve import (Engine, PagedKVCache, Request,
                         SamplingParamError, Scheduler)
from repro.shard import build_mesh

SMALL = LMConfig(name="test_paged", vocab_size=128, num_layers=1,
                 d_model=64, num_heads=2, num_kv_heads=1, head_dim=32,
                 d_ff=128)

# tp=2-shardable variant for the dp×tp test (kv heads must divide).
TP_CFG = LMConfig(name="test_paged_tp", vocab_size=128, num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                  d_ff=128, dtype="float64", param_dtype="float64")

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def model_params():
    model = Model(SMALL)
    params = model.init_params(jax.random.PRNGKey(0))
    params["lm_head"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(1), params["lm_head"].shape,
        dtype=jnp.float32)
    return model, params


def _prompts(lengths, seed=0, vocab=128):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, n)]
            for n in lengths]


def _reqs(prompts, max_new=6, **kw):
    return [Request(prompt=p, max_new_tokens=max_new, **kw)
            for p in prompts]


class TestPagedVsDense:
    RAGGED = [3, 17, 9, 31, 12, 24, 5, 16]

    def test_tokens_identical_single_device(self, model_params):
        """The tentpole bar: the paged block-table cache is an
        allocation change, not a numerics change — same greedy tokens
        as the dense rectangle for every ragged prompt."""
        model, params = model_params
        prompts = _prompts(self.RAGGED, seed=11)
        paged = Engine(model, params, batch_slots=4, max_len=64,
                       kv_layout="paged", block_size=16).run(
            _reqs(prompts))
        dense = Engine(model, params, batch_slots=4, max_len=64,
                       kv_layout="dense").run(_reqs(prompts))
        for p, d in zip(paged, dense):
            assert p.out == d.out

    def test_prefill_and_decode_bitwise(self, model_params):
        """Stronger than token identity: the paged programs' logits are
        *bit-identical* to the dense ones (the paged attention gather
        reconstructs the dense buffer layout exactly)."""
        model, params = model_params
        B, T, S, bs = 2, 16, 64, 16
        rng = np.random.default_rng(3)
        tokens = jnp.asarray(rng.integers(1, 128, (B, T)), jnp.int32)
        lengths = jnp.asarray([T, T - 5], jnp.int32)
        dense_cache, dense_logits = jax.jit(
            lambda p, t, n: model.prefill(p, t, n, S))(
            params, tokens, lengths)

        kv = PagedKVCache(model, batch_slots=B, max_len=S,
                          block_size=bs)
        for slot in range(B):
            kv.ensure(slot, int(lengths[slot]))
        cache = kv.sync_table(kv.init_cache())
        piece = lengths
        k, v, logits = jax.jit(model.prefill_chunk_paged)(
            params, cache["k"], cache["v"], cache["block_table"],
            tokens, jnp.zeros((B,), jnp.int32), piece)
        assert (np.asarray(logits) == np.asarray(dense_logits)).all()

        # Eight decode steps stay bitwise too.
        pcache = {"k": k, "v": v,
                  "block_table": cache["block_table"],
                  "length": lengths}
        dcache = dict(dense_cache, length=lengths)
        nxt_p = nxt_d = jnp.asarray(
            np.asarray(model.greedy(logits)), jnp.int32)
        active = jnp.ones((B,), bool)
        for _ in range(8):
            for slot in range(B):
                kv.ensure(slot, int(pcache["length"][slot]) + 1)
            pcache = kv.sync_table(pcache)
            pcache, lp = jax.jit(model.decode_step_paged)(
                params, pcache, nxt_p, active)
            dcache, ld = jax.jit(model.decode_step)(
                params, dcache, nxt_d, active)
            assert (np.asarray(lp) == np.asarray(ld)).all()
            nxt_p = jnp.asarray(np.asarray(model.greedy(lp)), jnp.int32)
            nxt_d = jnp.asarray(np.asarray(model.greedy(ld)), jnp.int32)

    @needs8
    def test_tokens_identical_dp_tp_mesh(self):
        """paged == dense == single-device under a 2-D dp=2×tp=2 mesh."""
        model = Model(TP_CFG)
        params = model.init_params(jax.random.PRNGKey(2))
        prompts = _prompts([3, 14, 7, 22, 11, 18, 5, 9], seed=21,
                           vocab=TP_CFG.vocab_size)
        ref = Engine(model, params, batch_slots=4, max_len=64,
                     kv_layout="dense").run(_reqs(prompts))
        mesh = build_mesh("dp=2,tp=2")
        for layout in ("paged", "dense"):
            got = Engine(model, params, batch_slots=4, max_len=64,
                         mesh=mesh, kv_layout=layout).run(
                _reqs(prompts))
            assert [r.out for r in ref] == [g.out for g in got], layout

    def test_paged_allocates_fewer_blocks(self, model_params):
        """Short prompts in a long-capacity engine must not pay the
        rectangle: the high-water block count stays strictly under the
        dense equivalent."""
        model, params = model_params
        eng = Engine(model, params, batch_slots=4, max_len=64,
                     kv_layout="paged", block_size=16)
        eng.run(_reqs(_prompts([4, 6, 9, 11], seed=5), max_new=4))
        stats = eng.kv.stats()
        assert stats["allocated_hwm"] > 0
        assert stats["allocated_hwm"] < stats["dense_equivalent_blocks"]
        # All blocks returned at drain.
        assert stats["allocated_blocks"] == 0

    def test_block_size_must_divide_max_len(self, model_params):
        model, params = model_params
        with pytest.raises(ValueError, match="multiple of block_size"):
            Engine(model, params, batch_slots=1, max_len=60,
                   kv_layout="paged", block_size=16)


class TestChunkedPrefill:
    def test_chunked_tokens_match_unchunked(self, model_params):
        """Chunk width is invisible in the emitted tokens, both
        layouts (per-position prefill math never reduces over the
        chunk axis, so every chunking is bitwise the same)."""
        model, params = model_params
        prompts = _prompts([5, 19, 33, 12], seed=8)
        for layout in ("paged", "dense"):
            ref = Engine(model, params, batch_slots=2, max_len=64,
                         kv_layout=layout).run(_reqs(prompts))
            chunked = Engine(model, params, batch_slots=2, max_len=64,
                             kv_layout=layout, chunk_tokens=4,
                             chunk_token_budget=8).run(_reqs(prompts))
            for r, c in zip(ref, chunked):
                assert r.out == c.out, layout

    def test_chunked_prefill_bitwise(self, model_params):
        """Model-level: ingesting a prompt in 4-token dense chunks
        reproduces the one-shot prefill logits bit-for-bit."""
        model, params = model_params
        B, T, S = 1, 16, 64
        rng = np.random.default_rng(13)
        tokens = jnp.asarray(rng.integers(1, 128, (B, T)), jnp.int32)
        lengths = jnp.asarray([T], jnp.int32)
        _, ref_logits = jax.jit(
            lambda p, t, n: model.prefill(p, t, n, S))(
            params, tokens, lengths)
        cache = model.init_cache(B, S)
        k, v = cache["k"], cache["v"]
        logits = None
        for pos in range(0, T, 4):
            k, v, logits = jax.jit(model.prefill_chunk)(
                params, k, v, tokens[:, pos:pos + 4],
                jnp.asarray([pos], jnp.int32),
                jnp.asarray([4], jnp.int32))
        assert (np.asarray(logits) == np.asarray(ref_logits)).all()

    def test_packing_beats_pad_to_wave_max(self, model_params):
        """The packing satellite: budget-packed chunk waves compute
        fewer padded tokens (∝ prefill FLOPs) than the old scheme of
        padding every prompt in one wave to the wave max."""
        model, params = model_params
        lengths = [3, 30, 5, 28]
        eng = Engine(model, params, batch_slots=4, max_len=64,
                     chunk_tokens=8, chunk_token_budget=16)
        eng.run(_reqs(_prompts(lengths, seed=9), max_new=2))
        # Old engine: one wave, 4 rows, padded to round_up8(max) = 32.
        old_cost = 4 * 32
        assert eng.runner.real_tokens_total == sum(lengths)
        assert eng.runner.padded_tokens_total < old_cost
        assert eng.runner.waves_total > 1


class TestSampling:
    def test_named_validation_errors(self, model_params):
        model, params = model_params
        eng = Engine(model, params, batch_slots=1, max_len=64)
        cases = [
            dict(prompt=[1, 2], temperature=-0.5),
            dict(prompt=[1, 2], temperature=1.0, seed="abc"),
            dict(prompt=[1, 2], latency_target_s=0.0),
        ]
        for kw in cases:
            with pytest.raises(SamplingParamError):
                eng.run([Request(max_new_tokens=2, **kw)])
        # The named error still is a ValueError (old API contract).
        assert issubclass(SamplingParamError, ValueError)

    def test_temperature_zero_is_greedy(self, model_params):
        model, params = model_params
        prompts = _prompts([7, 9], seed=14)
        greedy = Engine(model, params, batch_slots=2, max_len=64).run(
            _reqs(prompts))
        explicit = Engine(model, params, batch_slots=2,
                          max_len=64).run(
            _reqs(prompts, temperature=0.0, seed=123))
        for g, e in zip(greedy, explicit):
            assert g.out == e.out

    def test_sampled_request_deterministic_across_batching(
            self, model_params):
        """temperature>0 draws come from a per-request stream seeded by
        (seed, emission index): batch neighbours cannot change them."""
        model, params = model_params
        prompt = _prompts([9], seed=15)[0]
        solo, = Engine(model, params, batch_slots=1, max_len=64).run(
            [Request(prompt=prompt, max_new_tokens=6, temperature=0.8,
                     seed=42)])
        noise = _prompts([5, 11, 7], seed=16)
        batched = Engine(model, params, batch_slots=4, max_len=64).run(
            [Request(prompt=prompt, max_new_tokens=6, temperature=0.8,
                     seed=42)] + _reqs(noise))
        assert batched[0].out == solo.out
        # Different seed, different draw (overwhelmingly likely).
        other, = Engine(model, params, batch_slots=1, max_len=64).run(
            [Request(prompt=prompt, max_new_tokens=6, temperature=0.8,
                     seed=43)])
        assert other.out != solo.out


class TestScheduler:
    def test_edf_orders_by_deadline(self):
        sched = Scheduler(max_len=64, policy="edf")
        slow = Request(prompt=[1], max_new_tokens=1)
        fast = Request(prompt=[2], max_new_tokens=1,
                       latency_target_s=0.01)
        mid = Request(prompt=[3], max_new_tokens=1,
                      latency_target_s=5.0)
        sched.submit([slow, mid, fast], now=100.0)
        placed = sched.admit([0, 1, 2], lambda s, r: True)
        assert [r for _, r in placed] == [fast, mid, slow]
        # Lowest free slot goes to the earliest deadline.
        assert placed[0][0] == 0

    def test_fifo_preserves_submission_order(self):
        sched = Scheduler(max_len=64, policy="fifo")
        reqs = [Request(prompt=[i], max_new_tokens=1,
                        latency_target_s=9.0 - i) for i in range(3)]
        sched.submit(reqs, now=1.0)
        placed = sched.admit([0, 1, 2], lambda s, r: True)
        assert [r for _, r in placed] == reqs

    def test_head_of_line_blocks(self):
        sched = Scheduler(max_len=64, policy="fifo")
        big = Request(prompt=[1], max_new_tokens=1)
        small = Request(prompt=[2], max_new_tokens=1)
        sched.submit([big, small], now=1.0)
        placed = sched.admit([0], lambda s, r: r is not big)
        assert placed == []  # small must not overtake big
        assert sched.pending == 2


class TestRestart:
    POLICY = PrecisionPolicy(default_splits=6, min_dim=32)

    def _engine(self, model, params, metrics=None):
        """One serve 'process': a fresh engine has fresh offload
        wrappers and transform caches, so it stands in for a restart."""
        return Engine(model, params, batch_slots=2, max_len=64,
                      policy=self.POLICY, metrics=metrics)

    def test_fresh_engines_take_identical_decisions(self, model_params):
        """A restarted server re-traces its programs: it must emit the
        same tokens and take the same per-site decisions (names,
        offload, splits), which plan keying relies on."""
        model, params = model_params
        prompts = _prompts([20, 24, 28], seed=17)
        runs = []
        for _ in range(2):
            eng = self._engine(model, params)
            out = [r.out for r in eng.run(_reqs(prompts, max_new=4))]
            sites = [(s.name, s.offloaded, s.splits)
                     for s in eng.runner.sites_for(2, 24)]
            runs.append((out, sites))
        (out1, sites1), (out2, sites2) = runs
        assert out2 == out1
        assert sites2 == sites1
        assert any(offloaded for _, offloaded, _ in sites1)

    def test_obs_check_passes_on_hook_counted_run(self, model_params,
                                                  tmp_path):
        """``obs report --check`` holds a serve run's offloaded sites to
        the executions its site-event hook counted."""
        model, params = model_params
        with MetricsRun(tmp_path) as run:
            eng = self._engine(model, params, metrics=run)
            eng.run(_reqs(_prompts([20, 24], seed=18), max_new=4))
        import io
        buf = io.StringIO()
        assert obs_main(["report", str(tmp_path), "--check"],
                        out=buf) == 0, buf.getvalue()
        assert "CHECK OK" in buf.getvalue()
        events = load_runs(tmp_path)[run.run_id]
        offloaded = {e["site"] for e in events
                     if e["type"] == "site_decl" and e["offloaded"]}
        execs = {e["labels"]["site"]: e["value"] for e in events
                 if e["type"] == "metric" and e["name"] == "site_exec"}
        assert offloaded
        assert all(execs.get(site, 0) >= 1 for site in offloaded)


class TestKVCacheManager:
    def test_reservation_prevents_decode_deadlock(self, model_params):
        model, _ = model_params
        kv = PagedKVCache(model, batch_slots=2, max_len=64,
                          block_size=16, num_blocks=4)
        assert kv.can_reserve(0, prompt_len=30, max_new=30)
        kv.reserve(0, 30, 30)  # books all 4 blocks
        assert not kv.can_reserve(1, prompt_len=4, max_new=4)
        kv.ensure(0, 60)
        kv.release(0)
        assert kv.can_reserve(1, prompt_len=4, max_new=4)

    def test_allocation_is_deterministic(self, model_params):
        model, _ = model_params
        def trace():
            kv = PagedKVCache(model, batch_slots=2, max_len=64,
                              block_size=16)
            kv.ensure(0, 20)
            kv.ensure(1, 40)
            kv.release(0)
            kv.ensure(1, 50)
            kv.ensure(0, 10)
            return kv._table.copy()
        assert (trace() == trace()).all()

    def test_oversized_reservation_is_named(self, model_params):
        model, _ = model_params
        kv = PagedKVCache(model, batch_slots=2, max_len=64,
                          block_size=16, num_blocks=2)
        with pytest.raises(ValueError, match="raise num_blocks"):
            kv.reserve(0, 40, 20)
