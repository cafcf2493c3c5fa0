"""Serve-engine tests: continuous batching equals sequential decoding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import LMConfig
from repro.core import PrecisionPolicy
from repro.models import Model
from repro.serve import Engine, Request

SMALL = LMConfig(name="test_serve", vocab_size=128, num_layers=1,
                 d_model=64, num_heads=2, num_kv_heads=1, head_dim=32,
                 d_ff=128)


@pytest.fixture(scope="module")
def model_params():
    model = Model(SMALL)
    params = model.init_params(jax.random.PRNGKey(0))
    params["lm_head"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(1), params["lm_head"].shape,
        dtype=jnp.float32)
    return model, params


def _prompts(lengths, seed=0, vocab=SMALL.vocab_size):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, n)] for n in lengths]


class TestEngine:
    def test_mixed_lengths_match_sequential(self, model_params):
        """The satellite criterion: mixed-length prompts in one batch
        produce the same greedy tokens as one-at-a-time decoding."""
        model, params = model_params
        prompts = _prompts([3, 7, 12, 16])
        batched = Engine(model, params, batch_slots=4, max_len=64).run(
            [Request(prompt=p, max_new_tokens=8) for p in prompts])
        for req, prompt in zip(batched, prompts):
            solo, = Engine(model, params, batch_slots=1,
                           max_len=64).run(
                [Request(prompt=prompt, max_new_tokens=8)])
            assert req.out == solo.out, prompt

    def test_queue_longer_than_slots(self, model_params):
        model, params = model_params
        prompts = _prompts([4, 5, 6, 7, 8], seed=1)
        reqs = [Request(prompt=p, max_new_tokens=5) for p in prompts]
        done = Engine(model, params, batch_slots=2, max_len=64).run(reqs)
        assert done is reqs  # returned in submission order
        assert all(r.done and len(r.out) == 5 for r in done)
        # continuous batching must still match sequential decoding
        for req, prompt in zip(done, prompts):
            solo, = Engine(model, params, batch_slots=1,
                           max_len=64).run(
                [Request(prompt=prompt, max_new_tokens=5)])
            assert req.out == solo.out

    def test_kept_logits_align_with_tokens(self, model_params):
        # Chunked prefill keeps some slots prefilling while others
        # decode: each kept row must belong to an emitted token.
        model, params = model_params
        prompts = _prompts([4, 9, 6, 11, 5], seed=3)
        reqs = [Request(prompt=p, max_new_tokens=6,
                        logits=[] if i % 2 == 0 else None)
                for i, p in enumerate(prompts)]
        Engine(model, params, batch_slots=2, max_len=64,
               chunk_tokens=3).run(reqs)
        for i, req in enumerate(reqs):
            if i % 2:
                assert req.logits is None
                continue
            assert len(req.logits) == len(req.out) == 6
            assert [int(np.argmax(row)) for row in req.logits] == req.out
            assert all(row.shape == (SMALL.vocab_size,)
                       and row.dtype == np.float32 for row in req.logits)

    def test_eos_evicts_early(self, model_params):
        model, params = model_params
        prompt = _prompts([6], seed=2)[0]
        free, = Engine(model, params, batch_slots=1, max_len=64).run(
            [Request(prompt=prompt, max_new_tokens=20)])
        eos = free.out[0]  # whatever greedy decoding emits first
        eos_model = Model(SMALL.replace(eos_id=eos))
        done, = Engine(eos_model, params, batch_slots=1,
                       max_len=64).run(
            [Request(prompt=prompt, max_new_tokens=20)])
        assert done.out == [eos]

    def test_rejects_oversized_request(self, model_params):
        model, params = model_params
        eng = Engine(model, params, batch_slots=1, max_len=16)
        with pytest.raises(ValueError, match="exceeds max_len"):
            eng.run([Request(prompt=_prompts([12], seed=3)[0],
                             max_new_tokens=8)])
        with pytest.raises(ValueError, match="empty prompt"):
            eng.run([Request(prompt=[], max_new_tokens=2)])
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.run([Request(prompt=[1, 2], max_new_tokens=0)])

    def test_plan_at_startup_matches_unplanned_tokens(self,
                                                      model_params):
        """The engine loads a (train-calibrated) precision plan at
        startup and serves through the offload transform in subset
        mode; at solved split counts the emulation error is far below
        greedy-argmax resolution, so the tokens match exactly."""
        from repro.tune import Calibrator, solve_plan

        model, params = model_params
        batch = jnp.asarray(np.random.default_rng(9).integers(
            1, SMALL.vocab_size, (2, 33)))
        pol = PrecisionPolicy(default_splits=6, min_dim=32)
        cal = Calibrator(model.loss, pol)
        cal.run(params, batch)
        plan = solve_plan(cal.result(), budget=1e-9)

        prompts = _prompts([5, 9, 16, 12], seed=6)
        reqs = lambda: [Request(prompt=p, max_new_tokens=6)  # noqa: E731
                        for p in prompts]
        planned = Engine(model, params, batch_slots=4, max_len=64,
                         plan=plan)
        # The plan actually reaches the transform: the prefill program
        # offloads its projection GEMMs under the plan's size gate.
        psites = planned.prefill_sites(rows=4, width=16)
        assert sum(s.offloaded for s in psites) > 0
        done_plan = planned.run(reqs())
        done_bare = Engine(model, params, batch_slots=4,
                           max_len=64).run(reqs())
        for rp, rb in zip(done_plan, done_bare):
            assert rp.out == rb.out

    def test_slot_reuse_is_clean(self, model_params):
        """A slot's stale cache from a previous occupant must not
        influence the next request (prefill resets length and data)."""
        model, params = model_params
        prompt = _prompts([9], seed=4)[0]
        eng = Engine(model, params, batch_slots=1, max_len=64)
        first, = eng.run([Request(prompt=_prompts([14], seed=5)[0],
                                  max_new_tokens=6)])
        second, = eng.run([Request(prompt=prompt, max_new_tokens=6)])
        solo, = Engine(model, params, batch_slots=1, max_len=64).run(
            [Request(prompt=prompt, max_new_tokens=6)])
        assert second.out == solo.out
