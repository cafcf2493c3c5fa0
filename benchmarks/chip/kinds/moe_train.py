"""Expert-layer language-model training cells: the launcher's offloaded
train step on one chip's share of an expert-parallel deployment.

The configuration is a DeepSeek-V3 ``config.json`` (multi-head latent
attention, a leading dense layer, routed and shared experts) with
``experts_held``, the ``[first, count]`` of the routed experts this
chip holds.  The traffic keys are those of ``lm_train``.  Tokens are
drawn i.i.d. uniform over the configuration's (sliced) vocabulary from
the seed, so that routing load stays near its balanced expectation, as
the deployment's auxiliary-loss-free balancing keeps it.

Set-up, window and check are ``lm_train``'s (its ``Trainer``,
``Reference`` and ``gaps``): the step built by
``launch/train.build_train_step``, wrapped in ``offload`` with the
launcher's site-event hook, jitted with the state donated.  The
``work`` line adds ``int8_dots`` and ``grouped_rows`` (the rows the
grouped sites routed, summed over executions) beside ``site_exec``,
and ``model_flops`` comes from ``moe_counts``.
"""

from __future__ import annotations

import tempfile

import numpy as np

from kinds import lm_train


def token_batch(seed: int, step: int, batch: int, seq_len: int,
                vocab: int) -> np.ndarray:
    """(batch, seq_len + 1) int32 tokens of ``step``: i.i.d. uniform over
    the vocabulary, a function of the seed."""
    rng = np.random.default_rng([seed % (1 << 63), step, 1])
    return rng.integers(0, vocab, size=(batch, seq_len + 1),
                        dtype=np.int32)


def lm_config(cfg: dict, remat: bool = False):
    """The system's ``LMConfig`` of a DeepSeek-V3 ``config.json``."""
    from repro.configs import LMConfig

    heads = cfg["num_attention_heads"]
    return LMConfig(
        name=cfg.get("name", "bench"), vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=heads, num_kv_heads=heads, d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype="float32", param_dtype="float32",
        tie_embeddings=cfg["tie_word_embeddings"], remat=remat,
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        first_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["n_routed_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        shared_d_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        routed_scaling=cfg["routed_scaling_factor"],
        experts_held=tuple(cfg["experts_held"]))


class Program(lm_train.Program):
    """``lm_train``'s program with this configuration's block."""

    def __init__(self, cell, hook):
        import jax

        from repro.core import PrecisionPolicy, offload
        from repro.launch.train import build_train_step
        from repro.models import Model
        from repro.train import AdamW

        tr = cell.traffic
        self.opt = AdamW(**tr["optimizer"])
        step = build_train_step(
            Model(lm_config(cell.config, tr.get("remat", False))), self.opt)
        policy = PrecisionPolicy(backend=tr["backend"],
                                 default_splits=tr["splits"],
                                 min_dim=tr["min_dim"])
        self.fn = jax.jit(offload(step, policy, on_site_event=hook),
                          donate_argnums=(0, 1))
        self.precision = tr["matmul_precision"]


class Trainer(lm_train.Trainer):
    def batch(self, i):
        return token_batch(self.seed, i, *self.batch_args)


class Reference(lm_train.Reference):
    def readings(self, seed, n, precision="highest", keep=None):
        cell, ref = self.cell, self.ref
        tr = cell.traffic
        if (precision, keep) not in self._steps:
            self._steps[precision, keep] = ref.make_step(
                cell.config, tr["optimizer"], precision, keep)
        batches = [token_batch(seed, i, tr["batch"], tr["seq_len"],
                               cell.config["vocab_size"]) for i in range(n)]
        return ref.train_steps(self.init(ref.seed_words(seed)), batches,
                               self._steps[precision, keep])


def _counters(telemetry):
    """The site-event counters, and ``grouped_exec``: executions of
    grouped sites (their names carry a ``ragged`` component)."""
    out = {"site_exec": 0.0, "int8_dots": 0.0, "grouped_rows": 0.0,
           "grouped_exec": 0.0}
    for c in telemetry.registry.snapshot():
        if c["name"] in out:
            out[c["name"]] += c["value"]
        if c["name"] == "site_exec" and "ragged" in c["labels"]["site"]:
            out["grouped_exec"] += c["value"]
    return out


def run(cell):
    import jax

    jax.config.update("jax_enable_x64", False)
    import moe_counts
    from repro.obs import MetricsRun

    tr = cell.traffic
    n_check = tr["check_steps"]
    reference = Reference(cell)
    with tempfile.TemporaryDirectory() as tmp:
        telemetry = MetricsRun(tmp)
        program = Program(cell, telemetry.site_event_handler())
        norms, diff_norms = lm_train._norm_fns()
        run_ = Trainer(cell, program, reference, cell.seed)
        first = run_.first_steps(n_check, norms, diff_norms)
        jax.effects_barrier()
        before = _counters(telemetry)
        with cell.window() as expired:
            while True:
                run_.step()
                if expired():
                    break
        cell.read_memory_peak()
        jax.effects_barrier()
        after = _counters(telemetry)
        counted = {k: after[k] - before[k] for k in before}
        telemetry.close()
    steps = len(run_.losses) - n_check
    failed = int(sum(not np.isfinite(x) for x in run_.losses[n_check:]))
    run_.free()
    tokens = steps * tr["batch"] * tr["seq_len"]
    cell.work.update(
        steps=steps, tokens=tokens, **counted,
        model_flops=tokens * moe_counts.moe_train_flops_per_token(
            cell.config, tr["seq_len"]))
    numbers = lm_train.gaps(first, reference.readings(cell.seed, n_check))
    return {"end_to_end": {"train_tokens_per_s": tokens / cell.window_s},
            "attempted": steps, "failed": failed,
            "checks": {k: (v, cell.limits[k]) for k, v in numbers.items()}}


def readings(cell, seeds):
    """``lm_train.readings`` on this kind's program, tokens and
    reference: program, bf16_3x control and planted faults per seed."""
    import jax

    jax.config.update("jax_enable_x64", False)
    n = cell.traffic["check_steps"]
    ref = Reference(cell)
    program = Program(cell, None)
    norms, diff_norms = lm_train._norm_fns()
    rows = []
    for seed in seeds:
        trainer = Trainer(cell, program, ref, seed)
        first = trainer.first_steps(n, norms, diff_norms)
        trainer.free()
        reference = ref.readings(seed, n)
        control = ref.readings(seed, n, precision="bf16_3x")
        half = ref.readings(seed, n, keep=cell.traffic["seq_len"] // 2)
        losses = reference[0]
        frozen = (np.full_like(losses, losses[0]), np.zeros_like(reference[1]),
                  np.zeros_like(reference[2]))
        gaps = lm_train.gaps
        rows.append({"seed": seed,
                     "losses": {"program": first[0].tolist(),
                                "reference": losses.tolist(),
                                "control": control[0].tolist()},
                     "program": gaps(first, reference),
                     "control": gaps(control, reference),
                     "faults": {"half_batch": gaps(half, reference),
                                "state_unchanged": gaps(frozen, reference)}})
    return rows
