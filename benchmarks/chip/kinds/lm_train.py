"""Language-model training cells: the launcher's offloaded train step.

Traffic keys: ``backend``, ``splits``, ``min_dim`` (the offload
policy), ``matmul_precision`` (of the GEMMs left native), ``batch`` and
``seq_len`` (one micro-batch), ``remat`` (each layer recomputed in the
backward pass, the model's own ``LMConfig.remat``), ``optimizer``
(AdamW's hyperparameters) and ``check_steps`` (steps the reference
follows).  The configuration is
a Hugging Face ``config.json`` of a Llama-style model.

Set-up builds the step as ``launch/train.py`` does (``build_train_step``,
then ``offload`` with the launcher's ``site_exec`` counter as its site
hook, then ``jax.jit`` with the state donated), makes the weights from
the seed on the device, and drives that one compiled step through its
first ``check_steps`` steps with the window's own call and feed.  The
window runs further steps, each on a new batch, fetching each loss as
the launcher's loop does.  After the window the program's state is
freed and the plain reference follows the first steps from the same
weights and batches.
"""

from __future__ import annotations

import tempfile

import numpy as np

#: Anchor token, walk steps and their odds of the synthetic stream.
_DELTAS = np.array([1, 2, 3, 5, 8], dtype=np.int64)
_PROBS = np.array([0.40, 0.30, 0.15, 0.10, 0.05])
_ANCHOR_P = 0.25


def token_batch(seed: int, step: int, batch: int, seq_len: int,
                vocab: int) -> np.ndarray:
    """(batch, seq_len + 1) int32 tokens of ``step``, a function of the seed.

    A random walk over the vocabulary with skewed steps, each position
    replaced by token 0 with probability 1/4: the synthetic stream of
    ``repro.train.SyntheticText``, kept here so that the traffic is the
    benchmark's own.
    """
    rng = np.random.default_rng([seed % (1 << 63), step])
    start = rng.integers(0, vocab, size=(batch, 1))
    deltas = rng.choice(_DELTAS, size=(batch, seq_len), p=_PROBS)
    walk = np.concatenate([start, deltas], axis=1).cumsum(axis=1) % vocab
    return np.where(rng.random(walk.shape) < _ANCHOR_P, 0,
                    walk).astype(np.int32)


def lm_config(cfg: dict, remat: bool = False):
    from repro.configs import LMConfig

    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return LMConfig(remat=remat,
        name=cfg.get("name", "bench"), vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], d_model=d, num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", d // heads),
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype="float32", param_dtype="float32",
        tie_embeddings=cfg["tie_word_embeddings"])


class Program:
    """The system under test: one compiled, offloaded, donated train step."""

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

    def __call__(self, params, state, batch):
        import jax

        with jax.default_matmul_precision(self.precision):
            return self.fn(params, state, batch)


def _norm_fns():
    import jax
    import jax.numpy as jnp

    def norms(tree):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                          for x in jax.tree_util.tree_leaves(tree)])

    return (jax.jit(norms),
            jax.jit(lambda a, b: norms(jax.tree_util.tree_map(
                jnp.subtract, a, b))))


class Trainer:
    """One seed's run: weights, feed, and the first steps' readings."""

    def __init__(self, cell, program, reference, seed):
        import jax

        tr = cell.traffic
        self.program, self.seed = program, seed
        self.init = reference.init
        self.batch_args = (tr["batch"], tr["seq_len"],
                           cell.config["vocab_size"])
        self.words = reference.ref.seed_words(seed)
        self.params = self.init(self.words)
        self.state = jax.jit(program.opt.init)(self.params)
        self.next_step = 0
        self.losses = []

    def batch(self, i):
        return token_batch(self.seed, i, *self.batch_args)

    def step(self):
        import jax
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("train.data"):
            batch = jax.device_put(self.batch(self.next_step))
        with TraceAnnotation("train.step"):
            self.params, self.state, loss = self.program(
                self.params, self.state, batch)
        with TraceAnnotation("train.loss"):
            self.losses.append(float(loss))
        self.next_step += 1

    def first_steps(self, n, norms, diff_norms):
        """Steps 1..n; the first gradient from AdamW's first moment
        (mu = (1 - b1) g after one step), and each leaf's change."""
        for _ in range(n):
            self.step()
            if self.next_step == 1:
                grad = np.asarray(norms(self.state["mu"])) / (
                    1.0 - self.program.opt.b1)
        p0 = self.init(self.words)
        change = np.asarray(diff_norms(self.params, p0))
        del p0
        return np.asarray(self.losses[:n]), grad, change

    def free(self):
        self.params = self.state = None


class Reference:
    """The plain reference of the cell's configuration, compiled once."""

    def __init__(self, cell):
        import jax

        self.cell = cell
        self.ref = cell.reference()
        self.init = jax.jit(lambda w: self.ref.init_params(cell.config, w))
        self._steps = {}

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


def gaps(program, reference):
    """The numbers compared, ``program`` and ``reference`` being
    (losses, first-gradient leaf norms, leaf-change norms).

    ``loss1_rel``: the first step's relative loss gap.  (The later
    steps' losses are not compared: from the second step on, AdamW's
    normalized update turns rounding in near-zero gradient entries into
    sign flips, and a sound program's gap swings over seeds by as much
    as the control's.)
    ``grad_norm_gap`` and ``update_norm_gap``: the worst leaf's gap
    between the two norms, over that leaf's reference norm or the median
    leaf's, whichever is larger.  A leaf whose reference gradient is
    under a thousandth of the median leaf's is left out of the change:
    Adam moves it by round-off alone.
    """
    (l_p, g_p, d_p), (l_r, g_r, d_r) = program, reference
    loss1 = float(abs(l_p[0] - l_r[0]) / abs(l_r[0]))
    g_scale = np.maximum(g_r, np.median(g_r))
    grad = float(np.max(np.abs(g_p - g_r) / g_scale))
    moved = g_r >= 1e-3 * np.median(g_r)
    d_scale = np.maximum(d_r, np.median(d_r[moved]))
    update = float(np.max((np.abs(d_p - d_r) / d_scale)[moved]))
    return {"loss1_rel": loss1, "grad_norm_gap": grad,
            "update_norm_gap": update}


def run(cell):
    import jax

    jax.config.update("jax_enable_x64", False)
    import counts
    from repro.obs import MetricsRun

    tr = cell.traffic
    n_check = tr["check_steps"]
    reference = Reference(cell)
    with tempfile.TemporaryDirectory() as tmp:
        telemetry = MetricsRun(tmp)
        program = Program(cell, telemetry.site_event_handler())
        norms, diff_norms = _norm_fns()
        run_ = Trainer(cell, program, reference, cell.seed)
        first = run_.first_steps(n_check, norms, diff_norms)
        with cell.window() as expired:
            while True:
                run_.step()
                if expired():
                    break
        cell.read_memory_peak()
        jax.effects_barrier()
        site_exec = sum(c["value"] for c in telemetry.registry.snapshot()
                        if c["name"] == "site_exec")
        telemetry.close()
    steps = len(run_.losses) - n_check
    failed = int(sum(not np.isfinite(x) for x in run_.losses[n_check:]))
    run_.free()
    tokens = steps * tr["batch"] * tr["seq_len"]
    cell.work.update(
        steps=steps, tokens=tokens, site_exec=site_exec,
        model_flops=tokens * counts.lm_train_flops_per_token(
            cell.config, tr["seq_len"]))
    numbers = gaps(first, reference.readings(cell.seed, n_check))
    return {"end_to_end": {"train_tokens_per_s": tokens / cell.window_s},
            "attempted": steps, "failed": failed,
            "checks": {k: (v, cell.limits[k]) for k, v in numbers.items()}}


def readings(cell, seeds):
    """Program, control and fault readings of each seed, in one process.

    The control is the reference at ``bf16_3x`` (every product from
    three bf16 passes), the precision below the configuration's float32
    at ``highest``.  Faults,
    planted in the reference put in the program's place: half of the
    batch's positions left out of the mean; a step that returns its
    state unchanged (its readings follow from the reference's own).
    """
    import jax

    jax.config.update("jax_enable_x64", False)
    n = cell.traffic["check_steps"]
    ref = Reference(cell)
    program = Program(cell, None)
    norms, diff_norms = _norm_fns()
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
        rows.append({"seed": seed,
                     "losses": {"program": first[0].tolist(),
                                "reference": losses.tolist(),
                                "control": control[0].tolist()},
                     "program": gaps(first, reference),
                     "control": gaps(control, reference),
                     "faults": {"half_batch": gaps(half, reference),
                                "state_unchanged": gaps(frozen, reference)}})
    return rows
