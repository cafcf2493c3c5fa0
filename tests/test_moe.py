"""Moonlight-16B-A3B (DeepSeek-V3 block) on the offload path, at a tiny
size on the CPU: the model against the benchmark's plain reference, the
routing, the share of the experts a device holds, the grouped Ozaki
product, the interceptor's grouped sites and the benchmark's counts.
"""

import importlib.util
import json
import re
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import PrecisionPolicy, offload, site_report
from repro.core import intercept, ozaki
from repro.launch.train import build_train_step
from repro.models import Model
from repro.obs import MetricsRun
from repro.train import AdamW

CHIP = Path(__file__).resolve().parent.parent / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

from kinds import moe_train  # noqa: E402

import moe_counts  # noqa: E402
import moe_scopes  # noqa: E402
import scope_reduce  # noqa: E402


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "test_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(CHIP / "configs" / "moonlight_16b_a3b_ref.py")
_FILE = json.loads((CHIP / "configs" / "moonlight_16b_a3b.json").read_text())
#: d 64, 1 dense + 2 expert layers, 16 experts of which 4 are held.
TINY = {**_FILE, **_FILE["rehearsal"]}
TRAFFIC = json.loads(
    (CHIP / "traffic" / "moe_train_ozaki_s4.json").read_text())
TRAFFIC = {**TRAFFIC, **TRAFFIC["rehearsal"], "seq_len": 32, "batch": 2}


def _cfg(**keys):
    return {**TINY, **keys}


def _model(cfg, remat=True):
    return Model(moe_train.lm_config(cfg, remat))


def _params(cfg, seed=11):
    return REF.init_params(cfg, REF.seed_words(seed))


def _tokens(cfg, seed=5):
    return jnp.asarray(moe_train.token_batch(
        seed, 0, TRAFFIC["batch"], TRAFFIC["seq_len"], cfg["vocab_size"]))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# -- the model against the plain reference ---------------------------------


class TestAgainstReference:
    def test_config_file_maps_onto_the_model(self):
        lm = moe_train.lm_config(_FILE)
        assert lm.mla and lm.moe and lm.held == (0, 8)
        assert lm.dense_layers == 1 and lm.num_layers == 6
        assert lm.shared_d_ff == 2816 and lm.q_dim == 16 * 192
        # The model-configs guide's count of this cut: 669 M parameters.
        assert lm.num_params() == pytest.approx(669e6, rel=1e-3)
        shapes = jax.eval_shape(Model(lm).init_params, jax.random.PRNGKey(0))
        ref = REF.param_shapes(_FILE)
        assert (jax.tree_util.tree_structure(shapes)
                == jax.tree_util.tree_structure(
                    ref, is_leaf=lambda x: isinstance(x, tuple)))
        assert [tuple(x.shape) for x in jax.tree_util.tree_leaves(shapes)] \
            == jax.tree_util.tree_leaves(
                ref, is_leaf=lambda x: isinstance(x, tuple))

    def test_loss_and_gradients_match_the_reference(self):
        params, tokens = _params(TINY), _tokens(TINY)
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(_model(TINY).loss)(params,
                                                                tokens)
            want, want_g = jax.value_and_grad(REF.loss)(
                params, tokens, TINY, "highest")
        assert float(loss) == pytest.approx(float(want), rel=1e-6)
        for g, w in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want_g)):
            assert _rel(g, w) < 1e-4

    def test_offloaded_step_matches_the_reference(self):
        """The launcher's step through offload at s=4, every product the
        gate admits (grouped ones included) emulated."""
        params, tokens = _params(TINY), _tokens(TINY)
        policy = PrecisionPolicy(backend="fp64_int8_4", default_splits=4,
                                 min_dim=16)
        fn = offload(jax.value_and_grad(_model(TINY).loss), policy)
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.jit(fn)(params, tokens)
            want, want_g = jax.value_and_grad(REF.loss)(
                params, tokens, TINY, "highest")
        assert float(loss) == pytest.approx(float(want), rel=1e-6)
        for g, w in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want_g)):
            assert _rel(g, w) < 1e-4
        sites = fn.sites(params, tokens)
        assert any(s.offloaded and s.primitive == "ragged_dot_general"
                   for s in sites)

    def test_experts_come_with_latent_attention(self):
        with pytest.raises(ValueError, match="MLA"):
            moe_train.lm_config(TINY).replace(kv_lora_rank=0)

    @pytest.mark.parametrize("moe", [True, False])
    def test_num_params_matches_init(self, moe):
        """The config's fields decide the block: MLA with and without
        experts."""
        lm = moe_train.lm_config(TINY)
        mla = True
        if not moe:
            lm = lm.replace(num_experts=0, experts_held=None)
        params = Model(lm).init_params(jax.random.PRNGKey(0))
        assert ("dense" in params) == moe
        assert ("wkv_b" in params["blocks"]) == mla
        assert lm.num_params() == sum(
            x.size for x in jax.tree_util.tree_leaves(params))
        tokens = _tokens(TINY)
        loss, grads = jax.value_and_grad(Model(lm).loss)(params, tokens)
        assert np.isfinite(float(loss))
        assert all(np.all(np.isfinite(np.asarray(g)))
                   for g in jax.tree_util.tree_leaves(grads))

    def test_routing_is_the_references(self):
        params = _params(TINY)
        lp = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
        h = jax.random.normal(jax.random.PRNGKey(3), (64, TINY["hidden_size"]),
                              jnp.float32)
        with jax.default_matmul_precision("highest"):
            expert, weight = _model(TINY)._route(lp, h)
            want_e, want_w = REF.routing(
                h, lp["router"], TINY,
                lambda a, b: jnp.matmul(a, b, precision="highest"))
        np.testing.assert_array_equal(np.asarray(expert), np.asarray(want_e))
        np.testing.assert_array_equal(np.asarray(weight), np.asarray(want_w))
        # Normalized to sum 1, then scaled.
        np.testing.assert_allclose(np.asarray(weight).sum(-1),
                                   TINY["routed_scaling_factor"], rtol=1e-6)

    def test_shares_of_the_experts_add_up_to_the_whole_layer(self):
        """Four devices holding 4 of the 16 experts each: their routed
        parts, with the shared expert counted once, are the uncut layer."""
        whole = _cfg(experts_held=[0, 16])
        params = _params(whole)
        lp = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
        x = jax.random.normal(jax.random.PRNGKey(4),
                              (2, 16, TINY["hidden_size"]), jnp.float32)
        mm = lambda a, b: jnp.matmul(a, b, precision="highest")  # noqa: E731
        with jax.default_matmul_precision("highest"):
            h = REF._rms_norm(x, lp["mlp_norm"], TINY["rms_norm_eps"])
            h = h.reshape(32, -1)
            shared = mm(jax.nn.silu(mm(h, lp["shared_gate"]))
                        * mm(h, lp["shared_up"]), lp["shared_down"])
            total = jnp.zeros_like(h)
            for first in range(0, 16, 4):
                part = dict(lp)
                for key in ("expert_gate", "expert_up", "expert_down"):
                    part[key] = lp[key][first:first + 4]
                model = _model(_cfg(experts_held=[first, 4]))
                total = total + model._experts(part, h)
            total = total - 3 * shared
            want = REF.expert_layer(
                h, lp, whole, mm,
                lambda a, b: jnp.einsum("nd,edf->enf", a, b,
                                        precision="highest"),
                lambda a, b: jnp.einsum("enf,efd->end", a, b,
                                        precision="highest"))
        assert _rel(total, want) < 1e-5


# -- the grouped Ozaki product ---------------------------------------------


def _groups(case, m):
    return {"spread": [5, 0, 17, 9], "one": [0, m, 0, 0],
            "short": [3, 0, 4, 0]}[case]


@pytest.mark.parametrize("case", ["spread", "one", "short"])
@pytest.mark.parametrize("splits", [4, 6])
class TestGroupedOzaki:
    m, k, n = 31, 40, 24

    def _operands(self, form, case):
        rng = np.random.default_rng(7)
        a = rng.standard_normal((self.m, self.k))
        if form == "rows":
            b = rng.standard_normal((4, self.k, self.n))
        else:
            b = rng.standard_normal((self.m, self.n))
        sizes = np.asarray(_groups(case, self.m), np.int32)
        return a, b, sizes

    def test_rows_against_f64_and_the_dense_loop(self, splits, case):
        a, b, sizes = self._operands("rows", case)
        got = np.asarray(ozaki.ozaki_ragged_dot(
            a, b, sizes, ozaki.RAGGED_ROWS, num_splits=splits,
            out_dtype=jnp.float64))
        want = np.zeros((self.m, self.n))
        loop = np.zeros((self.m, self.n))
        start = 0
        for g, size in enumerate(sizes):
            rows = slice(start, start + size)
            want[rows] = a[rows] @ b[g]
            if size:
                loop[rows] = np.asarray(ozaki._real_ozaki(
                    jnp.asarray(a[rows]), jnp.asarray(b[g]), splits, "df32",
                    jnp.dtype(jnp.float64), ozaki.SLICE_BITS))
            start += size
        bound = (np.abs(a) @ np.abs(b).max(0)).max()
        assert np.max(np.abs(got - want)) <= bound * 2.0 ** (-6 * splits + 2)
        np.testing.assert_array_equal(got, loop)
        assert not got[start:].any()  # rows past the groups are zero

    def test_contraction_against_f64(self, splits, case):
        a, b, sizes = self._operands("contraction", case)
        got = np.asarray(ozaki.ozaki_ragged_dot(
            a, b, sizes, ozaki.RAGGED_CONTRACTION, num_splits=splits,
            out_dtype=jnp.float64))
        assert got.shape == (4, self.k, self.n)
        start = 0
        for g, size in enumerate(sizes):
            rows = slice(start, start + size)
            want = a[rows].T @ b[rows]
            # Each operand's scale spans all rows: the error is bounded
            # by the rows times the operands' column maxima.
            bound = (self.m * np.abs(a).max(0)[:, None]
                     * np.abs(b).max(0)[None, :] * 2.0 ** (-6 * splits + 4))
            assert np.all(np.abs(got[g] - want) <= bound)
            if case == "one" and size:
                # One group holds every row: the dense product exactly.
                np.testing.assert_array_equal(got[g], np.asarray(
                    ozaki._real_ozaki(jnp.asarray(a.T), jnp.asarray(b),
                                      splits, "df32",
                                      jnp.dtype(jnp.float64),
                                      ozaki.SLICE_BITS)))
            if size == 0:
                assert not got[g].any()
            start += size


def _unwritten_tail(fn, fill):
    """``fn`` (a grouped product) with the rows past the groups filled
    with ``fill``, as XLA:TPU's grouped product leaves them unwritten."""
    def grouped(lhs, rhs, sizes, *args, **kwargs):
        out = fn(lhs, rhs, sizes, *args, **kwargs)
        if out.shape[0] != lhs.shape[0]:  # a ragged contraction
            return out
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.asarray(fill, out.dtype), out)

    return grouped


def test_rows_past_the_groups_are_zeroed(monkeypatch):
    rng = np.random.default_rng(9)
    a, b = rng.standard_normal((40, 24)), rng.standard_normal((4, 24, 16))
    sizes = np.array([5, 0, 17, 9], np.int32)
    want = np.asarray(ozaki.ozaki_ragged_dot(a, b, sizes, ozaki.RAGGED_ROWS,
                                             num_splits=4))
    jax.clear_caches()
    monkeypatch.setattr(jax.lax, "ragged_dot_general", _unwritten_tail(
        jax.lax.ragged_dot_general, 2**30))
    got = np.asarray(ozaki.ozaki_ragged_dot(a, b, sizes, ozaki.RAGGED_ROWS,
                                            num_splits=4))
    jax.clear_caches()
    np.testing.assert_array_equal(got, want)
    assert not got[31:].any()


def test_the_model_selects_unwritten_rows_away(monkeypatch):
    """NaN in the rows past the groups changes neither loss nor
    gradients: the layer selects them away forward and backward."""
    params, tokens = _params(TINY), _tokens(TINY)
    fn = jax.jit(_grad_fn(TINY))
    want_loss, want_grads = fn(params, tokens)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _unwritten_tail(jax.lax.ragged_dot, jnp.nan))
    loss, grads = jax.jit(_grad_fn(TINY))(params, tokens)
    assert float(loss) == float(want_loss)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_grouped_product_needs_a_known_form():
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((1,), (2,)), ((), ())),
        lhs_ragged_dimensions=(0,), rhs_group_dimensions=(0,))
    assert ozaki.ragged_form(dims) is None
    with pytest.raises(ValueError):
        ozaki.ozaki_ragged_dot(np.ones((4, 3)), np.ones((2, 5, 3)),
                               np.array([2, 2], np.int32), dims)


# -- the interceptor's grouped sites ---------------------------------------


def _grad_fn(cfg):
    return jax.value_and_grad(_model(cfg).loss)


def test_grouped_sites_forward_and_backward_are_found():
    params, tokens = _params(TINY), _tokens(TINY)
    policy = PrecisionPolicy(backend="fp64_int8_4", default_splits=4,
                             min_dim=16)
    sites = site_report(_grad_fn(TINY), policy)(params, tokens)
    grouped = [s for s in sites if s.primitive == "ragged_dot_general"]
    # Per expert layer: gate, up, down forward (scan0); their remat
    # recompute, dX and dW backward (scan1).
    assert [s.name for s in grouped] == (
        [f"scan0/ragged{i}" for i in range(3)]
        + [f"scan1/ragged{i}" for i in range(9)])
    assert all(s.offloaded and s.group_count == 4 and s.mult == 2
               and s.int8_dots == len(ozaki.fold_runs(4, s.k))
               for s in grouped)
    rows = TRAFFIC["batch"] * TRAFFIC["seq_len"] * TINY["num_experts_per_tok"]
    dw = [s for s in grouped if s.rhs_shape[0] == rows]
    assert len(dw) == 3 and all(s.k == rows for s in dw)
    assert sites.native == ()
    # The same names as offload's, dense numbering untouched.
    assert [s.name for s in offload(_grad_fn(TINY), policy).sites(
        params, tokens)] == [s.name for s in sites]


def test_adaptive_backend_offloads_grouped_sites():
    params, tokens = _params(TINY), _tokens(TINY)
    policy = PrecisionPolicy(backend="adaptive:1e-9", min_dim=16)
    fn = offload(_grad_fn(TINY), policy)
    sites = fn.sites(params, tokens)
    assert all(s.offloaded for s in sites
               if s.primitive == "ragged_dot_general")
    with jax.default_matmul_precision("highest"):
        loss, _ = jax.jit(fn)(params, tokens)
        want = REF.loss(params, tokens, TINY, "highest")
    assert float(loss) == pytest.approx(float(want), rel=1e-6)


def test_grouped_sites_left_native_without_a_grouped_form():
    params, tokens = _params(TINY), _tokens(TINY)
    policy = PrecisionPolicy(backend="pallas_int8_4", default_splits=4,
                             min_dim=16)
    sites = offload(_grad_fn(TINY), policy).sites(params, tokens)
    grouped = [s for s in sites if s.primitive == "ragged_dot_general"]
    assert len(grouped) == 12 and not any(s.offloaded for s in grouped)
    assert {n.name for n in sites.native} == {s.name for s in grouped}
    assert all(n.primitive == "ragged_dot_general"
               and "no grouped form" in n.reason for n in sites.native)
    report = site_report(_grad_fn(TINY), policy)(params, tokens)
    assert report.native == sites.native


def test_other_contractions_are_listed_native():
    x = jnp.ones((1, 3, 8, 8))
    k = jnp.ones((4, 3, 3, 3))

    @jax.custom_vjp
    def inner(a, b):
        return a @ b

    inner.defvjp(lambda a, b: (a @ b, (a, b)),
                 lambda r, g: (g @ r[1].T, r[0].T @ g))

    def f(x, k, a):
        y = jax.lax.conv_general_dilated(x, k, (1, 1), "SAME")
        return jnp.sum(y) + jnp.sum(inner(a, a))

    a = jnp.ones((8, 8))
    report = site_report(f, PrecisionPolicy(min_dim=1))(x, k, a)
    assert list(report) == []
    assert [(n.primitive, n.name) for n in report.native] == [
        ("conv_general_dilated", "conv0"), ("dot_general", "custom0/dot0")]


def test_grouped_rows_are_counted_per_execution():
    params, tokens = _params(TINY), _tokens(TINY)
    policy = PrecisionPolicy(backend="fp64_int8_4", default_splits=4,
                             min_dim=16)
    with tempfile.TemporaryDirectory() as tmp:
        run = MetricsRun(tmp)
        fn = offload(_grad_fn(TINY), policy,
                     on_site_event=run.site_event_handler())
        with jax.default_matmul_precision("highest"):
            jax.jit(fn)(params, tokens)
        jax.effects_barrier()
        snap = run.registry.snapshot()
        run.close()
    rows = {c["labels"]["site"]: c["value"] for c in snap
            if c["name"] == "grouped_rows"}
    execs = {c["labels"]["site"]: c["value"] for c in snap
             if c["name"] == "site_exec"}
    assert len(rows) == 12 and all(execs[s] == 2 for s in rows)
    # Every grouped site of the step routes the same rows: the (token,
    # choice) pairs on the held experts, summed over the two layers.
    assert len(set(rows.values())) == 1
    per_layer = next(iter(rows.values())) / 2
    bound = TRAFFIC["batch"] * TRAFFIC["seq_len"] * TINY["num_experts_per_tok"]
    assert 0 < per_layer < bound


# -- the dense model's step is unchanged -----------------------------------

#: (name, offloaded, int8_dots, m, k, n, mult) of each site of the tiny
#: Llama train step (remat, 2 x 128 tokens, s=4, min_dim 128), as the
#: transform took them before grouped sites existed.
LLAMA_SITES = [
    ("scan0/dot0", True, 4, 256, 128, 128, 2),
    ("scan0/dot1", False, 0, 256, 128, 64, 2),
    ("scan0/dot2", False, 0, 256, 128, 64, 2),
    ("scan0/dot3", False, 0, 128, 32, 128, 2),
    ("scan0/dot4", False, 0, 32, 128, 128, 2),
    ("scan0/dot5", True, 4, 256, 128, 128, 2),
    ("scan0/dot6", True, 4, 256, 128, 256, 2),
    ("scan0/dot7", True, 4, 256, 128, 256, 2),
    ("scan0/dot8", True, 4, 256, 256, 128, 2),
    ("dot0", True, 4, 256, 128, 512, 1),
    ("dot1", True, 4, 512, 256, 128, 1),
    ("dot2", True, 4, 256, 512, 128, 1),
    ("scan1/dot0", True, 4, 256, 128, 128, 2),
    ("scan1/dot1", False, 0, 256, 128, 64, 2),
    ("scan1/dot2", False, 0, 256, 128, 64, 2),
    ("scan1/dot3", False, 0, 128, 32, 128, 2),
    ("scan1/dot4", False, 0, 32, 128, 128, 2),
    ("scan1/dot5", True, 4, 256, 128, 128, 2),
    ("scan1/dot6", True, 4, 256, 128, 256, 2),
    ("scan1/dot7", True, 4, 256, 128, 256, 2),
    ("scan1/dot8", True, 4, 128, 256, 256, 2),
    ("scan1/dot9", True, 4, 256, 128, 256, 2),
    ("scan1/dot10", True, 4, 256, 256, 128, 2),
    ("scan1/dot11", True, 4, 256, 256, 128, 2),
    ("scan1/dot12", True, 4, 256, 256, 128, 2),
    ("scan1/dot13", True, 4, 256, 256, 128, 2),
    ("scan1/dot14", True, 4, 128, 256, 128, 2),
    ("scan1/dot15", True, 4, 256, 128, 128, 2),
    ("scan1/dot16", False, 0, 128, 32, 128, 2),
    ("scan1/dot17", False, 0, 32, 128, 128, 2),
    ("scan1/dot18", False, 0, 128, 128, 32, 2),
    ("scan1/dot19", False, 0, 128, 128, 32, 2),
    ("scan1/dot20", False, 0, 64, 256, 128, 2),
    ("scan1/dot21", False, 0, 256, 64, 128, 2),
    ("scan1/dot22", False, 0, 64, 256, 128, 2),
    ("scan1/dot23", False, 0, 256, 64, 128, 2),
    ("scan1/dot24", True, 4, 128, 256, 128, 2),
    ("scan1/dot25", True, 4, 256, 128, 128, 2),
]


def test_llama_train_step_sites_are_unchanged():
    cfg = get_config("tiny").replace(remat=True)
    model, opt = Model(cfg), AdamW(lr=1e-3)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    state = jax.eval_shape(opt.init, params)
    batch = jax.ShapeDtypeStruct((2, 129), jnp.int32)
    policy = PrecisionPolicy(backend="fp64_int8_4", default_splits=4,
                             min_dim=128)
    sites = offload(build_train_step(model, opt), policy,
                    on_site_event=lambda payload: None).sites(
        params, state, batch)
    assert [(s.name, s.offloaded, s.int8_dots, s.m, s.k, s.n, s.mult)
            for s in sites] == LLAMA_SITES
    assert sites.native == ()


# -- site and expert-layer scopes in the compiled step ---------------------

#: One instruction of compiled HLO text: its opcode and its op_name.
_HLO_OP = re.compile(
    r"= \S+ ([a-z][\w-]*)\(.*op_name=\"([^\"]*)\"")


def _compiled_ops(model, min_dim, seq_len=32):
    """(sites, [(opcode, op_name)]) of the offloaded tiny train step."""
    opt = AdamW(lr=1e-3)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    state = jax.eval_shape(opt.init, params)
    batch = jax.ShapeDtypeStruct((2, seq_len + 1), jnp.int32)
    policy = PrecisionPolicy(backend="fp64_int8_4", default_splits=4,
                             min_dim=min_dim)
    step = offload(build_train_step(model, opt), policy,
                   on_site_event=lambda payload: None)
    sites = step.sites(params, state, batch)
    text = jax.jit(step).lower(params, state, batch).compile().as_text()
    return sites, _HLO_OP.findall(text)


@pytest.mark.parametrize("which", ["llama", "moonlight"])
def test_site_scopes_are_whole_components_of_op_names(which):
    """Under ``value_and_grad`` the step's equations carry ``jvp`` and
    ``transpose`` transforms; every offloaded site must still read as
    one ``ozaki_<site>`` component that the benchmark's readers match,
    forward and backward."""
    if which == "llama":
        model = Model(get_config("tiny").replace(remat=True))
        sites, ops = _compiled_ops(model, 128, seq_len=128)
    else:
        sites, ops = _compiled_ops(_model(TINY), 16)
    names = [name for _, name in ops]
    assert not [n for n in names if "(ozaki_" in n or "(native_" in n]
    dense = {scope_reduce.scope_of(n) for n in names}
    parts = {moe_scopes.PART.findall(n)[-1] for n in names
             if moe_scopes.part_of(n) == "grouped"}
    offloaded = [s for s in sites if s.offloaded]
    assert offloaded
    for site in offloaded:
        scope = intercept.site_scope(site)
        found = parts if site.primitive == "ragged_dot_general" else dense
        assert scope in found, scope
    if which == "llama":
        # The top-level head sites: forward, dX, dW.
        assert {"ozaki_dot0", "ozaki_dot1", "ozaki_dot2"} <= dense


def test_expert_layer_scopes_hold_their_backward_ops():
    """The dispatch gathers rows forward and scatters their cotangents
    backward, the combine the other way round: both directions read as
    the part, and the router product as its site."""
    _, ops = _compiled_ops(_model(TINY), 16)
    by_part = {}
    for opcode, name in ops:
        by_part.setdefault(moe_scopes.part_of(name), set()).add(opcode)
    for part in ("moe_dispatch", "moe_combine"):
        assert {"gather", "scatter"} <= by_part[part], part
    assert by_part["moe_route"]
    assert {"grouped", "site"} <= set(by_part)


@pytest.mark.parametrize("path, part", [
    ("jit(step)/while/body/moe_dispatch/gather", "moe_dispatch"),
    ("jit(step)/moe_combine/scatter-add", "moe_combine"),
    ("jit(step)/moe_route/ozaki_scan0.dot9/jit(_real_ozaki)/dot_general",
     "site"),
    ("jit(step)/while/body/ozaki_scan1.ragged4/jit(_ragged_ozaki)/x",
     "grouped"),
    ("jit(step)/native_scan1.ragged4/ragged_dot_general", "site"),
    ("jit(step)/transpose(jvp(ozaki_dot1))/dot_general", None),
    ("jit(step)/moe_dispatchx/gather", None),
    (None, None),
])
def test_moe_scopes_classify_an_op_by_its_innermost_part(path, part):
    assert moe_scopes.part_of(path) == part


# -- the benchmark's counts ------------------------------------------------


def test_grouped_count_equals_the_offloaded_grouped_sites():
    """At the rehearsal size with the rows at their bound, the count is
    the sites' own; at min_dim 128 every grouped product stays native."""
    model = _model(TINY)
    opt = AdamW(**TRAFFIC["optimizer"])
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    state = jax.eval_shape(opt.init, params)
    batch = jax.ShapeDtypeStruct(
        (TRAFFIC["batch"], TRAFFIC["seq_len"] + 1), jnp.int32)
    bound = TRAFFIC["batch"] * TRAFFIC["seq_len"] * TINY["num_experts_per_tok"]
    for min_dim in (16, 128):
        traffic = {**TRAFFIC, "min_dim": min_dim}
        policy = PrecisionPolicy(backend=traffic["backend"],
                                 default_splits=traffic["splits"],
                                 min_dim=min_dim)
        sites = offload(build_train_step(model, opt), policy).sites(
            params, state, batch)
        want = sum(s.flops for s in sites
                   if s.offloaded and s.primitive == "ragged_dot_general")
        assert moe_counts.grouped_ops(TINY, traffic, rows=bound) == want
        assert (want > 0) == (min_dim == 16)
    balanced = moe_counts.balanced_rows(TINY, TRAFFIC)
    assert balanced == bound * 4 / 16
    assert moe_counts.grouped_ops(TINY, TRAFFIC) == pytest.approx(
        moe_counts.grouped_ops(TINY, TRAFFIC, rows=bound) / 4)


def test_flops_count_matches_the_parameter_count():
    """With every expert held and picked, the active matrix parameters
    are the model's own, less norms and the embedding gather."""
    every = {**_FILE, "experts_held": [0, 64], "num_experts_per_tok": 64}
    lm = moe_train.lm_config(every)
    d, L = lm.d_model, lm.num_layers
    norms = L * (2 * d + lm.kv_lora_rank) + d
    matmul = lm.num_params() - norms - lm.vocab_size * d
    assert moe_counts.active_matmul_params(every) == matmul
    per_token = moe_counts.moe_train_flops_per_token(_FILE, 2048)
    attention = 12 * 6 * 2048 * 16 * (192 + 128) / 2
    assert per_token == pytest.approx(
        6 * moe_counts.active_matmul_params(_FILE) + attention)
    # 6 x (MLA 13.76 M x 6 + dense 69.2 M + 5 x (router, shared expert
    # and 6/64 of 8 experts' 8.65 M) + head 41.9 M), plus attention
    # (0.38 G).
    assert per_token == pytest.approx(2.257e9, rel=1e-3)
