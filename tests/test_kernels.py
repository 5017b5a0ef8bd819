"""Pallas kernel validation: shape/dtype sweeps + property tests against the
pure-jnp oracles (interpret=True executes the kernel bodies on CPU)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # not installed in this container — deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels import ops, ref


def _mk_qkv(B, S, Hq, Hkv, hd, dtype, seed=0, Sk=None):
    Sk = Sk or S
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, hd)).astype(dtype)
    return q, k, v


def _ref_attn(q, k, v, **kw):
    out = ref.flash_attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2), **kw)
    return jnp.swapaxes(out, 1, 2)


class TestFlashAttention:
    @pytest.mark.parametrize("shape", [
        # (B, S, Hq, Hkv, hd) — GQA ratios and head dims from the zoo
        (1, 32, 4, 4, 16),     # MHA
        (2, 64, 8, 2, 32),     # GQA 4:1
        (1, 128, 15, 5, 64),   # smollm ratios
        (1, 48, 6, 1, 80),     # MQA, stablelm head_dim
        (2, 40, 4, 2, 128),    # ragged seq (pad path)
    ])
    def test_shapes_causal(self, shape):
        B, S, Hq, Hkv, hd = shape
        q, k, v = _mk_qkv(B, S, Hq, Hkv, hd, jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True)
        exp = _ref_attn(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q, k, v = _mk_qkv(2, 64, 8, 4, 32, dtype)
        out = ops.flash_attention(q, k, v, causal=True)
        exp = _ref_attn(q, k, v, causal=True)
        atol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            atol=atol, rtol=atol)
        assert out.dtype == dtype

    @pytest.mark.parametrize("window", [4, 16, 64])
    def test_sliding_window(self, window):
        q, k, v = _mk_qkv(1, 96, 4, 4, 32, jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True, window=window)
        exp = _ref_attn(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)

    def test_block_size_invariance(self):
        q, k, v = _mk_qkv(1, 128, 4, 2, 32, jnp.float32)
        a = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
        b = ops.flash_attention(q, k, v, causal=True, bq=64, bk=128)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 1000),
           S=st.sampled_from([16, 33, 80]),
           ratio=st.sampled_from([1, 2, 4]))
    def test_property_matches_ref(self, seed, S, ratio):
        Hkv = 2
        q, k, v = _mk_qkv(1, S, Hkv * ratio, Hkv, 16, jnp.float32, seed=seed)
        out = ops.flash_attention(q, k, v, causal=True)
        exp = _ref_attn(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=3e-5, rtol=3e-5)

    def test_rows_are_convex_combinations(self):
        """Attention outputs lie in the convex hull of v rows ⇒ bounded by
        per-batch max |v|."""
        q, k, v = _mk_qkv(2, 32, 4, 4, 16, jnp.float32, seed=3)
        out = ops.flash_attention(q, k, v, causal=True)
        assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(v))) + 1e-5


class TestMambaScan:
    def _mk(self, B, L, Di, N, seed=0, dtype=jnp.float32):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        u = jax.random.normal(ks[0], (B, L, Di)).astype(dtype)
        dt = (jax.nn.softplus(jax.random.normal(ks[1], (B, L, Di))) * 0.1
              ).astype(dtype)
        A = -jnp.exp(jax.random.normal(ks[2], (Di, N)) * 0.3)
        Bm = jax.random.normal(ks[3], (B, L, N)).astype(dtype)
        Cm = jax.random.normal(ks[4], (B, L, N)).astype(dtype)
        D = jnp.linspace(0.5, 1.5, Di)
        return u, dt, A, Bm, Cm, D

    @pytest.mark.parametrize("shape", [
        (1, 16, 8, 4), (2, 64, 32, 16), (1, 40, 24, 8),  # ragged L
    ])
    def test_shapes(self, shape):
        B, L, Di, N = shape
        args = self._mk(B, L, Di, N)
        y, h = ops.mamba_scan(*args, chunk=16, bd=8)
        ye, he = ref.mamba_scan_ref(*args)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ye),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(h), np.asarray(he),
                                   atol=1e-5, rtol=1e-5)

    def test_chunk_invariance(self):
        args = self._mk(1, 64, 16, 8, seed=1)
        y1, _ = ops.mamba_scan(*args, chunk=8, bd=16)
        y2, _ = ops.mamba_scan(*args, chunk=64, bd=8)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 1000), L=st.sampled_from([8, 24, 48]))
    def test_property_matches_ref(self, seed, L):
        args = self._mk(1, L, 8, 4, seed=seed)
        y, h = ops.mamba_scan(*args, chunk=8, bd=8)
        ye, he = ref.mamba_scan_ref(*args)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ye),
                                   atol=2e-5, rtol=2e-5)

    def test_state_decays_with_negative_A(self):
        """With A < 0 and zero input, the state contribution decays — the
        kernel must not accumulate drift across chunk boundaries."""
        B, L, Di, N = 1, 64, 8, 4
        u = jnp.zeros((B, L, Di)).at[:, 0].set(1.0)
        dt = jnp.full((B, L, Di), 0.5)
        A = -jnp.ones((Di, N)) * 2.0
        Bm = jnp.ones((B, L, N))
        Cm = jnp.ones((B, L, N))
        D = jnp.zeros(Di)
        y, _ = ops.mamba_scan(u, dt, A, Bm, Cm, D, chunk=16, bd=8)
        mags = np.abs(np.asarray(y[0, :, 0]))
        assert mags[1] < mags[0] and mags[30] < 1e-3


def _gbdt_model(feats, thr, leaves, base=0.0):
    from repro.core.gbdt import GBDTModel, GBDTParams
    return GBDTModel(base=base, feats=feats, thresholds=thr, leaves=leaves,
                     split_gain=np.zeros(int(feats.max()) + 1),
                     params=GBDTParams(iterations=feats.shape[0],
                                       depth=feats.shape[1]))


class TestGBDTPredict:
    def test_matches_model_predict_trained(self):
        """Kernel leaf indices + the model's float64 leaf sum reproduce
        model.predict bit-for-bit."""
        from repro.core.gbdt import GBDTParams, fit_gbdt
        rng = np.random.default_rng(0)
        X = rng.normal(size=(300, 10))
        y = np.sin(X[:, 0]) + X[:, 1] * X[:, 2]
        m = fit_gbdt(X, y, GBDTParams(iterations=120, depth=4))
        np.testing.assert_array_equal(ops.gbdt_predict_model(m, X),
                                      m.predict(X))

    def test_matches_model_predict_predictor_size(self):
        """The predictor's own ensemble size — 400 depth-4 trees over the
        23 DVFS features — spans several tree blocks of the kernel grid."""
        from repro.core.gbdt import GBDTParams, fit_gbdt
        rng = np.random.default_rng(1)
        X = rng.normal(size=(600, 23))
        y = np.sin(X[:, 0]) + X[:, 1] * X[:, 2] + 0.1 * X[:, 22]
        m = fit_gbdt(X, y, GBDTParams(iterations=400, depth=4))
        Xq = rng.normal(size=(520, 23))
        np.testing.assert_array_equal(ops.gbdt_predict_model(m, Xq),
                                      m.predict(Xq))

    def test_leaf_sum_ignores_index_memory_order(self):
        """Index arrays copied back from a device may be column-major; the
        float64 leaf sum must not round differently for them."""
        from repro.core.gbdt import GBDTParams, fit_gbdt
        rng = np.random.default_rng(2)
        X = rng.normal(size=(300, 23))
        m = fit_gbdt(X, np.sin(X[:, 0]) + X[:, 1] * X[:, 2],
                     GBDTParams(iterations=400, depth=4))
        idx = m.leaf_indices(X)
        np.testing.assert_array_equal(
            m.predict_from_leaves(np.asfortranarray(idx.astype(np.int32))),
            m.predict(X))

    @pytest.mark.parametrize("n,T,depth,F", [
        (17, 9, 2, 5),      # ragged everything (pad path)
        (64, 64, 4, 23),    # production-ish (23 = DVFS feature count)
        (8, 130, 6, 8),     # deep trees, many trees
    ])
    def test_shape_sweep_random_ensembles(self, n, T, depth, F):
        rng = np.random.default_rng(42)
        X = rng.normal(size=(n, F))
        feats = rng.integers(0, F, size=(T, depth))
        thr = rng.normal(size=(T, depth))
        leaves = rng.normal(size=(T, 2 ** depth))
        got = np.asarray(ops.gbdt_leaf_indices(X, feats, thr))
        exp = np.asarray(ref.gbdt_leaf_indices_ref(
            jnp.asarray(X), jnp.asarray(feats), jnp.asarray(thr)))
        np.testing.assert_array_equal(got, exp)
        pred = ops.gbdt_predict_model(_gbdt_model(feats, thr, leaves, 1.5), X)
        exp = np.asarray(ref.gbdt_predict_ref(
            jnp.asarray(X), jnp.asarray(feats), jnp.asarray(thr),
            jnp.asarray(leaves), base=1.5))
        np.testing.assert_allclose(pred, exp, atol=1e-4, rtol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_property_random(self, seed):
        rng = np.random.default_rng(seed)
        n, T, depth, F = 13, 7, 3, 6
        X = rng.normal(size=(n, F))
        feats = rng.integers(0, F, size=(T, depth))
        thr = rng.normal(size=(T, depth))
        leaves = rng.normal(size=(T, 2 ** depth))
        got = np.asarray(ops.gbdt_leaf_indices(X, feats, thr))
        exp = np.asarray(ref.gbdt_leaf_indices_ref(
            jnp.asarray(X), jnp.asarray(feats), jnp.asarray(thr)))
        np.testing.assert_array_equal(got, exp)
        pred = ops.gbdt_predict_model(_gbdt_model(feats, thr, leaves), X)
        exp = np.asarray(ref.gbdt_predict_ref(
            jnp.asarray(X), jnp.asarray(feats), jnp.asarray(thr),
            jnp.asarray(leaves)))
        np.testing.assert_allclose(pred, exp, atol=1e-4, rtol=1e-4)


def _ensemble(seed, n_trees=400, depth=4, n_feat=23):
    """A random ensemble of the predictor's size whose thresholds are
    float32 values, so the numpy path's float64 compares agree with the
    kernel's float32 ones."""
    rng = np.random.default_rng(seed)
    feats = rng.integers(0, n_feat, size=(n_trees, depth)).astype(np.int32)
    thr = rng.normal(size=(n_trees, depth)).astype(np.float32)
    leaves = rng.normal(size=(n_trees, 2 ** depth))
    return _gbdt_model(feats, thr.astype(np.float64), leaves)


def _rows(seed, n, n_feat=23):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, n_feat)).astype(np.float32).astype(np.float64)


class TestGBDTLaunch:
    """The launch path of ``ops.gbdt_leaf_indices``: rows padded on the
    host, each ensemble's operands built once and kept on the device."""

    @pytest.mark.parametrize("n", [1, 255, 257, 1088])
    def test_row_counts_match_reference(self, n):
        m = _ensemble(7)
        X = _rows(n, n)
        got = ops.gbdt_leaf_indices(X, m.feats, m.thresholds)
        assert isinstance(got, jax.Array)
        assert got.shape == (n, 400) and got.dtype == jnp.int32
        exp = ref.gbdt_leaf_indices_ref(
            jnp.asarray(X), jnp.asarray(m.feats), jnp.asarray(m.thresholds))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
        np.testing.assert_array_equal(np.asarray(got), m.leaf_indices(X))

    def test_constants_built_once_per_ensemble(self):
        m = _ensemble(11)
        before = ops.gbdt_constants_info()
        for n in (64, 300, 64):
            X = _rows(n, n)
            np.testing.assert_array_equal(
                np.asarray(ops.gbdt_leaf_indices(X, m.feats, m.thresholds)),
                m.leaf_indices(X))
        after = ops.gbdt_constants_info()
        assert after.misses - before.misses == 1
        assert after.hits - before.hits == 2
        assert after.currsize <= ops.GBDT_CONSTANTS_MAX

    def test_same_shapes_other_splits_get_their_own_operands(self):
        a, b = _ensemble(21), _ensemble(22)
        X = _rows(0, 300)
        before = ops.gbdt_constants_info()
        got_a = np.asarray(ops.gbdt_leaf_indices(X, a.feats, a.thresholds))
        got_b = np.asarray(ops.gbdt_leaf_indices(X, b.feats, b.thresholds))
        np.testing.assert_array_equal(got_a, a.leaf_indices(X))
        np.testing.assert_array_equal(got_b, b.leaf_indices(X))
        assert (got_a != got_b).any()
        # an ensemble changed in place is another key
        a.thresholds[:] = b.thresholds
        a.feats[:] = b.feats
        np.testing.assert_array_equal(
            np.asarray(ops.gbdt_leaf_indices(X, a.feats, a.thresholds)),
            got_b)
        after = ops.gbdt_constants_info()
        assert after.misses - before.misses == 2
        assert after.hits - before.hits == 1

    def test_kernel_swapped_after_a_call_is_used(self, monkeypatch):
        """A control run swaps ``gbdt_predict.gbdt_leaf_indices`` for
        another kernel: the next call runs the new one."""
        from repro.kernels import gbdt_predict
        m = _ensemble(31)
        X = _rows(1, 40)
        first = np.asarray(ops.gbdt_leaf_indices(X, m.feats, m.thresholds))
        np.testing.assert_array_equal(first, m.leaf_indices(X))
        orig = gbdt_predict.gbdt_leaf_indices

        def swapped(Xp, onehot, thrp, **kw):
            return orig(Xp, onehot, thrp, **kw) ^ 1

        monkeypatch.setattr(gbdt_predict, "gbdt_leaf_indices", swapped)
        np.testing.assert_array_equal(
            np.asarray(ops.gbdt_leaf_indices(X, m.feats, m.thresholds)),
            first ^ 1)
        monkeypatch.undo()
        np.testing.assert_array_equal(
            np.asarray(ops.gbdt_leaf_indices(X, m.feats, m.thresholds)),
            first)


class TestModelIntegration:
    def test_attention_flash_impl_matches_xla(self):
        """attn_impl='flash' through the real attention module."""
        from repro.configs import get_config
        from repro.configs.base import reduce_for_smoke
        from repro.models import attention as attn_mod, model
        import dataclasses as dc
        cfg = reduce_for_smoke(get_config("mixtral-8x22b"))
        p = attn_mod.init_attention(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                              jnp.float32)
        out_x, _ = attn_mod.attention(p, x, cfg, impl="xla")
        out_f, _ = attn_mod.attention(p, x, cfg, impl="flash")
        np.testing.assert_allclose(np.asarray(out_x), np.asarray(out_f),
                                   atol=1e-4, rtol=1e-4)

    def test_mamba_flash_impl_matches_xla(self):
        from repro.configs import get_config
        from repro.configs.base import reduce_for_smoke
        from repro.models.ssm import init_mamba, mamba1_block
        import dataclasses as dc
        cfg = reduce_for_smoke(get_config("falcon-mamba-7b"))
        p = init_mamba(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                              jnp.float32)
        out_x, _ = mamba1_block(p, x, cfg)
        cfg_f = dc.replace(cfg, attn_impl="flash")
        out_f, _ = mamba1_block(p, x, cfg_f)
        np.testing.assert_allclose(np.asarray(out_x), np.asarray(out_f),
                                   atol=1e-4, rtol=1e-4)
