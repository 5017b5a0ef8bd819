"""Pallas TPU batched oblivious-tree ensemble traversal.

The scheduler's hot loop (Algorithm 1) evaluates every queued job against
every supported clock pair for two GBDT ensembles. On a GPU this is a
pointer-chasing tree walk; here every tile is 2-D (rows on sublanes, trees
on lanes) and the MXU does the feature gather:

  for each depth level d:
    g_d[n, t] = Σ_f X[n, f] · onehot[d, f, t]          (MXU, f32 HIGHEST)
    idx[n, t] += (g_d[n, t] > thr[d, t]) · 2^d          (VPU)

Oblivious trees make this possible: a depth-d tree is d (feature,
threshold) pairs plus a 2^d leaf table, so traversal is data-independent —
the property CatBoost exploits for SIMD scoring on CPU. The gather matmul
runs at ``Precision.HIGHEST`` so that one-hot × X reproduces X exactly in
f32: a gathered value rounded below f32 could cross a threshold and pick
another leaf.

The kernel returns the (rows, trees) leaf indices. The host sums the
selected leaf values in float64 with the numpy path's own code
(:meth:`repro.core.gbdt.GBDTModel.predict_from_leaves`), so equal indices
give bit-identical predictions: an f32 leaf sum differs from it by ~1e-7
relative, enough to flip near-tied clock choices and change a schedule.

Routing: :class:`repro.core.prediction_service.PredictionService` sends
predictor batches of at least ``DEFAULT_KERNEL_MIN_ROWS`` rows here when
the backend is a TPU (env override ``REPRO_GBDT_KERNEL_MIN_ROWS``; ≤ 0
routes everything). Single-ladder builds stay on numpy; the batched
admission-time prefetch is the caller that reaches kernel scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BN = 256   # rows per block
BT = 128   # trees per block (one lane width)


def _kernel(x_ref, oh_ref, thr_ref, idx_ref, *, depth: int):
    x = x_ref[...]                                         # (BN, F)
    idx = jnp.zeros(idx_ref.shape, jnp.int32)              # (BN, BT)
    for d in range(depth):
        g = jax.lax.dot_general(
            x, oh_ref[d], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)            # (BN, BT)
        idx = idx + jnp.where(g > thr_ref[d:d + 1, :], 1 << d, 0)
    idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bt"))
def gbdt_leaf_indices(X, feats_onehot, thresholds, interpret: bool = False,
                      bn: int = BN, bt: int = BT):
    """X: (n, F) fp32; feats_onehot: (D, F, T) fp32; thresholds: (D, T).
    n % bn == 0, T % bt == 0 (ops pads). Returns (n, T) int32 leaf
    indices."""
    n, F = X.shape
    depth, T = thresholds.shape
    return pl.pallas_call(
        functools.partial(_kernel, depth=depth),
        grid=(n // bn, T // bt),
        in_specs=[
            pl.BlockSpec((bn, F), lambda ni, ti: (ni, 0)),
            pl.BlockSpec((depth, F, bt), lambda ni, ti: (0, 0, ti)),
            pl.BlockSpec((depth, bt), lambda ni, ti: (0, ti)),
        ],
        out_specs=pl.BlockSpec((bn, bt), lambda ni, ti: (ni, ti)),
        out_shape=jax.ShapeDtypeStruct((n, T), jnp.int32),
        interpret=interpret,
        name="gbdt_leaf_indices",
    )(X, feats_onehot, thresholds)
