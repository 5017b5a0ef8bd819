"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -2.0 ** 30


def flash_attention_ref(q, k, v, causal: bool = True, window=None):
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Sk, hd). Returns (B, Hq, Sq, hd).

    GQA: Hq % Hkv == 0; head h attends kv head h // (Hq // Hkv).
    """
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, Sq, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgsh,bkth->bkgst", qf, kf) / np.sqrt(hd)
    qi = jnp.arange(Sq)[:, None] + (Sk - Sq)  # right-aligned queries
    kj = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask = mask & (kj <= qi)
    if window is not None:
        mask = mask & (kj > qi - window)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,bkth->bkgsh", probs, vf)
    return out.reshape(B, Hq, Sq, hd).astype(q.dtype)


def mamba_scan_ref(u, dt, A, Bm, Cm, D, h0=None):
    """Sequential selective scan (same math as models.ssm.mamba1_scan).

    u/dt: (B, L, Di); A: (Di, N); Bm/Cm: (B, L, N); D: (Di,).
    Returns (y (B, L, Di) fp32, h_last (B, Di, N) fp32).
    """
    Bsz, L, Di = u.shape
    N = A.shape[1]
    if h0 is None:
        h0 = jnp.zeros((Bsz, Di, N), jnp.float32)

    def step(h, inp):
        u_t, dt_t, B_t, C_t = inp
        dA = jnp.exp(dt_t[..., None] * A[None])
        dBu = (dt_t * u_t)[..., None] * B_t[:, None, :]
        h = dA * h + dBu
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y

    inputs = (jnp.moveaxis(u.astype(jnp.float32), 1, 0),
              jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
              jnp.moveaxis(Bm.astype(jnp.float32), 1, 0),
              jnp.moveaxis(Cm.astype(jnp.float32), 1, 0))
    h_last, ys = jax.lax.scan(step, h0, inputs)
    y = jnp.moveaxis(ys, 0, 1) + u.astype(jnp.float32) * D[None, None, :]
    return y, h_last


def gbdt_leaf_indices_ref(X, feats, thresholds):
    """Oblivious-tree traversal: the (n, T) int32 leaf index of every row in
    every tree. X: (n, F); feats: (T, D) int32; thresholds: (T, D)."""
    bits = X[:, feats] > thresholds[None]                   # (n, T, D)
    w = (1 << jnp.arange(feats.shape[1])).astype(jnp.int32)
    return jnp.sum(bits.astype(jnp.int32) * w[None, None], axis=-1)


def gbdt_predict_ref(X, feats, thresholds, leaves, base: float = 0.0):
    """Oblivious-tree ensemble inference.

    X: (n, F); feats: (T, D) int32; thresholds: (T, D); leaves: (T, 2**D).
    Returns (n,) fp32 predictions.
    """
    idx = gbdt_leaf_indices_ref(X, feats, thresholds)       # (n, T)
    contrib = jnp.take_along_axis(
        jnp.broadcast_to(leaves[None], (X.shape[0],) + leaves.shape),
        idx[..., None], axis=2)[..., 0]
    return base + jnp.sum(contrib, axis=1).astype(jnp.float32)
