"""jit'd public wrappers around the Pallas kernels.

Handles: layout conversion from model-space, padding to kernel tile
multiples, and the execution mode, decided at call time from the default
backend: compiled on a TPU, the Pallas interpreter on the CPU (where the
tests validate the kernel bodies, see tests/test_kernels.py), and an error
on any other platform.

GBDT ensembles keep their kernel operands on the device. The first
:func:`gbdt_leaf_indices` call for an ensemble builds its (D, T') float32
thresholds and (D, F, T') one-hot feature selectors, trees padded to the
tree block, and an LRU of ``GBDT_CONSTANTS_MAX`` entries keeps them. The
key is the content of ``feats`` and ``thresholds`` (their bytes after the
cast to int32 and float32) with the feature count and tree block, so an
ensemble refitted in place, or another with the same shapes, never gets
stale operands. :func:`gbdt_constants_info` counts hits and builds
(``misses``). Rows are cast to float32 and zero-padded to the row block on
the host, so a call is one transfer in, the kernel, and one slice: the
device sees only padded row counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import flash_attention as _fa
from . import gbdt_predict as _gp
from . import mamba_scan as _ms


def _interpret() -> bool:
    """True on the CPU backend, False on a TPU; any other platform raises
    rather than running the kernels somewhere they were never validated."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on a TPU or "
                       f"interpreted on the CPU, not on {platform!r}")


def _padded(size: int, mult: int) -> int:
    """``size`` rounded up to a multiple of ``mult``."""
    return size + (-size) % mult


def _pad_to(x, axis: int, mult: int, value=0.0):
    pad = _padded(x.shape[axis], mult) - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------- #
def flash_attention(q, k, v, causal: bool = True, window=None,
                    bq: int = None, bk: int = None):
    """Model-space layout q: (B, S, Hq, hd), k/v: (B, S, Hkv, hd).
    Returns (B, S, Hq, hd)."""
    B, Sq, Hq, hd = q.shape
    Sk = k.shape[1]
    bq = bq or min(_fa.BQ, max(Sq, 8))
    bk = bk or min(_fa.BK, max(Sk, 8))
    qt = _pad_to(jnp.swapaxes(q, 1, 2), 2, bq)           # (B, Hq, Sq', hd)
    kt = _pad_to(jnp.swapaxes(k, 1, 2), 2, bk)
    vt = _pad_to(jnp.swapaxes(v, 1, 2), 2, bk)
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              interpret=_interpret(), bq=bq, bk=bk)
    return jnp.swapaxes(out[:, :, :Sq], 1, 2)


def mamba_scan(u, dt, A, Bm, Cm, D, chunk: int = None, bd: int = None):
    """Selective scan; shapes as ref.mamba_scan_ref. Returns (y, h_last)."""
    B, L, Di = u.shape
    chunk = chunk or min(_ms.CHUNK, L)
    bd = bd or min(_ms.BD, Di)
    Lp = L + ((-L) % chunk)
    up = _pad_to(u, 1, chunk)
    dtp = _pad_to(dt, 1, chunk)
    Bp = _pad_to(Bm, 1, chunk)
    Cp = _pad_to(Cm, 1, chunk)
    up = _pad_to(up, 2, bd)
    dtp = _pad_to(dtp, 2, bd)
    Ap = _pad_to(A, 0, bd, value=-1.0)
    Dp = _pad_to(D, 0, bd)
    y = _ms.mamba_scan(up, dtp, Ap, Bp, Cp, Dp, interpret=_interpret(),
                       chunk=chunk, bd=bd)
    y = y[:, :L, :Di]
    h_last = _ms.final_state(u, dt, A, Bm, Cm)
    return y, h_last


def _gbdt_blocks(n: int, n_trees: int, bn: int = None,
                 bt: int = None) -> tuple[int, int]:
    """(row block, tree block) of a kernel call over ``n`` rows and
    ``n_trees`` trees."""
    return bn or min(_gp.BN, max(n, 8)), bt or min(_gp.BT, max(n_trees, 8))


def gbdt_padded_shape(n: int, n_trees: int) -> tuple[int, int]:
    """(rows, trees) the kernel computes for ``n`` rows and ``n_trees``
    trees at :func:`gbdt_leaf_indices`' default blocks: each axis padded
    up to a multiple of its block."""
    bn, bt = _gbdt_blocks(n, n_trees)
    return _padded(n, bn), _padded(n_trees, bt)


GBDT_CONSTANTS_MAX = 16   # ensembles whose operands stay on the device


@functools.lru_cache(maxsize=GBDT_CONSTANTS_MAX)
def _gbdt_constants(feats: bytes, thresholds: bytes, n_trees: int,
                    depth: int, n_feat: int, bt: int):
    """Device operands of one ensemble, keyed by its int32 ``feats`` and
    float32 ``thresholds`` bytes: (D, T') thresholds and (D, F, T') one-hot
    feature selectors, trees on the lane axis. Padded trees split on
    feature 0 at 0 and are sliced off the result."""
    f = np.zeros((_padded(n_trees, bt), depth), np.int32)
    thr = np.zeros(f.shape, np.float32)
    f[:n_trees] = np.frombuffer(feats, np.int32).reshape(n_trees, depth)
    thr[:n_trees] = np.frombuffer(thresholds, np.float32).reshape(
        n_trees, depth)
    onehot = f.T[:, None, :] == np.arange(n_feat)[None, :, None]
    return (jnp.asarray(onehot.astype(np.float32)),
            jnp.asarray(np.ascontiguousarray(thr.T)))


gbdt_constants_info = _gbdt_constants.cache_info


@functools.partial(jax.jit, static_argnums=(1, 2))
def _leading(idx, n: int, n_trees: int):
    return idx[:n, :n_trees]


def gbdt_leaf_indices(X, feats, thresholds, bn: int = None,
                      bt: int = None):
    """numpy/jnp inputs in GBDTModel layout: X (n, F), feats (T, D) int,
    thresholds (T, D). Returns the (n, T) int32 leaf index of every row in
    every tree, a device array."""
    X = np.asarray(X)
    feats = np.asarray(feats, np.int32)
    thresholds = np.asarray(thresholds, np.float32)
    n, n_feat = X.shape
    n_trees, depth = feats.shape
    bn, bt = _gbdt_blocks(n, n_trees, bn, bt)
    onehot, thrp = _gbdt_constants(feats.tobytes(), thresholds.tobytes(),
                                   n_trees, depth, n_feat, bt)
    Xp = np.zeros((_padded(n, bn), n_feat), np.float32)
    Xp[:n] = X
    # the kernel is looked up on the module at each call: a test or a
    # control run may swap it
    idx = _gp.gbdt_leaf_indices(Xp, onehot, thrp, interpret=_interpret(),
                                bn=bn, bt=bt)
    return _leading(idx, n, n_trees)


def gbdt_predict_model(model, X):
    """Predict with a fitted core.gbdt.GBDTModel: leaf indices from the
    kernel, leaf values summed on the host in float64 by the model itself
    (bit-identical to ``model.predict`` whenever the indices agree)."""
    idx = gbdt_leaf_indices(X, model.feats, model.thresholds)
    return model.predict_from_leaves(np.asarray(idx))
