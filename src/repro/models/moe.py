"""Mixture-of-Experts layer with capacity-based gather/scatter dispatch.

Design notes (TPU adaptation, DESIGN.md §3):

* Routing, sorting and capacity assignment happen **per batch row** so the
  token-permutation never crosses the data-parallel sharding of the batch.
* Dispatch uses sort + gather/scatter (active-FLOPs only) instead of the
  one-hot dispatch einsum — a dense (tokens, E, C) dispatch tensor at E = 384
  (Kimi-K2) would dominate compiled FLOPs and HBM.
* Expert-parallel sharding when E % TP == 0 (Kimi: 384/16 = 24 experts per
  shard; the scatter output is sharding-constrained to (data, model, ...) so
  XLA materializes the token all-to-all). For small E (Mixtral: 8) experts
  are replicated across TP and each expert's FFN is tensor-parallel instead.
* Load-balance auxiliary loss (Switch-style) is returned to the train loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import common
from .common import FSDP, TP, dense_init, dtype_of, maybe_shard
from .mlp import init_mlp, mlp, spec_mlp


def init_moe(key, cfg):
    dt = dtype_of(cfg.param_dtype)
    D, E, F = cfg.d_model, cfg.n_experts, cfg.resolved_moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32),
        "w_gate": dense_init(ks[1], (E, D, F), dt),
        "w_up": dense_init(ks[2], (E, D, F), dt),
        "w_down": dense_init(ks[3], (E, F, D), dt, fan_in=F),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg,
                               d_ff=F * cfg.n_shared_experts)
    return p


def spec_moe(cfg):
    if cfg.n_experts % 16 == 0:  # expert-parallel
        w = P(TP, FSDP, None)
        wd = P(TP, None, FSDP)
    else:  # per-expert tensor-parallel
        w = P(None, FSDP, TP)
        wd = P(None, TP, FSDP)
    p = {"router": P(FSDP, None), "w_gate": w, "w_up": w, "w_down": wd}
    if cfg.n_shared_experts:
        p["shared"] = spec_mlp()
    return p


def _mesh_axes():
    mesh = common.current_mesh()
    return set(mesh.axis_names) if mesh is not None else set()


def moe_sharded(p, x, cfg):
    """shard_map expert-parallel MoE (the production path under a mesh).

    Why not plain jit: GSPMD handles the dispatch *scatter* by replicating
    its operands — the (B, E·C, D) dispatch buffer materializes at GLOBAL
    batch per device (hundreds of GB for Kimi-K2) and the combine becomes
    full all-gathers (the dominant collective term in the baseline dry-run,
    EXPERIMENTS §Perf iteration 1).

    Layout: tokens batch-sharded over (pod, data) and REPLICATED over model;
    experts sharded over model (E_loc = E/TP per device); expert weights'
    d_model dim FSDP-sharded over data. Each device:
      1. routes its local tokens (router weights replicated, E small·D),
      2. keeps assignments for its LOCAL experts, capacity-gathers,
      3. all-gathers its expert weights' D-shards over `data` (FSDP),
      4. runs the expert FFN on (B_loc, E_loc, C, D),
      5. combine-scatters locally and psums the output over `model`
         (same collective shape as a dense TP MLP).
    """
    axes = _mesh_axes()
    mesh = common.current_mesh()
    # batch sharding: largest ('pod','data') subset that divides B (decode
    # at batch 1 / long-context cells run with the batch replicated)
    dp = ()
    for cand in (("pod", "data"), ("data",), ("pod",)):
        if all(a in axes for a in cand):
            size = 1
            for a in cand:
                size *= mesh.shape[a]
            if x.shape[0] % size == 0:
                dp = cand
                break
    E, k = cfg.n_experts, cfg.top_k
    tp = mesh.shape[TP]
    # E-sharding (expert parallel) when divisible (Kimi: 384/16); otherwise
    # experts replicate across TP and each expert's FFN dim shards
    # (Mixtral: 8 experts, F = 16384/16) — both end in the same single psum
    e_sharded = E % tp == 0
    E_loc = E // tp if e_sharded else E

    def local(x_loc, router, wg, wu, wd, *shared_w):
        B, S, D = x_loc.shape
        C = int(np.ceil(S * k * cfg.capacity_factor / E))
        C = max(min(C, S * k), 1)
        e0 = jax.lax.axis_index(TP) * E_loc if e_sharded else 0

        logits = jnp.einsum("bsd,de->bse", x_loc.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        flat_e = top_i.reshape(B, S * k)
        flat_w = top_p.reshape(B, S * k)
        flat_tok = jnp.broadcast_to(
            jnp.arange(S)[:, None], (S, k)).reshape(-1)
        is_local = (flat_e >= e0) & (flat_e < e0 + E_loc)
        sort_key = jnp.where(is_local, flat_e - e0, E_loc)  # non-local last
        order = jnp.argsort(sort_key, axis=1, stable=True)
        sorted_e = jnp.take_along_axis(sort_key, order, axis=1)
        sorted_w = jnp.take_along_axis(flat_w, order, axis=1)
        sorted_tok = flat_tok[order]
        seg_start = jax.vmap(
            lambda se: jnp.searchsorted(se, se, side="left"))(sorted_e)
        pos_in_e = jnp.arange(S * k)[None, :] - seg_start
        keep = (pos_in_e < C) & (sorted_e < E_loc)
        dest = jnp.where(keep, sorted_e * C + pos_in_e, E_loc * C)

        vals = jnp.take_along_axis(x_loc, sorted_tok[..., None], axis=1)
        vals = vals * keep[..., None].astype(x_loc.dtype)
        xe = jnp.zeros((B, E_loc * C + 1, D), x_loc.dtype)
        bidx = jnp.arange(B)[:, None]
        xe = xe.at[bidx, dest].add(vals)[:, :-1].reshape(B, E_loc, C, D)

        # FSDP: gather the D-shards of the local experts' weights
        if "data" in axes:
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
        g = jnp.einsum("becd,edf->becf", xe, wg.astype(x_loc.dtype))
        u = jnp.einsum("becd,edf->becf", xe, wu.astype(x_loc.dtype))
        h = jax.nn.silu(g) * u
        ye = jnp.einsum("becf,efd->becd", h, wd.astype(x_loc.dtype))
        ye = ye.reshape(B, E_loc * C, D)
        ye = jnp.concatenate(
            [ye, jnp.zeros((B, 1, D), ye.dtype)], axis=1)

        gathered = ye[bidx, dest]
        gathered = gathered * (sorted_w * keep)[..., None].astype(x_loc.dtype)
        out = jnp.zeros((B, S, D), x_loc.dtype)
        out = out.at[bidx, sorted_tok].add(gathered)

        if shared_w:
            sg, su, sd = shared_w  # F TP-sharded: partial after w_down
            hsh = jax.nn.silu(
                jnp.einsum("bsd,df->bsf", x_loc, sg.astype(x_loc.dtype))
            ) * jnp.einsum("bsd,df->bsf", x_loc, su.astype(x_loc.dtype))
            out = out + jnp.einsum("bsf,fd->bsd", hsh,
                                   sd.astype(x_loc.dtype))
        out = jax.lax.psum(out, TP)

        me = jnp.mean(probs, axis=(0, 1))
        one_hot = jax.nn.one_hot(top_i[..., 0], E, dtype=jnp.float32)
        ce = jnp.mean(one_hot, axis=(0, 1))
        aux = E * jnp.sum(me * ce)
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return out, aux

    bspec = P(dp if dp else None, None, None)
    fs = FSDP if "data" in axes else None
    if e_sharded:
        w_specs = [P(TP, fs, None), P(TP, fs, None), P(TP, None, fs)]
    else:
        w_specs = [P(None, fs, TP), P(None, fs, TP), P(None, TP, fs)]
    in_specs = [bspec, P(None, None)] + w_specs        # x, router, weights
    args = [x, p["router"], p["w_gate"], p["w_up"], p["w_down"]]
    if cfg.n_shared_experts:
        in_specs += [P(None, TP), P(None, TP), P(TP, None)]
        args += [p["shared"]["w_gate"], p["shared"]["w_up"],
                 p["shared"]["w_down"]]
    fn = jax.shard_map(
        local, mesh=common.current_mesh(),
        in_specs=tuple(in_specs),
        out_specs=(bspec, P()),
        check_vma=False,
    )
    return fn(*args)


def moe(p, x, cfg):
    """x: (B, S, D) → (out (B, S, D), aux_loss scalar)."""
    axes = _mesh_axes()
    if TP in axes:
        tp = common.current_mesh().shape[TP]
        if cfg.n_experts % tp == 0 or cfg.resolved_moe_d_ff % tp == 0:
            return moe_sharded(p, x, cfg)  # E-sharded or F-sharded variant
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = int(np.ceil(S * k * cfg.capacity_factor / E))
    C = max(min(C, S * k), 1)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                 # (B, S, E)
    top_p, top_i = jax.lax.top_k(probs, k)                  # (B, S, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize

    # ---- per-row capacity assignment (sort by expert id) -------------- #
    flat_e = top_i.reshape(B, S * k)                        # (B, T)
    flat_w = top_p.reshape(B, S * k)
    flat_tok = jnp.broadcast_to(jnp.arange(S)[:, None], (S, k)).reshape(-1)
    order = jnp.argsort(flat_e, axis=1, stable=True)        # (B, T)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    sorted_w = jnp.take_along_axis(flat_w, order, axis=1)
    sorted_tok = flat_tok[order]                            # (B, T)
    # position of each assignment within its expert segment
    seg_start = jax.vmap(
        lambda se: jnp.searchsorted(se, se, side="left"))(sorted_e)
    pos_in_e = jnp.arange(S * k)[None, :] - seg_start       # (B, T)
    keep = pos_in_e < C
    dest = sorted_e * C + jnp.minimum(pos_in_e, C - 1)      # (B, T)

    # ---- dispatch: gather tokens into (B, E, C, D) --------------------- #
    vals = jnp.take_along_axis(
        x, sorted_tok[..., None], axis=1)                   # (B, T, D)
    vals = vals * keep[..., None].astype(x.dtype)
    xe = jnp.zeros((B, E * C, D), x.dtype)
    bidx = jnp.arange(B)[:, None]
    xe = xe.at[bidx, dest].add(vals)                        # unique dests
    xe = xe.reshape(B, E, C, D)
    if cfg.n_experts % 16 == 0:
        xe = maybe_shard(xe, P(("pod", FSDP), TP, None, None))

    # ---- expert FFN (active FLOPs only) --------------------------------- #
    g = jnp.einsum("becd,edf->becf", xe, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(x.dtype))
    ye = ye.reshape(B, E * C, D)

    # ---- combine: weighted scatter-add back to token order -------------- #
    gathered = ye[bidx, dest]                               # (B, T, D)
    gathered = gathered * (sorted_w * keep)[..., None].astype(x.dtype)
    out = jnp.zeros((B, S, D), x.dtype)
    out = out.at[bidx, sorted_tok].add(gathered)

    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], x)

    # ---- Switch-style load-balance aux loss ------------------------------ #
    me = jnp.mean(probs, axis=(0, 1))                       # (E,)
    one_hot = jax.nn.one_hot(top_i[..., 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=(0, 1))
    aux = E * jnp.sum(me * ce)
    return out, aux
