"""Three-term roofline analysis from AOT-compiled artifacts (§Roofline).

  compute term    = HLO_FLOPs / (peak_FLOP/s)          [cost_analysis, per-device]
  memory term     = HLO_bytes / HBM_bw                 [cost_analysis, per-device]
  collective term = collective_bytes / link_bw         [parsed from compiled HLO]

cost_analysis() on the SPMD-partitioned executable reports *per-device*
FLOPs/bytes (verified against analytic 6·N·D), so no further division by
chip count. Hardware constants: TPU v5e-class — 197 TFLOP/s bf16, 819 GB/s
HBM, ~50 GB/s/link ICI.

collective_bytes: cost_analysis does not include collectives; we parse the
post-partitioning HLO text and, for every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute, take the instruction's
result shape and replica-group size. Two numbers are reported:
  * ``coll_bytes_raw`` — Σ result-shape bytes (the literal
    "sum of operand sizes" convention), and
  * ``coll_bytes_modeled`` — per-device ring-algorithm link traffic
    (all-reduce 2·s·(N-1)/N, all-gather s·(N-1)/N, reduce-scatter s·(N-1),
    all-to-all s·(N-1)/N, permute s),
the collective term uses the modeled number (it is what the 50 GB/s link
actually carries).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

# ---------------------------------------------------------------------- #
PEAK_FLOPS = 197e12       # bf16 / chip
HBM_BW = 819e9            # B/s / chip
LINK_BW = 50e9            # B/s / ICI link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", )
_TUPLE_COLL_RE = re.compile(
    r"=\s*\(([^)]*)\)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUP_BRACKET_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUP_BRACKET_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUP_BRACE_RE.search(line)
    if m:
        return max(len([x for x in m.group(1).split(",") if x.strip()]), 1)
    return 1


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    raw_bytes: float
    modeled_bytes: float
    by_kind: dict


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: dict = {}
    raw = 0.0
    modeled = 0.0
    by_kind: dict = {}
    for line in hlo_text.splitlines():
        if "replica_groups" not in line:
            continue
        kind = None
        shapes: list[tuple[str, str]] = []
        m = _COLL_RE.search(line)
        if m:
            kind = m.group(3)
            shapes = [(m.group(1), m.group(2))]
        else:
            mt = _TUPLE_COLL_RE.search(line)
            if mt:
                kind = mt.group(2)
                shapes = _SHAPE_RE.findall(mt.group(1))
        if kind is None:
            continue
        if line.strip().startswith("%fusion") and "fused_computation" in line:
            pass
        n = _group_size(line)
        size = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        raw += size
        if kind == "all-reduce":
            traffic = 2.0 * size * (n - 1) / max(n, 1)
        elif kind == "all-gather":
            traffic = size * (n - 1) / max(n, 1)
        elif kind == "reduce-scatter":
            traffic = float(size) * (n - 1)
        elif kind == "all-to-all":
            traffic = size * (n - 1) / max(n, 1)
        else:  # collective-permute
            traffic = float(size)
        modeled += traffic
        counts[kind] = counts.get(kind, 0) + 1
        by_kind[kind] = by_kind.get(kind, 0.0) + traffic
    return CollectiveStats(counts=counts, raw_bytes=raw,
                           modeled_bytes=modeled, by_kind=by_kind)


@dataclasses.dataclass
class Roofline:
    flops: float                 # per device
    bytes_accessed: float        # per device
    coll_bytes_raw: float
    coll_bytes_modeled: float
    coll_counts: dict
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float           # analytic useful FLOPs per device
    useful_ratio: float          # model_flops / hlo_flops
    memory_per_device: dict

    def to_dict(self):
        return dataclasses.asdict(self)


def memory_stats(compiled) -> dict:
    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
    }
    mem["total_bytes"] = (mem["argument_bytes"] + mem["output_bytes"]
                          + mem["temp_bytes"] - mem["alias_bytes"])
    return mem


def costs_of(compiled) -> dict:
    cost = compiled.cost_analysis() or {}   # None: backend reports none
    stats = parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_raw": stats.raw_bytes,
        "coll_modeled": stats.modeled_bytes,
        "coll_counts": stats.counts,
    }


def make_roofline(flops, bytes_accessed, coll_raw, coll_modeled, coll_counts,
                  mem, model_flops_per_device,
                  peak_flops: float = PEAK_FLOPS, hbm_bw: float = HBM_BW,
                  link_bw: float = LINK_BW) -> Roofline:
    compute_s = flops / peak_flops
    memory_s = bytes_accessed / hbm_bw
    collective_s = coll_modeled / link_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    return Roofline(
        flops=flops, bytes_accessed=bytes_accessed,
        coll_bytes_raw=coll_raw, coll_bytes_modeled=coll_modeled,
        coll_counts=coll_counts,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant,
        model_flops=model_flops_per_device,
        useful_ratio=(model_flops_per_device / flops) if flops else 0.0,
        memory_per_device=mem,
    )


def analyze(compiled, model_flops_per_device: float,
            peak_flops: float = PEAK_FLOPS, hbm_bw: float = HBM_BW,
            link_bw: float = LINK_BW) -> Roofline:
    c = costs_of(compiled)
    return make_roofline(c["flops"], c["bytes"], c["coll_raw"],
                         c["coll_modeled"], c["coll_counts"],
                         memory_stats(compiled), model_flops_per_device,
                         peak_flops, hbm_bw, link_bw)


def extrapolate_costs(base: dict, bigger: dict, l1: float, l2: float,
                      n_units: float) -> dict:
    """Linear-in-depth cost model from two unrolled compiles at depths
    l1 < l2: total(n) = intercept + n * slope, with slope from the diff.
    Collective counts are extrapolated the same way."""
    out = {}
    for k in ("flops", "bytes", "coll_raw", "coll_modeled"):
        slope = (bigger[k] - base[k]) / (l2 - l1)
        out[k] = max(base[k] - l1 * slope, 0.0) + n_units * slope
    counts = {}
    for kind in set(base["coll_counts"]) | set(bigger["coll_counts"]):
        c1 = base["coll_counts"].get(kind, 0)
        c2 = bigger["coll_counts"].get(kind, 0)
        slope = (c2 - c1) / (l2 - l1)
        counts[kind] = int(round(max(c1 - l1 * slope, 0) + n_units * slope))
    out["coll_counts"] = counts
    return out


def ssm_scan_correction(cfg, shape, n_chips: int) -> tuple[float, float]:
    """(extra_flops, extra_bytes) per device for the sequence-recurrence that
    XLA's cost model counts once (the scan body): modeled at the *chunked
    Pallas kernel*'s cost — state resident in VMEM, inputs streamed once.

    mamba1 per token per layer: dA exp + dBu + h-update + y=h·C ≈ 7·Di·N
    FLOPs; stream u,dt (fp32) + B,C + y ≈ (3·Di + 2·N)·4 bytes.
    mamba2: ≈ 6·Di·N FLOPs (scalar-A heads), same streaming shape.
    Sharding: Di over TP(16), tokens over DP — ≈ /n_chips overall.
    """
    if cfg.family not in ("ssm", "hybrid") or shape.mode == "decode":
        return 0.0, 0.0
    tokens = shape.seq_len * shape.global_batch
    Di, N = cfg.d_inner, cfg.ssm_state
    c = 7.0 if cfg.mamba_version == 1 else 6.0
    flops_tok_layer = c * Di * N
    bytes_tok_layer = (3 * Di + 2 * N) * 4.0
    mult = 3.0 if shape.mode == "train" else 1.0  # bwd ≈ 2x fwd re-scan
    total_flops = cfg.n_layers * tokens * flops_tok_layer * mult
    total_bytes = cfg.n_layers * tokens * bytes_tok_layer * mult
    return total_flops / n_chips, total_bytes / n_chips


def model_flops(cfg, shape, n_chips: int) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train (N = active params), 2·N·D forward
    (prefill), 2·N per token (decode) — per device.

    Encoder-decoder (audio): the encoder's params see `encoder_seq` frames
    per sample, not the decoder's token count — counted separately."""
    n_active = cfg.active_param_count()
    mult = {"train": 6.0, "prefill": 2.0, "decode": 2.0}[shape.mode]
    if cfg.family == "audio":
        D = cfg.d_model
        att = (D * cfg.n_heads * cfg.resolved_head_dim
               + 2 * D * cfg.n_kv_heads * cfg.resolved_head_dim
               + cfg.n_heads * cfg.resolved_head_dim * D)
        enc_params = cfg.n_encoder_layers * (att + 3 * D * cfg.d_ff + 2 * D)
        dec_params = n_active - enc_params
        if shape.mode == "decode":
            dec_tokens = shape.global_batch
            enc_tokens = 0  # encoder output precomputed in the cache
        else:
            dec_tokens = shape.seq_len * shape.global_batch
            enc_tokens = cfg.encoder_seq * shape.global_batch
        total = mult * (dec_params * dec_tokens + enc_params * enc_tokens)
        return total / n_chips
    if shape.mode == "decode":
        tokens = shape.global_batch
    else:
        tokens = shape.seq_len * shape.global_batch
    return mult * n_active * tokens / n_chips
