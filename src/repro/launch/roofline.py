"""Roofline-term extraction from compiled dry-run artifacts.

  compute term    = HLO_FLOPs / (chips x peak bf16 FLOP/s)
  memory term     = HLO_bytes / (chips x peak HBM bytes/s)
  collective term = collective_bytes / (chips x ICI link bytes/s)

with the peaks of the target chip's ``device_kind`` (``launch/mesh.py``).

HLO_FLOPs / bytes come from ``compiled.cost_analysis()`` (the partitioned
SPMD module -> per-device numbers; we multiply back to global).
collective_bytes is parsed from the compiled HLO text: the result bytes of
every all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute (async ``-start`` counted once, ``-done`` skipped).
"""
from __future__ import annotations

import re

from repro.launch.mesh import chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\(?[^=]*?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind result bytes (per device, SPMD module)."""
    out: dict[str, int] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_types, kind, _ = m.groups()
        b = shape_bytes(result_types)
        out[kind] = out.get(kind, 0) + b
        counts[kind] = counts.get(kind, 0) + 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def roofline_terms(cost: dict, coll: dict, n_chips: int,
                   model_flops: float = 0.0,
                   memory_bytes_analytic: float = 0.0, *,
                   device_kind: str) -> dict:
    """cost: per-device cost_analysis dict. Terms are in SECONDS, against
    the peaks of one ``device_kind`` chip.

    Two memory terms are reported: ``memory_s`` from HLO 'bytes accessed'
    (an UNFUSED upper bound — the CPU-backend HLO counts every
    instruction's operands, while TPU fusion keeps flash-attention tiles
    etc. in VMEM) and ``memory_analytic_s`` from the fusion-aware model
    (weights + optimizer + layer-boundary activations + collective
    buffers). Dominance uses the analytic term when available."""
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll_dev = float(coll["total_bytes"])
    peaks = chip_peaks(device_kind)
    compute_s = flops_dev / peaks["bf16_flops"]
    memory_s = bytes_dev / peaks["hbm_bw"]
    memory_analytic_s = memory_bytes_analytic / peaks["hbm_bw"]
    collective_s = coll_dev / peaks["ici_link_bw"]
    mem_for_bound = memory_analytic_s if memory_bytes_analytic else memory_s
    terms = {"compute": compute_s, "memory": mem_for_bound,
             "collective": collective_s}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    useful = model_flops / (flops_dev * n_chips) if flops_dev else 0.0
    return {
        "compute_s": compute_s, "memory_s": memory_s,
        "memory_analytic_s": memory_analytic_s,
        "collective_s": collective_s,
        "dominant": dom,
        "hlo_flops_per_device": flops_dev,
        "hlo_gflops_global": flops_dev * n_chips / 1e9,
        "hlo_bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "model_flops": model_flops,
        "useful_flops_ratio": useful,
        "roofline_fraction": (compute_s / bound) if bound else 0.0,
    }


def lm_model_flops(cfg, shape) -> float:
    """6*N_active*D convention (D = tokens). Decode/prefill use the 2*N*D
    inference convention; attention-score FLOPs reported separately by the
    HLO numbers."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token / request


def lm_memory_bytes(cfg, shape, n_chips: int, model_shards: int = 16) -> float:
    """Fusion-aware per-device HBM traffic estimate for one step.

    Counts: optimizer state read+write (fp32 m/v + params, train only),
    gathered bf16 weights streamed fwd(+remat)+bwd, layer-boundary
    activations (flash attention keeps scores in VMEM), logits chunks,
    KV-cache traffic for serving."""
    n_active = cfg.active_param_count()
    n_total = cfg.param_count()
    dp_shards = n_chips // model_shards
    B = shape.global_batch
    B_loc = max(B // dp_shards, 1)
    S = shape.seq_len
    d, ff, L = cfg.d_model, (cfg.d_ff_expert if cfg.moe else cfg.d_ff), cfg.n_layers

    if shape.kind == "train":
        opt_traffic = n_total * 4 * 6 / n_chips        # read+write p/m/v fp32
        weight_stream = n_active * 2 * 3 / model_shards  # bf16, fwd+remat+bwd
        act_unit = B_loc * S * 2.0                       # bf16 token-row
        ff_width = ff * (cfg.top_k + cfg.n_shared_experts) if cfg.moe else ff
        acts = act_unit * L * (6 * d + 3 * ff_width / model_shards) * 3
        logits = B_loc * S * cfg.vocab_size / model_shards * 4 * 2
        return opt_traffic + weight_stream + acts + logits
    if shape.kind == "prefill":
        weight_stream = n_active * 2 / model_shards
        act_unit = B_loc * S * 2.0
        ff_width = ff * (cfg.top_k + cfg.n_shared_experts) if cfg.moe else ff
        acts = act_unit * L * (6 * d + 3 * ff_width / model_shards)
        kv = B_loc * S * cfg.kv_dim * 2 * 2 * L / model_shards
        return weight_stream + acts + kv
    # decode: weights (TP-sharded, replicated over data) + KV cache read
    weight_stream = n_active * 2 / model_shards
    kv = B_loc * S * cfg.kv_dim * 2 * 2 * L / model_shards
    return weight_stream + kv
