"""FLOPs accounting: the numerator and denominator of MFU.

MFU (model FLOPs utilization) is the headline comparison metric of the
Gemma-on-TPU technical report (PAPERS.md): achieved model FLOP/s over the
chip generation's peak. This module provides both sides:

- numerator: analytic ``6·N`` training FLOPs per token for the model
  families in ``ray_tpu.models`` (plus the attention score/value term the
  6N rule misses), or the exact per-execution FLOPs XLA reports through
  ``Compiled.cost_analysis()`` when available;
- denominator: a per-generation bf16 peak-FLOPs table (public spec
  sheets), with a documented nominal constant for non-TPU backends so
  off-silicon test runs still produce a meaningful (relative) number.
"""
from __future__ import annotations

from typing import Any, Optional

# bf16 peak FLOP/s per chip by device kind (public spec sheets). The
# longest-prefix match wins so "TPU v5 lite" resolves before "TPU v5".
PEAK_FLOPS_BF16 = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,  # v6e / Trillium
    "TPU v6e": 918e12,
}

# Nominal peaks for non-TPU backends: MFU off-silicon is only meaningful
# as a relative series (regression tracking in tier-1 / CI), so the
# constants just need to be stable and documented, not precise.
NOMINAL_PEAK_FLOPS = {
    "cpu": 5e11,
    "gpu": 312e12,  # A100-class bf16, the reference comparison point
}


def device_peak_flops(device: Any = None) -> float:
    """bf16 peak FLOP/s of one device (jax Device or None for the first
    local device). A TPU whose device_kind is not in the table raises: an
    assumed peak would put a wrong denominator under every MFU."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or ""
    for name, peak in sorted(PEAK_FLOPS_BF16.items(),
                             key=lambda kv: -len(kv[0])):
        if kind.startswith(name):
            return peak
    platform = getattr(device, "platform", "") or ""
    if platform == "tpu":
        raise ValueError(
            f"no bf16 peak for TPU device_kind {kind!r}: add it to "
            "observability.flops.PEAK_FLOPS_BF16 with its source")
    return NOMINAL_PEAK_FLOPS.get(platform, NOMINAL_PEAK_FLOPS["cpu"])


def total_peak_flops(devices) -> float:
    """Aggregate bf16 peak over a device collection (e.g. mesh.devices)."""
    import numpy as np

    flat = np.asarray(devices).reshape(-1)
    return float(sum(device_peak_flops(d) for d in flat))


# ------------------------------------------------------------- analytic 6N

def param_count(cfg: Any) -> int:
    """Analytic parameter count for a ``ray_tpu.models`` config
    (GPT2Config / LlamaConfig / MoEConfig). For MoE this is the ACTIVE
    parameter count (top_k experts), which is what the 6N rule wants."""
    name = type(cfg).__name__
    if name == "GPT2Config":
        return (cfg.padded_vocab * cfg.d_model          # wte (tied head)
                + cfg.max_seq_len * cfg.d_model         # wpe
                + cfg.num_layers * 12 * cfg.d_model * cfg.d_model)
    if name in ("LlamaConfig", "MoEConfig"):
        d, L = cfg.d_model, cfg.num_layers
        kv_dim = cfg.num_kv_heads * cfg.head_dim
        attn = d * d + 2 * d * kv_dim + d * d           # q, kv, o
        if name == "MoEConfig":
            mlp = cfg.top_k * 3 * d * cfg.d_ff          # active experts
        else:
            mlp = 3 * d * cfg.d_ff                      # gate/up/down
        return cfg.padded_vocab * d + L * (attn + mlp)
    raise TypeError(f"no analytic parameter count for {name}; pass a "
                    "ray_tpu.models config or use params_size()")


def params_size(params: Any) -> int:
    """Parameter count of an actual pytree (model-agnostic fallback —
    counts TOTAL parameters, so MoE models overcount vs. active)."""
    import jax

    return int(sum(x.size for x in jax.tree.leaves(params)
                   if hasattr(x, "size")))


def attn_flops_per_token(cfg: Any, seq: Optional[int] = None,
                         causal: bool = True) -> float:
    """Attention score/value FLOPs per token the 6N rule misses:
    2 matmuls (QK^T, PV) x 2·d·T each, fwd+bwd = 3x, halved causal."""
    seq = seq or cfg.max_seq_len
    per = 12.0 * cfg.num_layers * cfg.d_model * seq
    return per / 2 if causal else per


def train_flops_per_token(cfg: Any, seq: Optional[int] = None,
                          causal: bool = True) -> float:
    """Training (fwd+bwd) FLOPs per token: 6·N plus the attention term."""
    return 6.0 * param_count(cfg) + attn_flops_per_token(cfg, seq, causal)


# ------------------------------------------------------ XLA cost analysis

def compiled_flops(compiled: Any) -> Optional[float]:
    """Per-execution FLOPs from an XLA ``Compiled.cost_analysis()``, or
    None when the backend doesn't report them. Normalizes the two
    historical return shapes (dict vs. list-of-dicts)."""
    try:
        cost = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend without cost analysis
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    try:
        flops = float(cost.get("flops", 0.0))
    except (AttributeError, TypeError, ValueError):
        return None
    return flops if flops > 0 else None


def mfu(flops_per_step: Optional[float], step_seconds: float,
        peak_flops_total: Optional[float]) -> Optional[float]:
    """Achieved / peak model FLOP/s, or None when either side is unknown."""
    if not flops_per_step or not peak_flops_total or step_seconds <= 0:
        return None
    return flops_per_step / step_seconds / peak_flops_total
