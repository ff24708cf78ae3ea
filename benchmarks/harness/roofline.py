"""Operations and bytes the mathematics of a kernel call needs, from its
shapes, and the least time a chip with the given peaks could take.

Recomputation is not counted (as in MFU): the flash backward's second pass
over the scores and the fused cross-entropy's recomputed logits are the
implementation's way to save memory, not work the result needs. A share
computed from these numbers is therefore a lower reading, and cannot pass
100% through double counting."""
from __future__ import annotations

from typing import Dict, Tuple


def flash_attention_cost(batch_heads: int, seq: int, head_dim: int,
                         *, causal: bool = True, backward: bool = True,
                         bytes_per_el: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of attention over `batch_heads` independent
    [seq, head_dim] heads. Forward: Q.K^T and P.V, 2*seq*seq*head_dim
    multiply-adds each, so 4*seq^2*head_dim operations. Backward: dV, dP, dQ,
    dK, four such products, 8*seq^2*head_dim. A causal mask halves both.
    Bytes: forward reads Q, K, V and writes O; backward reads Q, K, V, O,
    dO and writes dQ, dK, dV; plus one float32 row statistic each way."""
    sq = float(seq) * seq * head_dim
    ops = 4.0 * sq + (8.0 * sq if backward else 0.0)
    if causal:
        ops /= 2.0
    tensor = float(seq) * head_dim * bytes_per_el
    nbytes = 4.0 * tensor + 4.0 * seq
    if backward:
        nbytes += 8.0 * tensor + 2.0 * 4.0 * seq
    return batch_heads * ops, batch_heads * nbytes


def fused_ce_cost(rows: int, d_model: int, vocab: int, *,
                  backward: bool = True, bytes_per_el: int = 2
                  ) -> Tuple[float, float]:
    """(operations, bytes) of linear + cross-entropy over `rows` tokens:
    logits = x.W^T is 2*rows*vocab*d_model; backward adds dx and dW, the
    same each. Bytes: x and W read (twice with a backward), dx and dW
    written, targets and per-row loss."""
    mm = 2.0 * rows * vocab * d_model
    ops = mm * (3.0 if backward else 1.0)
    x = float(rows) * d_model * bytes_per_el
    w = float(vocab) * d_model * bytes_per_el
    nbytes = x + w + 8.0 * rows
    if backward:
        nbytes += 2.0 * (x + w)
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peaks: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The larger of ops/peak FLOP/s and bytes/peak bytes/s, and which
    of the two it is (`compute` or `memory`)."""
    t_ops = ops / peaks["flops_bf16"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
