"""What a ZAYA1 decode tick moves, and which device operations of a
program are its attention's: beside `harness/roofline.py`, from the sizes
the family's `shape()` gives and from what a run met.

  the decode tick (`tick_bytes`): the whole `_tick` program against its
    memory roofline, each byte counted ONCE and at the width it is served
    in, so a share computed from it is a lower reading and cannot pass
    100% by over-counting: for each expert that got a row its three
    matrices (`moe_experts_hit`, summed over the layers, times
    `expert_params` in bf16: a top-1 tick of 64 slots misses an expert of
    16 now and then, and an expert with no row is not read); every
    layer's other weights (`layer_bytes`: the attention's in bf16, the
    router's in float32, the norms and residual vectors) and the head
    (`head_bytes`: the tied embedding ONCE; a tick gathers a row a slot
    besides); for each live slot its `position` rows of keys and values
    (`live_rows`, `row_bytes` a row over the layers) and its state, the
    convolutions' tails and the last token's half-values, read AND
    written (`state_bytes`). The program steps every slot's tails, walks
    a parked slot's one block and routes a dead slot's token: what is
    not live counts nothing here and reads as lost time.
  the attention's operations (`scoped_seconds`): the trace names a device
    operation by its HLO instruction (`fusion.12`, `gqa_decode_t1.3`) and
    the harness keeps no more of an event than that. The program names
    its sublayers with `jax.named_scope` (`cca`, `router`, `moe`,
    `head`), which XLA carries on every instruction of the COMPILED
    program as `metadata={op_name=".../cca/..."}`. So the program is
    lowered and compiled again here, after the window, from the shapes
    of the cell alone, which finds the set-up's own executable in JAX's
    compilation cache, and its text gives {instruction: scope}; an
    operation event belongs to the program event whose interval holds
    its start (`harness/program_ops.py`). A fusion carries the scope of
    its root, so an elementwise operation XLA fuses across a scope's
    edge (a residual sum into the next norm) is counted on the side of
    the fusion's root: a few microseconds a layer either way.
"""
from __future__ import annotations

import functools
import json
import re
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.harness import program_ops
from benchmarks.harness.common import log

Event = Tuple[str, float, float]
WEIGHT_BYTES = 2
SCOPES = ("cca", "router", "moe", "head")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")
_PROMPT_KERNEL = re.compile(r"gqa_prefill_w\d+_t(\d+)")


def tick_bytes(shape: Dict[str, Any], experts_hit: float, live_slots: float,
               live_rows: float) -> float:
    """The least one decode tick moves (module docstring).
    `experts_hit`: over all layers."""
    return (experts_hit * shape["expert_params"] * WEIGHT_BYTES
            + shape["layers"] * shape["layer_bytes"] + shape["head_bytes"]
            + live_rows * shape["row_bytes"]
            + 2.0 * live_slots * shape["state_bytes"])


def scopes_of(text: str) -> Dict[str, str]:
    """{HLO instruction: the first of `SCOPES` on its op_name's path} of a
    compiled program's text; an instruction under none is left out."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        scope = next((p for p in m.group(2).split("/") if p in SCOPES),
                     None)
        if scope is not None:
            out[m.group(1)] = scope
    return out


def _abstract(tree: Any) -> Any:
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def program_scopes(cell: Dict[str, Any], tokens: Optional[int] = None
                   ) -> Dict[str, str]:
    """`scopes_of` the engine's `_tick` for this cell (`tokens` None) or
    of its `_prefill_paged` over a prompt of `tokens`, compiled from
    shapes alone, once a process; {} where it cannot be had."""
    mix = cell["traffic"]
    return _compiled_scopes(json.dumps(cell["conf"], sort_keys=True),
                            int(mix["max_seq_len"]), int(mix["max_batch"]),
                            tokens)


@functools.lru_cache(maxsize=16)
def _compiled_scopes(conf_json: str, max_seq_len: int, batch: int,
                     tokens: Optional[int]) -> Dict[str, str]:
    import jax
    import jax.numpy as jnp

    conf = json.loads(conf_json)
    try:
        from benchmarks.harness.configs import init_params, program_config
        from ray_tpu.models import engine
        from ray_tpu.models.family import family_of, slab_spec

        cfg = program_config(conf, max_seq_len)
        params = _abstract(jax.eval_shape(
            lambda: init_params(conf, cfg, 0)))
        if tokens is None:
            cache = _abstract(jax.eval_shape(
                lambda: family_of(cfg).init_cache(cfg, batch)))
            vec = jax.ShapeDtypeStruct((batch,), jnp.int32)
            lowered = engine._tick.lower(params, cfg, cache, vec, vec, vec)
        else:
            spec = slab_spec(cfg, 1)
            empty = jax.ShapeDtypeStruct(spec.stack_shape(0), spec.dtype)
            lowered = engine._prefill_paged.lower(
                params, jax.ShapeDtypeStruct((1, int(tokens)), jnp.int32),
                cfg, empty, empty)
        return scopes_of(lowered.compile().as_text())
    except Exception as e:  # noqa: BLE001 - a reader returns None instead
        log(f"zaya_cost: no compiled text of the program ({tokens}): "
            f"{type(e).__name__}: {str(e)[:200]}")
        return {}


def scoped_seconds(events: List[Event], scopes: Dict[str, str], scope: str
                   ) -> float:
    return sum(d for name, _s, d in events
               if scopes.get(name) == scope) / 1e9


def tick_share(obs: Dict[str, Any], scope: str) -> Optional[float]:
    """The device time of the `scope` operations inside the whole `_tick`
    events of the traced window over those events' own, in per cent."""
    trace = obs.get("trace")
    ticks = program_ops.whole_programs(trace, "_tick") if trace else []
    if not ticks:
        return None
    scopes = program_scopes(obs["cell"])
    if not scopes:
        return None
    ops = [ev for evs in trace["ops"].values() for ev in evs]
    inside = sum(scoped_seconds(program_ops.inside(ops, t), scopes, scope)
                 for t in ticks)
    total = sum(d for _n, _s, d in ticks) / 1e9
    log(f"zaya_cost: {scope} {1e3 * inside / len(ticks):.3f} ms of "
        f"{1e3 * total / len(ticks):.3f} ms a tick over {len(ticks)} ticks")
    return 100.0 * inside / total if inside else None


def prompt_lengths_of(prefills: List[List[Event]], lengths: List[int],
                      kernel_lengths: List[int]) -> List[Optional[int]]:
    """The prompt length of each prefill (its operations): the one the
    prompt kernel carries in its name; a prefill without the kernel (a
    prompt of at most one block: the plain form) is of the cell's ONE
    length that never took the kernel (`kernel_lengths`: those that
    did), if there is exactly one such."""
    plain = sorted(set(lengths) - set(kernel_lengths))
    out: List[Optional[int]] = []
    for ops in prefills:
        named = next((int(m.group(1)) for name, _s, _d in ops
                      for m in [_PROMPT_KERNEL.search(name)] if m), None)
        out.append(named if named is not None
                   else plain[0] if len(plain) == 1 else None)
    return out


def prefill_scope_ms_per_ktok(obs: Dict[str, Any], scope: str
                              ) -> Optional[float]:
    """Device milliseconds of the `scope` operations a 1,000 prompt tokens,
    over the whole `_prefill_paged` events of the traced window whose
    prompt length can be told."""
    trace = obs.get("trace")
    events = program_ops.whole_programs(trace, "_prefill_paged") \
        if trace else []
    if not events:
        return None
    from benchmarks.harness.traffic import prompt_lengths

    ops = [ev for evs in trace["ops"].values() for ev in evs]
    inside = [program_ops.inside(ops, e) for e in events]
    from ray_tpu.ops import dispatch

    lengths = prompt_lengths_of(
        inside, prompt_lengths(obs["cell"]["traffic"]),
        [c["shape"][1] for c in dispatch.kernel_choices("gqa_prefill")
         if c["choice"] == "pallas"])
    took = tokens = 0.0
    for evs, n in zip(inside, lengths):
        scopes = program_scopes(obs["cell"], n) if n else {}
        if scopes:
            took += scoped_seconds(evs, scopes, scope)
            tokens += n
    if not took:
        return None
    log(f"zaya_cost: {scope} {1e3 * took:.2f} ms over {tokens:.0f} prompt "
        f"tokens of {sum(n is not None for n in lengths)} prefills")
    return 1e3 * took / (tokens / 1e3)
