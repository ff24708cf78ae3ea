"""What the Granite hybrid's scan and tick need, and which device
operations of a prefill are the scan's and the expert layer's: beside
`harness/roofline.py`, from the sizes the family's `shape()` gives and
from what a run met.

  the chunked scan (`scan_flops`, `scan_bytes`): the operations of the
    chunked form of the Mamba-2 recurrence at the FILE's chunk Q, whatever
    implements it. A chunk and head (head size P, state size N): `2 Q^2 P`
    for the output inside the chunk, `2 Q P N` for what the carried state
    gives the outputs and `2 Q P N` for what the chunk's inputs give the
    state; a chunk and group: `2 Q^2 N` for C . B. A prompt of T tokens is
    T / Q chunks (a ragged last chunk counts its real share: what the
    form needs, not what a padded lowering spends), in each Mamba layer.
    The decay terms, the cumulative sums and the `D x` term are
    elementwise and counted nowhere: a lower reading, which cannot pass
    100% by over-counting. The bytes: x' and y [H, P], dt [H], B and C [G,
    N] a token, at the width each is served in (the inputs bf16, dt and y
    float32), and the float32 state in and out once a prompt and layer.
  the decode tick (`tick_bytes`): the whole `_tick` program against its
    memory roofline, each byte counted ONCE and at the width it is served
    in: for each expert that got a row its three matrices
    (`moe_experts_hit`, summed over the layers, times `expert_params` in
    bf16: an expert with no row is not read); the weights every tick reads
    whatever is routed (`dense_bytes`: the mixers, the routers, the shared
    MLPs, the norms) and the head (`head_bytes`: the tied embedding ONCE);
    for each live slot its `position` rows of keys and values
    (`live_rows`, `row_bytes` a row) and its state and tails read AND
    written (`state_bytes`). What is not live counts nothing here and
    reads as lost time.
  a scope's operations (`prefill_scope_seconds`): the trace names a device
    operation by its HLO instruction and the program names its sublayers
    with `jax.named_scope` (`SCOPES`), which XLA carries on every
    instruction of the COMPILED program as `op_name`; the program is
    lowered and compiled again here after the window, from the shapes of
    the cell alone (which finds the set-up's own executable in JAX's
    compilation cache), as `harness/zaya_cost.py` does and with its
    helpers. `ssd_scan` lies INSIDE `mamba2`: an instruction under both is
    the scan's. A fusion carries the scope of its root.
"""
from __future__ import annotations

import functools
import json
import re
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.harness import program_ops
from benchmarks.harness.common import log
from benchmarks.harness.zaya_cost import (_INSTRUCTION, _abstract,
                                          prompt_lengths_of,
                                          scoped_seconds)

Event = Tuple[str, float, float]
WEIGHT_BYTES = 2
# the innermost first: an instruction under several is the first's here
SCOPES = ("ssd_scan", "moe", "shared_mlp", "mamba2", "attention", "head")
_CONTAINER = re.compile(r"\s(?:while|conditional|call)\(")


def scan_flops(shape: Dict[str, Any], tokens: float) -> float:
    """The chunked form's operations over `tokens` of a prompt, all Mamba
    layers (module docstring)."""
    q, p, n = (shape["scan_chunk"], shape["scan_head_dim"],
               shape["scan_state"])
    a_chunk = (shape["scan_heads"] * (2.0 * q * q * p + 4.0 * q * p * n)
               + shape["scan_groups"] * 2.0 * q * q * n)
    return shape["scan_layers"] * a_chunk * tokens / q


def scan_bytes(shape: Dict[str, Any], tokens: float, prompts: float = 1.0
               ) -> float:
    """What the scan must move over `tokens` of `prompts` prompts, all
    Mamba layers (module docstring)."""
    h, p, n = (shape["scan_heads"], shape["scan_head_dim"],
               shape["scan_state"])
    a_token = (WEIGHT_BYTES * (h * p + 2 * shape["scan_groups"] * n)
               + 4 * h + 4 * h * p)
    return shape["scan_layers"] * (tokens * a_token
                                    + prompts * 2.0 * 4 * h * p * n)


def tick_bytes(shape: Dict[str, Any], experts_hit: float, live_slots: float,
               live_rows: float) -> float:
    """The least one decode tick moves (module docstring).
    `experts_hit`: over all layers."""
    return (experts_hit * shape["expert_params"] * WEIGHT_BYTES
            + shape["dense_bytes"] + shape["head_bytes"]
            + live_rows * shape["row_bytes"]
            + 2.0 * live_slots * shape["state_bytes"])


def scopes_of(text: str) -> Dict[str, str]:
    """{HLO instruction: the first of `SCOPES` on its op_name's path} of a
    compiled program's text; an instruction under none is left out, and
    so is a loop, a conditional or a call: the trace holds an event for
    it AND one for each operation of its body (the scan's loop over a
    block's chunks lies under `ssd_scan` with everything it runs), and
    the body's events carry the time."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or _CONTAINER.search(line):
            continue
        path = m.group(2).split("/")
        scope = next((s for s in SCOPES if s in path), None)
        if scope is not None:
            out[m.group(1)] = scope
    return out


def prefill_scopes(cell: Dict[str, Any], tokens: int) -> Dict[str, str]:
    """`scopes_of` the engine's `_prefill_paged` over a prompt of
    `tokens` for this cell, compiled from shapes alone, once a process;
    {} where it cannot be had."""
    mix = cell["traffic"]
    return _compiled_scopes(json.dumps(cell["conf"], sort_keys=True),
                            int(mix["max_seq_len"]), int(tokens))


@functools.lru_cache(maxsize=16)
def _compiled_scopes(conf_json: str, max_seq_len: int, tokens: int
                     ) -> Dict[str, str]:
    import jax
    import jax.numpy as jnp

    conf = json.loads(conf_json)
    try:
        from benchmarks.harness.configs import init_params, program_config
        from ray_tpu.models import engine
        from ray_tpu.models.family import slab_spec

        cfg = program_config(conf, max_seq_len)
        params = _abstract(jax.eval_shape(
            lambda: init_params(conf, cfg, 0)))
        spec = slab_spec(cfg, 1)
        empty = jax.ShapeDtypeStruct(spec.stack_shape(0), spec.dtype)
        lowered = engine._prefill_paged.lower(
            params, jax.ShapeDtypeStruct((1, tokens), jnp.int32), cfg,
            empty, empty)
        return scopes_of(lowered.compile().as_text())
    except Exception as e:  # noqa: BLE001 - a reader returns None instead
        log(f"granite_hybrid_cost: no compiled text of the prefill "
            f"({tokens}): {type(e).__name__}: {str(e)[:200]}")
        return {}


def prefill_scope_seconds(obs: Dict[str, Any], scope: str
                          ) -> Optional[Tuple[float, float, float, int]]:
    """Over the whole `_prefill_paged` events of the traced window whose
    prompt length can be told (the prompt kernel carries it in its name):
    (device seconds of the `scope` operations inside them, those events'
    own seconds, their prompt tokens, how many they are). None without a
    device trace, without such an event, or against a program that names
    no such scope."""
    trace = obs.get("trace")
    events = program_ops.whole_programs(trace, "_prefill_paged") \
        if trace else []
    if not events:
        return None
    from benchmarks.harness.traffic import prompt_lengths
    from ray_tpu.ops import dispatch

    ops = [ev for evs in trace["ops"].values() for ev in evs]
    inside = [program_ops.inside(ops, e) for e in events]
    lengths = prompt_lengths_of(
        inside, prompt_lengths(obs["cell"]["traffic"]),
        [c["shape"][1] for c in dispatch.kernel_choices("gqa_prefill")
         if c["choice"] == "pallas"])
    whole = tokens = 0.0
    count = 0
    by_scope = dict.fromkeys(SCOPES, 0.0)
    for event, evs, n in zip(events, inside, lengths):
        scopes = prefill_scopes(obs["cell"], n) if n else {}
        if scope in scopes.values():
            for name in SCOPES:
                by_scope[name] += scoped_seconds(evs, scopes, name)
            whole += event[2] / 1e9
            tokens += n
            count += 1
    if not by_scope[scope]:
        return None
    log(f"granite_hybrid_cost: of {1e3 * whole:.2f} ms over {tokens:.0f} "
        f"prompt tokens of {count} prefills, by scope: " + ", ".join(
            f"{name} {1e3 * s:.2f}" for name, s in by_scope.items()))
    return by_scope[scope], whole, tokens, count


def prefill_share(obs: Dict[str, Any], scope: str) -> Optional[float]:
    """The `scope` operations' share of those prefills' device time, in
    per cent."""
    met = prefill_scope_seconds(obs, scope)
    return None if met is None else 100.0 * met[0] / met[1]
