"""What a model family gives the serving stack, and what its cache is
made of: the ONE module the engine, the paged pool, the prefill tier and
`generate` ask. A family is a file of its own that ends in `FAMILY =
Family(...)` over the functions it has; nothing here, and no table or
import list anywhere, names one.

The cache (`init_cache(config, batch)`) is the family's own pytree, a
list of entries of four kinds, told apart by SHAPE and never by a
family's name. "k" and "v" `[B, rows, H, hd]`: keys and values with a
sequence axis. "k" ALONE `[B, rows, ...]`: one latent row a token, from
which keys and values are both made. Either with `rows` shorter than
`config.max_seq_len`, beside entries of the full length: a RING (the
token at position p lies in row `p mod rows`). Any other entry: a slot's
STATE, with no sequence axis, written whole. What the serving stack
takes for granted of full-length keys and values in pairs does not hold
for the other three, and is refused in words (`refuse`) rather than
served silently wrong.

A state need not be large, nor the cache's main part: a cache of
full-length keys and values IN PAIRS, one stack of one width, with a
state of KILOBYTES after them (`models/zaya.py`: the tails of two
convolutions that make a token's query from the token before it, 5.4 KB
a slot and layer beside 1 KB of keys and values a token) is of kind
"state" all the same. Its pairs would fit the paged pool's block, and a
prefix of them still cannot be resumed without the tails at that block,
which the pool does not keep: what a recurrence of megabytes is refused,
it is refused, in the same words.

The kinds combine. A cache that is latent AND ring holds latent rows
alone, some entries of the full length and some in rings: what either
kind is refused, it is refused, in words of its own. And the entries
under ONE row count may differ in WIDTH (latent rows beside the narrow
keys of an indexer, an entry each): a layer may leave more than one
entry, an entry is what is stacked, and a prefill hands the sequence
entries back one stack for each (rows, row shape) the cache shows
(`stacks`), never assuming the first entry's width of the others.

So may keys and values IN PAIRS stand beside a narrower entry WITHOUT
values under the same row count (an indexer's keys beside grouped-query
heads: a cache that is pairs AND index): a stack of it is in pairs or is
not (`paired_stacks`), the slab serves it as it serves any entry, and
what takes ONE stack of pairs for the whole cache (the paged pool, an
adoption, the transfer) is refused it in words of its own.
"""
from __future__ import annotations

import functools
import sys
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax


@dataclass(frozen=True)
class Family:
    """A family's functions. `forward_counted`: a `forward_cached` that
    hands back a third value, a dict of small counters of the run (the
    engine's admission record). `lora_targets(config)`: the leaves of
    every block's ["attn"] an adapter applies to, `((name, in, out),
    ...)`. `decode_walks`: the tick's attention walks
    each slot's rows up to its position, block by block
    (`ops/swa.decode_attention` over keys and values [B, S, G, d] of any
    G and whole 128-lane rows, several packed heads to a row among them:
    `models/gpt2.py` hands each as a query head with the other heads'
    lanes zeroed; `ops/mla.absorbed_attention` by positions over latent
    rows [B, S, width]), by the block `ops/swa.decode_block` gives for
    the slab's longest entry; a tick that reads every row, or rows the
    caller marks, does not.
    `state_walks`: the tick's state step visits the live slots alone:
    `decode` takes `live` [B], the tick's own liveness vector, beside its
    other arguments (`ops/mamba2.ssd_step`, `ops/kda.kda_step`)."""

    config_type: type
    init: Callable
    forward: Callable
    loss: Callable
    partition_specs: Callable
    init_cache: Callable
    forward_cached: Callable
    decode: Callable
    forward_counted: Optional[Callable] = None
    lora_targets: Optional[Callable] = None
    decode_walks: bool = False
    state_walks: bool = False


def family_of(config: Any, support: str = "generation") -> Family:
    """The record of the module that defines the config's class, or the
    nearest base of it that has one (a subclass defined elsewhere is
    served as its base is)."""
    for cls in type(config).__mro__:
        rec = getattr(sys.modules.get(cls.__module__), "FAMILY", None)
        if isinstance(rec, Family) and isinstance(config, rec.config_type):
            return rec
    raise TypeError(f"no {support} support for {type(config).__name__}")


def row_counts(cache) -> Dict[int, List[int]]:
    """The sequence entries of a cache by their rows, in the order the
    cache first shows each count: {rows: [entry indices]}."""
    by_rows: Dict[int, List[int]] = {}
    for i, blk in enumerate(cache):
        if "k" in blk:
            by_rows.setdefault(blk["k"].shape[1], []).append(i)
    return by_rows


def stacks(cache) -> Dict[Tuple[int, ...], List[int]]:
    """The sequence entries of a cache by what can be stacked: {(rows,
    *row shape): [entry indices]}, in the order the cache first shows
    each. One key for a cache of one row count and one width."""
    by_shape: Dict[Tuple[int, ...], List[int]] = {}
    for i, blk in enumerate(cache):
        if "k" in blk:
            by_shape.setdefault(tuple(blk["k"].shape[1:]), []).append(i)
    return by_shape


def _nbytes(tree, per: int) -> int:
    return sum(x.size * x.dtype.itemsize // per
               for x in jax.tree.leaves(tree))


class SlabSpec:
    """The layout of a family's cache for `batch` slots, from the shapes
    of `init_cache` alone (nothing is allocated): `slab_spec`."""

    def __init__(self, config: Any, batch: int):
        cache = jax.eval_shape(
            lambda: family_of(config).init_cache(config, batch))
        self.by_rows = row_counts(cache)
        self.stacks = stacks(cache)
        seq = [cache[i] for at in self.by_rows.values() for i in at]
        # "v" beside "k", stack by stack (a stack's entries are alike)
        # and for the whole cache
        self.paired_stacks = tuple("v" in cache[at[0]]
                                   for at in self.stacks.values())
        self.paired = all(self.paired_stacks)
        self.latent_only = len(seq) == len(cache) and all(
            len(blk) == 1 for blk in seq)
        self.state_bytes_per_slot = sum(
            _nbytes(blk, batch) for blk in cache if "k" not in blk)
        self.stateful = self.state_bytes_per_slot > 0
        self.kv_bytes_per_token = sum(
            _nbytes(blk, batch * blk["k"].shape[1]) for blk in seq)
        # kv_stats()["slab"]: for each row count, its layers and cost
        self.slab = [{"rows": rows, "layers": len(at), "bytes_per_slot": sum(
            _nbytes(cache[i], batch) for i in at)}
            for rows, at in self.by_rows.items()]
        # the longest entries' rows, and the first of them ("k": its
        # shape and dtype); the ring's rows, None without one
        self.rows = max(self.by_rows)
        self.longest = cache[self.by_rows[self.rows][0]]["k"]
        shortest = min(self.by_rows)
        self.ring_rows = shortest if shortest < config.max_seq_len else None
        self.kind = ("state" if self.stateful else "latent_ring"
                     if self.latent_only and self.ring_rows else "latent"
                     if self.latent_only else "pairs_index"
                     if any(self.paired_stacks) and not self.paired
                     else "ring" if self.ring_rows else None)
        # what a pool block, a cached prefix and a transfer are made of:
        # a stack [layers, n, *row_shape] of the first entry's rows (an
        # entry is a layer wherever such a stack is used). Only a cache
        # of ONE stack (`len(self.stacks) == 1`) is ever asked for it:
        # entries of several widths or row counts are a kind that
        # `refuse` turns away from every consumer of such a stack
        self.layers = len(cache)
        self.row_shape: Tuple[int, ...] = tuple(seq[0]["k"].shape[2:])
        self.dtype = seq[0]["k"].dtype

    def stack_shape(self, n: int) -> Tuple[int, ...]:
        return (self.layers, n) + self.row_shape


slab_spec = functools.lru_cache(maxsize=64)(SlabSpec)
_KINDS = {
    "state": "this family's slots own recurrent state: ",
    "latent": ("this family's cache holds one latent row a token and no "
               "values: "),
    "ring": ("this family's cache holds a ring (layers that keep only their "
             "last rows, fewer than max_seq_len): "),
    "latent_ring": ("this family's cache holds one latent row a token and "
                    "no values, some of them in rings (layers that keep "
                    "only their last rows, fewer than max_seq_len): "),
    "pairs_index": ("this family's cache holds keys and values in pairs "
                    "and, beside them under the same row count, an "
                    "indexer's keys with no values (a narrower entry of "
                    "its own a layer): "),
}
_ENDS = {
    "prefix_cache": " (prefix_cache=True)",
    "speculate_k": " (speculate_k={k})",
    "lora_pool": " (lora_pool)",
    "adopt_prefill": " (adopt_prefill)",
    "transfer": (", so it cannot be served disaggregated "
                 "(engine.adopt_prefill refuses it too)"),
}
_WHY = {
    ("state", "prefix_cache"):
        "a block-aligned prefix of keys and values cannot resume a "
        "recurrence without a snapshot of the state at that block, which "
        "the pool does not keep",
    ("state", "speculate_k"):
        "a rejected draft's rows need no copy-back, but a state the draft "
        "has advanced cannot be un-advanced",
    ("state", "lora_pool"):
        "the adapter pool's targets are attention projections of every "
        "block, and the per-tenant prefix namespaces need the prefix pool",
    ("state", "adopt_prefill"):
        "an adoption carries ck/cv rows only, and a prefill replica has no "
        "way to hand over the state its prefill ended in",
    ("state", "transfer"): "a transfer carries ck/cv rows only",
    ("latent", "prefix_cache"):
        "the paged pool sizes a block [heads, head_dim] from a key tensor "
        "and commits keys and values side by side, and has no block of one "
        "latent row",
    ("latent", "speculate_k"):
        "the pool proposer drafts from the paged pool's token chains, "
        "which this cache has none of, and the family's decode has no "
        "[B, k+1] verify form",
    ("latent", "lora_pool"):
        "the adapter pool's per-tenant prefix namespaces are the paged "
        "pool's, and its targets are the attention projections of the "
        "families it knows",
    ("latent", "adopt_prefill"):
        "an adoption carries ck and cv rows in pairs, as the paged pool and "
        "the transfer between replicas speak them, and there are no values "
        "to carry",
    ("latent", "transfer"):
        "a transfer carries ck and cv rows in pairs and the prefill tier's "
        "pool commits them side by side",
    ("ring", "prefix_cache"):
        "a block-aligned prefix cannot be resumed where layers have "
        "forgotten all but their last {rows} rows, and the paged pool has "
        "one block shape and one length for every layer",
    ("ring", "speculate_k"):
        "a rejected draft's rows need no copy-back only while they stay "
        "masked, and in a ring they have overwritten rows the window still "
        "sees; the pool proposer drafts from the paged pool's token chains, "
        "which this cache has none of",
    ("ring", "lora_pool"):
        "the adapter pool's per-tenant prefix namespaces are the paged "
        "pool's, which this cache cannot have",
    ("ring", "adopt_prefill"):
        "an adoption carries ONE stack of ck and cv rows of the prompt's "
        "length, as the paged pool and the transfer between replicas speak "
        "them, and a ring's {rows} rows are a stack of their own, each row "
        "at its position mod the ring",
    ("ring", "transfer"):
        "a transfer carries ONE stack of ck and cv rows of the prompt's "
        "length and the prefill tier's pool has one block shape",
    ("latent_ring", "prefix_cache"):
        "the paged pool has one block shape [heads, head_dim] and one "
        "length for every layer, keys and values side by side: no block of "
        "one latent row, none of a narrower entry beside it, and no prefix "
        "to resume where layers have forgotten all but their last {rows} "
        "rows",
    ("latent_ring", "speculate_k"):
        "a rejected draft's rows in a ring have overwritten rows the "
        "window still sees, the pool proposer drafts from the paged pool's "
        "token chains, which this cache has none of, and the family's "
        "decode has no [B, k+1] verify form",
    ("latent_ring", "lora_pool"):
        "the adapter pool's per-tenant prefix namespaces are the paged "
        "pool's, which this cache cannot have, and its targets are the "
        "attention projections of the families it knows",
    ("latent_ring", "adopt_prefill"):
        "an adoption carries ONE stack of ck and cv rows in pairs, of the "
        "prompt's length, and here there are no values to carry, entries "
        "of several widths, and a ring's {rows} rows are a stack of their "
        "own, each row at its position mod the ring",
    ("latent_ring", "transfer"):
        "a transfer carries ONE stack of ck and cv rows in pairs, of the "
        "prompt's length, and the prefill tier's pool has one block shape",
    ("pairs_index", "prefix_cache"):
        "the paged pool has ONE block shape [heads, head_dim], keys and "
        "values side by side, and no block of an index key; and a prompt "
        "resumed behind a cached prefix must score the prefix's rows too, "
        "which the pool does not keep the index keys of",
    ("pairs_index", "speculate_k"):
        "the pool proposer drafts from the paged pool's token chains, "
        "which this cache has none of, and the family's decode has no "
        "[B, k+1] verify form (each drafted token would select rows of its "
        "own)",
    ("pairs_index", "lora_pool"):
        "the adapter pool's per-tenant prefix namespaces are the paged "
        "pool's, which this cache cannot have, and its targets are the "
        "attention projections of the families it knows, not an indexer's",
    ("pairs_index", "adopt_prefill"):
        "an adoption carries ONE stack of ck and cv rows in pairs, as the "
        "paged pool and the transfer between replicas speak them, and the "
        "index keys are a second stack, of another width and with no "
        "values, without which the first tick cannot select",
    ("pairs_index", "transfer"):
        "a transfer carries ONE stack of ck and cv rows in pairs and the "
        "prefill tier's pool has one block shape: the index keys would be "
        "left behind",
}


def refuse(spec: SlabSpec, capability: str, asked: Any = True, **detail
           ) -> None:
    """The ValueError that names why a cache of `spec.kind` cannot give
    `capability`, where it was `asked` for (an option left to its
    default asks for nothing: `prefix_cache=None` simply builds no
    pool). Nothing for a cache of full-length keys and values."""
    if asked and spec.kind is not None:
        raise ValueError((_KINDS[spec.kind] + _WHY[spec.kind, capability]
                          + _ENDS[capability]).format(
                              rows=spec.ring_rows, **detail))
