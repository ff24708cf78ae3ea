"""`models/family.py`: a family is one record found from its config's
class, the slab's layout is learnt once from shapes, and every refusal is
one row of one table. The last test serves a family this file defines,
with no edit to the package."""
from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu.models as models
from ray_tpu.models import gpt2
from ray_tpu.models.engine import ContinuousBatchingEngine
from ray_tpu.models.family import (Family, family_of, refuse, row_counts,
                                   slab_spec)
from ray_tpu.models.generate import _model_fns, lora_targets
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import dispatch, mla, swa

# tiny(): (module, kind, ring, latent_only, stateful, state bytes a slot,
# bytes a token, [(rows, layers, bytes a slot)], entries, walks)
TINY = {
    "GPT2Config": ("gpt2", None, None, False, False, 0, 1024,
                   [(128, 2, 131072)], 2, True),
    "LlamaConfig": ("llama", None, None, False, False, 0, 512,
                    [(128, 2, 65536)], 2, True),
    "NemotronHConfig": ("nemotron_h", "state", None, False, True, 18688,
                        128, [(128, 1, 16384)], 3, True),
    "KimiLinearConfig": ("kimi_linear", "state", None, False, True, 15744,
                         256, [(128, 1, 32768)], 4, True),
    "DeepseekV2Config": ("deepseek_v2", "latent", None, True, False, 0,
                         768, [(128, 3, 98304)], 3, True),
    "SmallThinkerConfig": ("smallthinker", "ring", 8, False, False, 0, 640,
                           [(128, 2, 32768), (8, 3, 3072)], 5, True),
    "JambaConfig": ("jamba", "state", None, False, True, 8448, 128,
                    [(128, 2, 16384)], 4, True),
}


def _tiny(name):
    return getattr(models, name).tiny()


@pytest.mark.parametrize("name", sorted(TINY))
def test_the_record_is_found_from_the_configs_class(name):
    cfg = _tiny(name)
    rec = family_of(cfg)
    module = sys.modules[type(cfg).__module__]
    assert rec is module.FAMILY and rec.config_type is type(cfg)
    assert module.__name__ == "ray_tpu.models." + TINY[name][0]
    assert _model_fns(cfg) == (rec.forward_cached, rec.init_cache, rec.decode)
    # the public names the benchmark's family files hold as strings
    stem = TINY[name][0]
    for field in ("init", "forward", "loss", "partition_specs"):
        assert getattr(rec, field) is getattr(module, f"{stem}_{field}")
    # a subclass defined elsewhere (here) is served as its base is, though
    # this module has a record of its own
    sub = type("Sub" + name, (type(cfg),), {"__module__": __name__})
    assert family_of(object.__new__(sub)) is rec


@pytest.mark.parametrize("name", sorted(TINY))
def test_the_slab_is_described_once_from_shapes(name):
    (_, kind, ring, latent, stateful, state_bytes, token_bytes, slab,
     entries, _) = TINY[name]
    cfg = _tiny(name)
    spec = slab_spec(cfg, 4)
    assert spec is slab_spec(cfg, 4)        # once a (config, batch)
    assert (spec.kind, spec.ring_rows, spec.latent_only, spec.stateful) \
        == (kind, ring, latent, stateful)
    assert spec.state_bytes_per_slot == state_bytes
    assert spec.kv_bytes_per_token == token_bytes
    assert spec.slab == [{"rows": r, "layers": n, "bytes_per_slot": b}
                         for r, n, b in slab]
    # against the cache itself, allocated
    cache = family_of(cfg).init_cache(cfg, 4)
    assert spec.by_rows == row_counts(cache) and spec.layers == entries
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)) \
        == 4 * (state_bytes + sum(b for _, _, b in slab))
    first = cache[next(iter(spec.by_rows.values()))[0]]["k"]
    assert spec.stack_shape(7) == (entries, 7) + first.shape[2:]
    assert spec.dtype == first.dtype and spec.rows == max(spec.by_rows)
    assert spec.paired == all("v" in blk for blk in cache if "k" in blk)
    assert spec.longest.shape == (4, spec.rows) + first.shape[2:]


STATE, LATENT, RING = "NemotronHConfig", "DeepseekV2Config", \
    "SmallThinkerConfig"
# (family, capability): the kind's words, then the words the families'
# own tests hold of the row
REFUSALS = {
    (STATE, "prefix_cache"): ("slots own recurrent state",
                              "cannot resume a recurrence",
                              "snapshot of the", "(prefix_cache=True)"),
    (STATE, "speculate_k"): ("own recurrent state", "cannot be un-advanced",
                             "(speculate_k=2)"),
    (STATE, "lora_pool"): ("own recurrent state", "adapter pool",
                           "(lora_pool)"),
    (STATE, "adopt_prefill"): ("own recurrent state", "ck/cv rows only",
                               "(adopt_prefill)"),
    (STATE, "transfer"): ("own recurrent state", "ck/cv rows only",
                          "cannot be served disaggregated"),
    (LATENT, "prefix_cache"): ("one latent row a token and no values",
                               "no block of one latent",
                               "(prefix_cache=True)"),
    (LATENT, "speculate_k"): ("one latent row a token and no values",
                              "pool proposer", "[B, k+1] verify form",
                              "(speculate_k=2)"),
    (LATENT, "lora_pool"): ("one latent row a token and no values",
                            "adapter pool", "(lora_pool)"),
    (LATENT, "adopt_prefill"): ("one latent row a token and no values",
                                "no values to carry", "(adopt_prefill)"),
    (LATENT, "transfer"): ("one latent row a token and no values",
                           "cannot be served disaggregated"),
    (RING, "prefix_cache"): ("holds a ring", "their last 8 rows",
                             "one block shape and one length",
                             "(prefix_cache=True)"),
    (RING, "speculate_k"): ("holds a ring",
                            "overwritten rows the window still sees",
                            "(speculate_k=2)"),
    (RING, "lora_pool"): ("holds a ring", "prefix namespaces",
                          "(lora_pool)"),
    (RING, "adopt_prefill"): ("holds a ring", "a ring's 8 rows are a stack "
                              "of their own", "(adopt_prefill)"),
    (RING, "transfer"): ("holds a ring", "one block shape",
                         "cannot be served disaggregated"),
}


@pytest.mark.parametrize("name,capability", sorted(REFUSALS))
def test_every_refusal_is_one_row_of_one_table(name, capability):
    spec = slab_spec(_tiny(name), 2)
    with pytest.raises(ValueError) as err:
        refuse(spec, capability, k=2)
    for words in REFUSALS[name, capability]:
        assert words in str(err.value)
    # an option left to its default asks for nothing, and a cache of
    # full-length keys and values is refused nothing
    for asked in (None, False, 0):
        refuse(spec, capability, asked, k=2)
    refuse(slab_spec(_tiny("LlamaConfig"), 2), capability, True, k=2)


@pytest.mark.parametrize("name", sorted(TINY))
def test_the_engine_asks_the_kernels_own_rule_for_the_walk(name):
    """The block `slab_rows_read` is counted by is the one the tick's
    attention records for the slab's longest entry (`decode_attention`
    over keys and values, `absorbed_attention` over latent rows), and
    None for a family whose tick reads every row. The engine is built
    with no weights: nothing is traced to learn it."""
    cfg = _tiny(name)
    eng = ContinuousBatchingEngine(None, cfg, max_batch=3)
    try:
        block = eng._walk_block
        stats = eng.kv_stats()
        spec = slab_spec(cfg, 3)
        assert (stats["ring_rows"], stats["latent_only"], stats["stateful"],
                stats["state_bytes_per_slot"], stats["kv_bytes_per_token"],
                stats["slab"]) == (
            spec.ring_rows, spec.latent_only, spec.stateful,
            spec.state_bytes_per_slot, spec.kv_bytes_per_token, spec.slab)
        assert (eng.ring_rows, eng.latent_only, eng.stateful) == (
            spec.ring_rows, spec.latent_only, spec.stateful)
    finally:
        eng.stop()
    if not TINY[name][-1]:
        assert block is None and not family_of(cfg).decode_walks
        return
    entry = spec.longest
    kv = jnp.zeros(entry.shape, entry.dtype)
    at = jnp.zeros((3, 1), jnp.int32)
    if entry.ndim == 3:
        _, rows, width = entry.shape
        q = jnp.zeros((3, 1, 2, 8), entry.dtype)
        mla.absorbed_attention(q, q, kv, at,
                               jnp.zeros((16, 2, 16), entry.dtype))
        took = [c for c in dispatch.kernel_choices("mla_decode")
                if tuple(c["shape"]) == (3, 1, 2, width, 16, rows)]
    else:
        _, rows, groups, d = entry.shape
        q = jnp.zeros((3, 1, 2 * groups, d), entry.dtype)
        swa.decode_attention(q, kv, kv, at)
        took = [c for c in dispatch.kernel_choices("gqa_decode")
                if tuple(c["shape"]) == (3, 1, 2 * groups, groups, d, rows)]
    assert took and all(c["block"] == block for c in took)
    assert block == swa.decode_block(entry.shape, entry.dtype)


def test_a_tick_over_marked_rows_does_not_walk():
    """`dots3_note`'s tick hands `absorbed_attention` the rows a selection
    or a ring's window marks (`visible`): no walk up to a position, so
    the family says so, the engine counts every row, and every latent
    layer of the traced tick keeps the plain form though a kernel could
    run."""
    from ray_tpu.models import dots3_note as m
    cfg = m.Dots3NoteConfig.tiny()
    assert not family_of(cfg).decode_walks
    eng = ContinuousBatchingEngine(None, cfg, max_batch=2)
    try:
        assert eng._walk_block is None
    finally:
        eng.stop()
    params = jax.eval_shape(lambda: m.dots3_note_init(
        cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: m.dots3_note_init_cache(cfg, 2))
    dispatch.reset_kernel_choices()
    with dispatch.pallas_interpret():
        jax.eval_shape(
            lambda p, c: m.dots3_note_decode(
                p, jnp.zeros((2,), jnp.int32), cfg, c,
                jnp.zeros((2,), jnp.int32)), params, cache)
    took = dispatch.kernel_choices("mla_decode")
    assert took and all(c["choice"] == "reference"
                        and "`visible`" in c["reason"] for c in took)


@pytest.mark.parametrize("what,call,words", [
    ("generation", family_of, "no generation support for MoEConfig"),
    ("generation", _model_fns, "no generation support for MoEConfig"),
    ("LoRA", lora_targets, "no LoRA support for MoEConfig"),
])
def test_a_config_with_no_record_is_a_type_error_in_todays_words(
        what, call, words):
    with pytest.raises(TypeError, match=words):
        call(models.MoEConfig.tiny())


@pytest.mark.parametrize("name", sorted(TINY))
def test_lora_targets_are_the_records(name):
    cfg = _tiny(name)
    if name == "LlamaConfig":
        kv = cfg.num_kv_heads * cfg.head_dim
        assert lora_targets(cfg) == (("wq", cfg.d_model, cfg.d_model),
                                     ("wv", cfg.d_model, kv))
    elif name == "GPT2Config":
        assert lora_targets(cfg) == (("qkv", cfg.d_model, 3 * cfg.d_model),)
    else:
        with pytest.raises(TypeError, match=f"no LoRA support for {name}"):
            lora_targets(cfg)


# ------------------------------------------------- a family of this file

@dataclasses.dataclass(frozen=True)
class ToyConfig:
    """A config class of its own: no class of the package is its base."""

    inner: gpt2.GPT2Config

    @property
    def max_seq_len(self) -> int:
        return self.inner.max_seq_len

    @property
    def vocab_size(self) -> int:
        return self.inner.vocab_size


def _over_gpt2(fn, at):
    def wrapped(*args, **kwargs):
        args = list(args)
        args[at] = args[at].inner
        return fn(*args, **kwargs)
    return wrapped


FAMILY = Family(
    config_type=ToyConfig,
    init=_over_gpt2(gpt2.gpt2_init, 0),
    forward=_over_gpt2(gpt2.gpt2_forward, 2),
    loss=_over_gpt2(gpt2.gpt2_loss, 3),
    partition_specs=_over_gpt2(gpt2.gpt2_partition_specs, 0),
    init_cache=_over_gpt2(gpt2.gpt2_init_kv_cache, 0),
    forward_cached=_over_gpt2(gpt2.gpt2_forward_cached, 2),
    decode=_over_gpt2(gpt2.gpt2_decode, 2))


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_a_family_defined_here_is_served_with_no_edit_to_the_package(
        prefix_cache):
    inner = dataclasses.replace(gpt2.GPT2Config.tiny(), dtype=jnp.float32)
    toy = ToyConfig(inner)
    assert family_of(toy) is FAMILY
    assert family_of(LlamaConfig.tiny()) is not FAMILY
    params = FAMILY.init(toy, jax.random.PRNGKey(0))
    prompts = [np.arange(1, 20, dtype=np.int32) % 97,
               np.arange(5, 14, dtype=np.int32)]
    outs = []
    for cfg in (toy, inner):
        eng = ContinuousBatchingEngine(params, cfg, max_batch=2,
                                       prefix_cache=prefix_cache,
                                       kv_block_size=4)
        try:
            streams = [eng.stream(p, 6) for p in prompts]
            outs.append([[int(t) for t in s] for s in streams])
            assert (eng.kv_cache is not None) == prefix_cache
            assert eng.kv_stats()["slab"] == slab_spec(inner, 2).slab
        finally:
            eng.stop()
    assert outs[0] == outs[1] and all(len(o) == 6 for o in outs[0])
    # and through generate(), which asks the same record
    want = models.generate(params, toy, jnp.asarray(prompts[1])[None],
                           max_new_tokens=6)
    assert [int(t) for t in want[0]] == outs[0][1]
