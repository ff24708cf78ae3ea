"""GPT-2's tick through the decode walk (PR 61): rows of several packed
heads ride `ops/swa.decode_attention` as query heads with the other
heads' lanes zeroed, over an entry that the chip lays down head by head
where its row groups are no power of two (GPT-2 small: 3 rows of 256
lanes). On the CPU the walk is its `jax.numpy` blocks, under
`dispatch.pallas_interpret()` the kernel; the last two compile for a
described v5e (the kernel over entries of 3, 5, 6 and 12 heads, and the
served tick) and read that no entry is copied."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2
from ray_tpu.models.engine import ContinuousBatchingEngine, _tick
from ray_tpu.models.generate import generate
from ray_tpu.observability import requests as reqtrace
from ray_tpu.ops import dispatch, swa

S_ROWS = 384
FORMS = {"blocks": contextlib.nullcontext, "kernel": dispatch.pallas_interpret}


@pytest.fixture()
def blocks_of_128(monkeypatch):
    """Toy entries are small: the served block's bytes would make one
    block of the whole entry."""
    monkeypatch.setattr(swa, "_DECODE_BLOCK_BYTES", 1)


def _packed(seed, t, heads, p, hd, batch=4):
    """q [B, t, g, W], keys and values [B, S, g, W]: p heads to a row."""
    rng = np.random.default_rng(seed)
    rand = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    rows = (heads // p, p * hd)
    return (rand(batch, t, *rows), rand(batch, S_ROWS, *rows),
            rand(batch, S_ROWS, *rows))


def _positions(base, t):
    return jnp.asarray(np.asarray(base)[:, None] + np.arange(t)[None],
                       jnp.int32)


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("t", [1, 3])
@pytest.mark.parametrize("heads", [4, 12])
@pytest.mark.parametrize("p,hd", [(1, 128), (2, 64), (4, 32), (4, 64)])
def test_the_walked_tick_is_the_slab_form(p, hd, heads, t, form,
                                          blocks_of_128):
    """PR 30's packings, with 4 heads (1, 2 or 4 row groups: the entry
    lies row by row) and with 12 (3, 6 or 12: head by head): a parked
    slot, a slot on a block's last row, one on the entry's last row and
    one mid-block read what `_cache_attention` reads over the whole
    slab, from the blocks up to their positions alone."""
    q, ck, cv = _packed(p + hd + heads + t, t, heads, p, hd)
    pos = _positions([0, 127 - (t - 1), S_ROWS - t, 200], t)
    want = gpt2._cache_attention(q, ck, cv, pos, hd)
    dispatch.reset_kernel_choices()
    with FORMS[form]():
        got = gpt2._attention(q, ck, cv, pos, hd)
    choice, = dispatch.kernel_choices("gqa_decode")
    assert choice["choice"] == ("pallas" if form == "kernel"
                                else "reference")
    assert choice["block"] == 128 and tuple(choice["shape"]) == (
        4, t, heads, heads // p, p * hd, S_ROWS)
    np.testing.assert_allclose(got, want, atol=3e-6, rtol=0)


@pytest.mark.parametrize("form", list(FORMS))
def test_a_packed_verify_row_is_the_sequential_ticks_to_the_bit(
        form, blocks_of_128):
    """Three row groups of four heads of 64: row j of a run of four that
    crosses a block's edge is what a one-token tick at its position
    gives, bit for bit."""
    q, ck, cv = _packed(5, 4, 12, 4, 64)
    pos = _positions([126, 0, S_ROWS - 4, 254], 4)
    with FORMS[form]():
        run = gpt2._attention(q, ck, cv, pos, 64)
        for j in range(4):
            one = gpt2._attention(q[:, j:j + 1], ck, cv, pos[:, j:j + 1], 64)
            np.testing.assert_array_equal(run[:, j:j + 1], one)


def test_a_longer_run_keeps_the_slab_form():
    """Nine rows are a suffix or a prompt: no walk is recorded."""
    q, ck, cv = _packed(3, swa.DECODE_ROWS + 1, 12, 4, 64, batch=1)
    dispatch.reset_kernel_choices()
    gpt2._attention(q, ck, cv, _positions([7], swa.DECODE_ROWS + 1), 64)
    assert not dispatch.kernel_choices("gqa_decode")


# six heads of 64 lie two to a row of 128 lanes: three row groups, an
# entry that lies head by head, blocks of the served 128 rows
WALK_CFG = gpt2.GPT2Config(vocab_size=64, max_seq_len=S_ROWS, num_layers=1,
                           num_heads=6, d_model=384, dtype=jnp.float32)
LONG_PROMPT = [1 + i % 60 for i in range(150)]     # into the second block


def test_a_gpt2_engines_tick_walks_its_live_slots_blocks():
    """The engine's tick takes the kernel (interpreted) at GPT-2's packed
    shape, its tokens are `generate()`'s, and the ring counts whole
    blocks of what the slots hold, a finished slot's one block, and not
    `max_batch x max_seq_len`."""
    params = gpt2.gpt2_init(WALK_CFG, jax.random.PRNGKey(1))
    with dispatch.pallas_interpret():
        eng = ContinuousBatchingEngine(params, WALK_CFG, max_batch=2,
                                       prefix_cache=False)
        try:
            assert eng._walk_block == 128
            short = eng.stream(LONG_PROMPT, 4)
            long = eng.stream([5, 6, 7], 12)
            got = [[int(t) for t in s] for s in (short, long)]
            slot = short._req.slot
            eng.stop()
            took = [c for c in eng.kv_stats()["gqa_decode"]
                    if tuple(c["shape"]) == (2, 1, 6, 3, 128, S_ROWS)]
            assert eng._pos[slot] == 0
        finally:
            eng.stop()
    assert took and all(c["choice"] == "pallas" and c["block"] == 128
                        for c in took)
    for prompt, out in zip((LONG_PROMPT, [5, 6, 7]), got):
        want = generate(params, WALK_CFG, jnp.asarray(prompt)[None],
                        max_new_tokens=len(out))[0]
        assert out == [int(t) for t in want]
    ring = [r["slab_rows_read"] for r in reqtrace.store().loop_records()
            if r["engine_id"] == eng.engine_id and "slab_rows_read" in r]
    # two blocks for the long prompt's slot and one beside it; the short
    # request gone, one block a slot
    assert ring[0] == 3 * 128 and ring[-1] == 2 * 128
    assert set(ring) == {3 * 128, 2 * 128}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("groups", [3, 5, 6, 12])
def test_an_entry_of_no_power_of_two_heads_lies_head_by_head(groups,
                                                            one_chip):
    """`_lies_by_group`'s rule against the chip's compiler: an entry [B,
    S, G, d] whose G is no power of two reaches the kernel as [B, G, S,
    d] under a bitcast, not a copy (nothing runs)."""
    b, s, d = 8, 1024, 128
    placed = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                       sharding=one_chip)
    q = placed((b, 1, 2 * groups, d), jnp.bfloat16)
    kv = placed((b, s, groups, d), jnp.bfloat16)
    text = swa._decode_pallas.lower(
        q, kv, kv, placed((b, 1), jnp.int32),
        swa._decode_block(s, groups, d, 2), False).compile().as_text()
    assert swa._lies_by_group(groups)
    assert f"bf16[{b},{s},{groups},{d}]{{3,1,2,0:" in text
    by_group = f"bf16[{b},{groups},{s},{d}]{{3,2,1,0:"
    for side in ("ck", "cv"):   # the parameter itself, under other axes
        assert re.search(re.escape(by_group) + r"\S* bitcast\(%" + side,
                         text), side
    assert "gqa_decode_t1" in text


def test_the_served_tick_reads_an_entry_where_it_lies(one_chip, monkeypatch):
    """`gpt2-chat`'s tick (64 slots of 1,024 rows; two layers of GPT-2
    small's width) compiled for a described v5e: `gqa_decode_t1` is in
    it once a layer, its operands are the entries under a bitcast, the
    slab is aliased whole and no op copies an entry. Nothing runs: this
    is no time."""
    cfg = dataclasses.replace(gpt2.GPT2Config.small(), num_layers=2)
    placed = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = placed(jax.eval_shape(
        lambda: gpt2.gpt2_init(cfg, jax.random.PRNGKey(0))))
    cache = placed(jax.eval_shape(lambda: gpt2.gpt2_init_kv_cache(cfg, 64)))
    vec = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    monkeypatch.setattr(dispatch, "backend_reason", lambda: "")
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()      # a trace before this took the other branch
    try:
        compiled = _tick.lower(params, cfg, cache, vec, vec, vec).compile()
    finally:
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", cached)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2 and all("gqa_decode_t1" in c for c in calls)
    assert all("bf16[64,3,1024,256]{3,2,1,0}, bf16[64,3,1024,256]{3,2,1,0}"
               in c and "%bitcast" in c for c in calls)
    entry = ("bf16[64,1024,3,256]", "bf16[64,3,1024,256]")
    copies = [line.strip()[:120] for line in text.splitlines()
              if line.strip().startswith("%") and " copy(" in line
              and line.split("=", 1)[1].strip().startswith(entry)]
    assert not copies, copies
    slab = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes == slab
