"""Compile rehearsal of the decode kernel (PR 41): the tick of each cell
whose family attends through `llama._cache_attention`, compiled for a
described (not attached) v5e chip with `gqa_decode_t<t>` in it. Nothing
runs, so nothing here is a time or a rate.

The fixtures and helpers are `test_yardstick_compile.py`'s own, imported:
that file is the accepted benchmark's, and a PR that changes the program
may not edit it. Under several workers this file can go to another
worker than that one; where that worker cannot load the TPU's library a
second time, the imported fixture skips these tests (the driver's test
command sets `ALLOW_MULTIPLE_LIBTPU_LOAD=1`, and they run)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from test_yardstick_compile import (  # noqa: E402,F401 - fixtures
    HBM_BYTES, _cell, _param_shapes, _shapes, _total_bytes, one_chip, topo)

# the cells whose family's tick attends through `llama._cache_attention`,
# and the shape (B, t, H, G, d, S) `ops/swa.decode_attention` records for
# their slab's longest entries
DECODE_CELLS = {
    "mistral-chat": (32, 1, 32, 8, 128, 2304),
    "mistral-summarize": (8, 1, 32, 8, 128, 4160),
    "nemotron-3-super-reason": (96, 1, 32, 2, 128, 1536),
    "smallthinker-longctx": (16, 1, 28, 4, 128, 16384),
    "jamba2-docqa-32k": (8, 1, 20, 1, 128, 33280),
}


@pytest.mark.parametrize("cell", sorted(DECODE_CELLS))
def test_tick_compiles_with_the_decode_kernel_in_it(cell, one_chip,
                                                    monkeypatch):
    """The tick as the chip traces it: here the backend is the CPU and
    `decode_attention` would take its `jax.numpy` blocks, so the test
    says "a TPU" in the entry point's place; the kernel `gqa_decode_t1`
    is then a Mosaic call of the compiled program, once a slab entry."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import engine
    from ray_tpu.models.generate import _model_fns
    from ray_tpu.ops import dispatch

    conf, mix, cfg = _cell(cell)
    params = _param_shapes(conf, cfg, one_chip)
    batch = int(mix["max_batch"])
    cache = _shapes(jax.eval_shape(
        lambda: _model_fns(cfg)[1](cfg, batch)), one_chip)
    vec = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    monkeypatch.setattr(dispatch, "backend_reason", lambda: "")
    jax.clear_caches()      # a trace before this took the other branch
    try:
        compiled = engine._tick.lower(params, cfg, cache, vec,
                                      vec).compile()
    finally:
        jax.clear_caches()
    took = {c["shape"]: c["choice"]
            for c in dispatch.kernel_choices("gqa_decode")}
    assert took[DECODE_CELLS[cell]] == "pallas"
    assert "gqa_decode_t1" in compiled.as_text()
    slab = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert _total_bytes(compiled) + slab < HBM_BYTES


def test_the_verify_pass_compiles_with_the_decode_kernel(one_chip):
    """Five rows a slot (k = 4): 160 rows of one product."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import swa

    b, t, h, g, d, s = 32, 5, 32, 8, 128, 2304
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, g, d), jnp.bfloat16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one_chip)
    compiled = swa._decode_pallas.lower(
        q, kv, kv, pos, swa._decode_block(s, g, d, 2), False).compile()
    assert "gqa_decode_t5" in compiled.as_text()
