"""Compile rehearsal of the grouped-query selected prompt form (PR 49):
the longest and the checked prefill of `keye-vl2-videoqa-32k`, compiled
for a described (not attached) v5e chip AS THE CHIP TRACES THEM (the
kernels, not their `jax.numpy` forms), hold the three kernels under the
names the benchmark's readers find them by, and fit the chip beside the
slab. Nothing runs, so nothing here is a time or a rate.

The fixtures and helpers are `test_yardstick_compile.py`'s own, imported,
as `test_yardstick_dsa_compile.py` does (under several workers this file
can go to another worker than that one; where that worker cannot load the
TPU's library a second time, the imported fixture skips these tests; the
driver's test command sets `ALLOW_MULTIPLE_LIBTPU_LOAD=1`, and they
run)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from test_yardstick_compile import (  # noqa: E402,F401 - fixtures
    HBM_BYTES, _cell, _param_shapes, _total_bytes, one_chip, topo)

CELL = "keye-vl2-videoqa-32k"


@pytest.mark.parametrize("tokens", [4000, 32768])
def test_prefill_compiles_with_the_kernels_and_fits(tokens, one_chip,
                                                    monkeypatch):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import engine
    from ray_tpu.models.generate import _model_fns
    from ray_tpu.ops import dispatch

    conf, mix, cfg = _cell(CELL)
    params = _param_shapes(conf, cfg, one_chip)
    cache = jax.eval_shape(
        lambda: _model_fns(cfg)[1](cfg, int(mix["max_batch"])))
    entry = cache[0]["k"]
    suffix = jax.ShapeDtypeStruct((1, tokens), jnp.int32, sharding=one_chip)
    empty = jax.ShapeDtypeStruct((len(cache), 0) + entry.shape[2:],
                                 entry.dtype, sharding=one_chip)
    monkeypatch.setattr(dispatch, "backend_reason", lambda: "")
    dispatch.reset_kernel_choices()
    jax.clear_caches()      # a trace before this took the other branch
    try:
        compiled = engine._prefill_paged.lower(
            params, suffix, cfg, empty, empty).compile()
    finally:
        jax.clear_caches()
    took = {c["op"]: c["choice"] for c in dispatch.kernel_choices()}
    assert {took[op] for op in ("dsa_select", "gqa_selected")} == {"pallas"}
    # every query head and all four heads of keys in one call a layer
    assert dispatch.kernel_choices("gqa_selected")[0]["shape"] \
        == (tokens, 32, 4, 128, 512)
    text = compiled.as_text()
    for name in (f"dsa_index_t{tokens}", f"dsa_select_t{tokens}",
                 f"gqa_selected_t{tokens}"):
        assert name in text, name
    # beside the program: the decode slab (this cache can have no pool)
    slab = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert round(slab / 1e9, 3) == 1.765
    assert _total_bytes(compiled) + slab < HBM_BYTES - (1 << 30)
