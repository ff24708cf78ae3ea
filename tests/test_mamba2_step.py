"""The Mamba-2 state step's live-slot walk (`ops/mamba2.py`
`ssd_step_live`) on the CPU in interpret mode: the live rows against the
plain step over every row, the dead rows' state to the bit, and what the
lowering for a TPU says of the state: aliased to its output, nowhere
copied."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import dispatch, mamba2

B, H, P, G, N = 6, 8, 16, 2, 16
LIVE = {"none": [0, 0, 0, 0, 0, 0], "one": [0, 0, 1, 0, 0, 0],
        "all": [1, 1, 1, 1, 1, 1], "scattered": [1, 0, 0, 7, 1, 0],
        "the last alone": [0, 0, 0, 0, 0, 1]}


def _inputs(shape=(B, H, P, G, N)):
    b, h, p, g, n = shape
    key = jax.random.split(jax.random.PRNGKey(0), 7)
    rand = lambda k, *s: jax.random.normal(key[k], s, jnp.float32)
    return (rand(0, b, h, p), jax.nn.softplus(rand(1, b, h)),
            -jnp.exp(rand(2, h)), rand(3, b, g, n), rand(4, b, g, n),
            rand(5, h), rand(6, b, h, p, n))


@pytest.mark.parametrize("heads_block", [4, 8])
@pytest.mark.parametrize("pattern", sorted(LIVE))
def test_the_walk_steps_the_live_rows_and_no_other(pattern, heads_block):
    *small, state = _inputs()
    live = np.asarray(LIVE[pattern], np.int32)
    lv = live != 0
    want_y, want_s = mamba2.ssd_step(*small, state)
    y, s = mamba2._step_pallas(*small, state, jnp.asarray(live),
                               heads_block, True)
    assert y.dtype == jnp.float32 and s.dtype == state.dtype
    np.testing.assert_allclose(np.asarray(y)[lv], np.asarray(want_y)[lv],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s)[lv], np.asarray(want_s)[lv],
                               atol=1e-5, rtol=1e-5)
    # a dead row's state is what went in, to the bit; its y is a number
    np.testing.assert_array_equal(np.asarray(s)[~lv],
                                  np.asarray(state)[~lv])
    assert np.isfinite(np.asarray(y)).all()
    if pattern == "all":
        # no liveness given means every row: the plain step, and under
        # interpret mode still the plain step
        with dispatch.pallas_interpret():
            none_y, none_s = mamba2.ssd_step(*small, state)
            all_y, all_s = mamba2.ssd_step(*small, state, jnp.asarray(live))
        np.testing.assert_array_equal(none_y, want_y)
        np.testing.assert_array_equal(none_s, want_s)
        np.testing.assert_array_equal(all_y, y)
        np.testing.assert_array_equal(all_s, s)


def test_the_choice_is_recorded_and_follows_what_the_step_can_see():
    *small, state = _inputs()
    live = jnp.ones(B, jnp.int32)
    shape = (B, H, P, G, N)

    def choice():
        (rec,) = [c for c in dispatch.kernel_choices("state_step")
                  if c["shape"] == shape]
        return rec

    mamba2.ssd_step(*small, state, live)        # this backend: no Mosaic
    assert choice()["choice"] == "reference" and "backend" in \
        choice()["reason"]
    with dispatch.pallas_interpret():
        mamba2.ssd_step(*small, state, live)
        assert choice()["choice"] == "pallas"
        assert choice()["heads_block"] == H     # a slot's state whole
        mamba2.ssd_step(*small, state)
        assert choice()["choice"] == "reference" and "liveness" in \
            choice()["reason"]
        mamba2.ssd_step(*small, state.astype(jnp.bfloat16), live)
        assert choice()["choice"] == "reference" and "bfloat16" in \
            choice()["reason"]
    # the block of a visit: whole groups that divide the heads, a slot's
    # state whole while it fits 4 MB (the served widths: 128 x 64 x 128)
    assert mamba2.step_heads_block(128, 16, 64, 128) == 128
    assert mamba2.step_heads_block(128, 16, 64, 256) == 64
    assert mamba2.step_heads_block(24, 8, 2048, 512) == 8
    slots, count = mamba2.live_first(jnp.asarray([0, 3, 0, 1, 1, 0]))
    assert slots.tolist() == [1, 3, 4, 0, 2, 5] and count.tolist() == [3]


def test_lowered_for_a_tpu_the_state_is_aliased_and_nowhere_copied():
    """The tick donates the slab: lowered for a TPU (no chip and no TPU
    compiler needed to lower), the state must be the kernel's own output
    operand, and nothing but the kernel may make or take an array of its
    shape."""
    shape = (12, 16, 16, 2, 128)
    args = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in _inputs(shape)]
    live = jax.ShapeDtypeStruct((shape[0],), jnp.int32)
    step = jax.jit(
        lambda *a: mamba2._step_pallas(*a, 16, False), donate_argnums=(6,))
    text = step.trace(*args, live).lower(
        lowering_platforms=("tpu",)).as_text()
    state_type = "tensor<12x16x16x128xf32>"
    # the donated argument is the program's second result ...
    arg = re.search(r"%arg6: " + re.escape(state_type) + r" \{([^}]*)\}",
                    text)
    assert arg and "tf.aliasing_output = 1" in arg.group(1), text[:2000]
    # ... and the kernel writes it where it lies: the operand that IS the
    # argument (behind the grid's length and the two maps) is its output 1
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    operands = re.search(r"@tpu_custom_call\(([^)]*)\)", call).group(1)
    at = [name.strip() for name in operands.split(",")].index("%arg6")
    assert re.search(r"output_operand_alias<output_tuple_indices = \[1\],\s*"
                     rf"operand_index = {at},", call), call[-1500:]
    assert "ssd_step_live" in call
    # no other op has a result of the state's shape: no copy, no reshape
    # (the call of the kernel's own jitted function hands it through)
    made = [ln for ln in text.splitlines()
            if re.search(r"-> (\(.*)?" + re.escape(state_type), ln)
            and "tpu_custom_call" not in ln and "func.func" not in ln
            and " call @_step_pallas(" not in ln]
    assert not made, made
