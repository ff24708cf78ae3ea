"""The KDA state step's live-slot walk (`ops/kda.py` `kda_step_live`) on
the CPU in interpret mode: the live rows against the plain step over
every row, the dead rows' state to the bit, what the entry point chooses
from what it can see, and what the lowering for a TPU says of the state:
aliased to its output, nowhere copied. The kernel at the served shape is
compiled for a described (not attached) v5e chip; nothing here is a time
or a rate."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import dispatch, kda, mamba2

B, H, DK, DV = 6, 8, 16, 16
LIVE = {"none": [0, 0, 0, 0, 0, 0], "one": [0, 0, 1, 0, 0, 0],
        "alternating": [1, 0, 7, 0, 1, 0], "all": [1, 1, 1, 1, 1, 1],
        "the last alone": [0, 0, 0, 0, 0, 1]}
# `kimi-linear-generate`'s slab: 128 slots, 32 heads of 128 x 128
SERVED = (128, 32, 128, 128)


def _inputs(shape=(B, H, DK, DV), state_dtype=jnp.float32):
    b, h, dk, dv = shape
    key = jax.random.split(jax.random.PRNGKey(0), 6)
    rand = lambda k, *s: jax.random.normal(key[k], s, jnp.float32)
    return (kda.l2_normalize(rand(0, b, h, dk)) * dk ** -0.5,
            kda.l2_normalize(rand(1, b, h, dk)), rand(2, b, h, dv),
            -jax.nn.softplus(rand(3, b, h, dk)),
            jax.nn.sigmoid(rand(4, b, h)),
            rand(5, b, h, dk, dv).astype(state_dtype))


@pytest.mark.parametrize("heads_block", [4, 8])
@pytest.mark.parametrize("pattern", sorted(LIVE))
def test_the_walk_steps_the_live_rows_and_no_other(pattern, heads_block):
    *small, state = _inputs()
    live = np.asarray(LIVE[pattern], np.int32)
    lv = live != 0
    want_o, want_s = kda.kda_step(*small, state)
    o, s = kda._step_pallas(*small, state, jnp.asarray(live), heads_block,
                            True)
    assert o.dtype == jnp.float32 and s.dtype == state.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o)[lv], np.asarray(want_o)[lv],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s)[lv], np.asarray(want_s)[lv],
                               atol=1e-5, rtol=1e-5)
    # a dead row's state is what went in, to the bit (the plain step moves
    # it); its o is 0
    np.testing.assert_array_equal(np.asarray(s)[~lv],
                                  np.asarray(state)[~lv])
    np.testing.assert_array_equal(np.asarray(o)[~lv], 0.0)
    if (~lv).any():
        assert np.abs(np.asarray(want_s)[~lv]
                      - np.asarray(state)[~lv]).max() > 0
    if pattern == "all":
        # no liveness given means every row: the plain step, and under
        # interpret mode still the plain step
        with dispatch.pallas_interpret():
            none_o, none_s = kda.kda_step(*small, state)
            all_o, all_s = kda.kda_step(*small, state, jnp.asarray(live))
        np.testing.assert_array_equal(none_o, want_o)
        np.testing.assert_array_equal(none_s, want_s)
        np.testing.assert_array_equal(all_o, o)
        np.testing.assert_array_equal(all_s, s)


def test_the_choice_is_recorded_and_follows_what_the_step_can_see(
        monkeypatch):
    *small, state = _inputs()
    live = jnp.ones(B, jnp.int32)

    def choice(shape=(B, H, DK, DV)):
        (rec,) = [c for c in dispatch.kernel_choices("state_step")
                  if c["shape"] == shape]
        return rec

    kda.kda_step(*small, state, live)           # this backend: no Mosaic
    assert choice()["choice"] == "reference" and "backend" in \
        choice()["reason"]
    with dispatch.pallas_interpret():
        kda.kda_step(*small, state, live)
        assert choice()["choice"] == "pallas"
        assert choice()["heads_block"] == H     # a slot's state whole
        kda.kda_step(*small, state)
        assert choice()["choice"] == "reference" and "liveness" in \
            choice()["reason"]
        o, s = kda.kda_step(*small, state.astype(jnp.bfloat16), live)
        assert choice()["choice"] == "reference" and "bfloat16" in \
            choice()["reason"]
        # the plain step keeps a state's dtype; the kernel never sees one
        # that is not float32
        assert s.dtype == jnp.bfloat16 and o.dtype == jnp.float32
    # on a TPU (said in the backend's place: nothing is lowered, the
    # reference is taken) a head's tile must fill the registers
    narrow = (B, H, DK, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kda.kda_step(*_inputs(narrow), live)
    assert choice(narrow)["choice"] == "reference" and "64" in \
        choice(narrow)["reason"] and "tiles" in choice(narrow)["reason"]
    # the block of a visit: a slot's state whole while it fits 4 MB (the
    # served widths: 32 x 128 x 128 float32 are 2 MB), by the function
    # the Mamba-2 walk sizes its own with
    b, h, dk, dv = SERVED
    assert mamba2.step_heads_block(h, 1, dk, dv) == 32
    assert mamba2.step_heads_block(64, 1, 256, 128) == 32
    assert kda.live_first is mamba2.live_first


def test_lowered_for_a_tpu_the_state_is_aliased_and_nowhere_copied():
    """The tick donates the slab: lowered for a TPU (no chip and no TPU
    compiler needed to lower), the state must be the kernel's own output
    operand, and nothing but the kernel may make or take an array of its
    shape."""
    shape = (12, 16, 16, 128)
    args = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in _inputs(shape)]
    live = jax.ShapeDtypeStruct((shape[0],), jnp.int32)
    step = jax.jit(
        lambda *a: kda._step_pallas(*a, 16, False), donate_argnums=(5,))
    text = step.trace(*args, live).lower(
        lowering_platforms=("tpu",)).as_text()
    state_type = "tensor<12x16x16x128xf32>"
    # the donated argument is the program's second result ...
    arg = re.search(r"%arg5: " + re.escape(state_type) + r" \{([^}]*)\}",
                    text)
    assert arg and "tf.aliasing_output = 1" in arg.group(1), text[:2000]
    # ... and the kernel writes it where it lies: the operand that IS the
    # argument (behind the grid's length and the two maps) is its output 1
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    operands = re.search(r"@tpu_custom_call\(([^)]*)\)", call).group(1)
    at = [name.strip() for name in operands.split(",")].index("%arg5")
    assert re.search(r"output_operand_alias<output_tuple_indices = \[1\],\s*"
                     rf"operand_index = {at},", call), call[-1500:]
    assert "kda_step_live" in call
    # no other op has a result of the state's shape: no copy, no reshape
    # (the call of the kernel's own jitted function hands it through)
    made = [ln for ln in text.splitlines()
            if re.search(r"-> (\(.*)?" + re.escape(state_type), ln)
            and "tpu_custom_call" not in ln and "func.func" not in ln
            and " call @_step_pallas(" not in ln]
    assert not made, made


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described device's programs cannot be read back from the
    # persistent cache: keep them out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_the_kernel_at_the_served_shape_compiles_for_the_chip(one_chip):
    """`kimi-linear-generate`'s layer state through the walk at the block
    the entry point chooses, compiled by the TPU's compiler for a
    described chip: the tiles, the VMEM and the aliasing it would refuse
    there it refuses here. The state (268 MB) is the program's own
    result, no op copies it, and beside it the program holds no more than
    the small operands."""
    b, h, dk, dv = SERVED
    hb = mamba2.step_heads_block(h, 1, dk, dv)
    sd = lambda *s, dtype=jnp.float32: jax.ShapeDtypeStruct(
        s, dtype, sharding=one_chip)
    step = jax.jit(lambda *a: kda._step_pallas(*a, hb, False),
                   donate_argnums=(5,))
    compiled = step.lower(
        sd(b, h, dk), sd(b, h, dk), sd(b, h, dv), sd(b, h, dk), sd(b, h),
        sd(b, h, dk, dv), sd(b, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    header = text[text.index("input_output_alias"):].split("\n", 1)[0]
    assert re.search(r"\{1\}: \(5, \{\}, (may|must)-alias\)", header), header
    (call,) = [ln for ln in text.splitlines()
               if "custom-call(" in ln and "kda_step_live" in ln]
    assert "output_to_operand_aliasing={{1}: (" in call, call[:600]
    assert not re.findall(rf"f32\[{b},{h},{dk},{dv}\][^ ]* copy\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * b * h * dk * dv
    assert mem.temp_size_in_bytes < 32 << 20
