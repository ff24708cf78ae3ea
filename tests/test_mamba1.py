"""`ops/mamba1.py`: the selective scan against the recurrence written out
token by token in numpy (with a carried-in state, at lengths that end in
a ragged block of the kernel's own), the Pallas kernel in interpret mode
against the same, a run cut in two, padding (dt = 0) that neither decays
nor feeds the state, the one-step form, and what the dispatch records."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.ops import dispatch, mamba1  # noqa: E402


def _inputs(b, t, c, n, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(ks[0], (b, t, c), jnp.float32).astype(dtype)
    z = jax.random.normal(ks[1], (b, t, c), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (b, t, c)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[3], (n, c)) * 0.5)
    bm = jax.random.normal(ks[4], (b, t, n))
    cm = jax.random.normal(ks[5], (b, t, n))
    state = jax.random.normal(ks[6], (b, n, c))     # what came before
    return u, dt, a, bm, cm, jnp.linspace(0.5, 1.5, c), z, state


def _by_hand(u, dt, a, bm, cm, d, z, state):
    """The module docstring's two lines, a token at a time, in float64."""
    u, dt, a, bm, cm, d, z, s = (np.asarray(x, np.float64) for x in (
        u, dt, a, bm, cm, d, z, state))
    ys = np.zeros_like(u)
    for t in range(u.shape[1]):
        s = np.exp(dt[:, t, None, :] * a) * s \
            + (dt[:, t] * u[:, t])[:, None, :] * bm[:, t, :, None]
        y = (s * cm[:, t, :, None]).sum(1) + d * u[:, t]
        ys[:, t] = y * z[:, t] / (1.0 + np.exp(-z[:, t]))
    return ys, s


# 300 = 256 + 44: the kernel's time block, then a ragged one; 37 alone is
# padded to 40 steps; 128 channels are one lane's width, 1,536 a block of
# 1,024 and a ragged one
@pytest.mark.parametrize("b,t,c,n", [(2, 37, 256, 16), (1, 300, 1536, 16),
                                     (1, 5, 128, 4)])
def test_the_scan_and_the_kernel_agree_with_the_recurrence(b, t, c, n):
    args = _inputs(b, t, c, n)
    want_y, want_s = _by_hand(*args)
    dispatch.reset_kernel_choices()
    y, s = mamba1.selective_scan(*args)
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=2e-5)
    assert dispatch.kernel_choices("selective_scan")[-1]["choice"] \
        == "reference"
    with dispatch.pallas_interpret():
        yk, sk = mamba1.selective_scan(*args, tokens=4 * t)
    # float32 rounding: the same operations in the same order
    np.testing.assert_allclose(yk, y, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(sk, s, atol=2e-6, rtol=2e-6)
    choice = dispatch.kernel_choices("selective_scan")[-1]
    assert choice["choice"] == "pallas"
    assert tuple(choice["shape"]) == (b, t, c, n, 4 * t)
    assert choice["time_block"] == min(256, -(-t // 8) * 8)
    assert choice["channel_block"] == min(1024, c)


def test_channels_that_are_no_whole_lanes_take_the_steps_and_say_why():
    args = _inputs(1, 9, 96, 4)
    dispatch.reset_kernel_choices()
    with dispatch.pallas_interpret():
        y, s = mamba1.selective_scan(*args)
    want_y, want_s = _by_hand(*args)
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=2e-5)
    choice = dispatch.kernel_choices("selective_scan")[-1]
    assert choice["choice"] == "reference" and "96 channels" in choice[
        "reason"]


@pytest.mark.parametrize("interpret", [False, True])
def test_a_run_cut_in_two_and_padding_leave_the_state_alone(interpret):
    u, dt, a, bm, cm, d, z, state = _inputs(1, 21, 128, 8, seed=3)
    scan = mamba1.selective_scan
    if interpret:
        def scan(*args):
            with dispatch.pallas_interpret():
                return mamba1.selective_scan(*args)
    y, s = scan(u, dt, a, bm, cm, d, z, state)
    cut = lambda x, lo, hi: x[:, lo:hi]
    y1, s1 = scan(*(cut(x, 0, 13) for x in (u, dt)), a,
                  *(cut(x, 0, 13) for x in (bm, cm)), d, cut(z, 0, 13),
                  state)
    y2, s2 = scan(*(cut(x, 13, 21) for x in (u, dt)), a,
                  *(cut(x, 13, 21) for x in (bm, cm)), d, cut(z, 13, 21),
                  s1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, atol=1e-6)
    np.testing.assert_allclose(s2, s, atol=1e-6)
    # eleven steps of padding behind the run: dt = 0 AFTER the softplus,
    # whatever u, B and C hold there
    pad = lambda x: jnp.concatenate([x, 7.0 + x[:, :11]], 1)
    dt_p = jnp.concatenate([dt, jnp.zeros_like(dt[:, :11])], 1)
    yp, sp = scan(pad(u), dt_p, a, pad(bm), pad(cm), d, pad(z), state)
    np.testing.assert_array_equal(sp, s)
    np.testing.assert_array_equal(yp[:, :21], y)


def test_one_step_for_every_row_is_the_scans_step():
    u, dt, a, bm, cm, d, z, state = _inputs(3, 1, 128, 16, seed=5,
                                            dtype=jnp.bfloat16)
    y, s = mamba1.selective_step(u[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                                 d, z[:, 0], state)
    want_y, want_s = _by_hand(u, dt, a, bm, cm, d, z, state)
    assert y.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    np.testing.assert_allclose(s, want_s, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(y.astype(jnp.float32), want_y[:, 0],
                               atol=0.05, rtol=0.02)
    # the state is donated in the tick: elementwise, one read, one write
    step = jax.jit(mamba1.selective_step, donate_argnums=(7,))
    text = step.lower(u[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d, z[:, 0],
                      state).compile().as_text()
    assert "input_output_alias" in text
