"""`ops/cca.py`: compressed convolutional attention over a run of tokens
on top of a carried state. The prompt form in ONE block against the same
prompt in two blocks with the tails carried, and against the tick form
token by token; the steps by hand against plain numpy for two tokens; the
state's shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import cca

F32 = jnp.float32
GEO = {"heads": 4, "kv_heads": 2, "head_dim": 16, "rotary": 8,
       "theta": 5e6}
D = 48


def _weights(time0, time1, dtype, seed=0):
    heads, kv, d = GEO["heads"], GEO["kv_heads"], GEO["head_dim"]
    lat = (heads + kv) * d
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    n = lambda *s, scale: (jax.random.normal(next(k), s, F32)
                           * scale).astype(dtype)
    return {"w_in": n(D, lat + kv * d, scale=D ** -0.5),
            "conv0_w": n(time0, lat, scale=time0 ** -0.5),
            "conv0_b": n(lat, scale=0.1),
            "conv1_w": n(heads + kv, time1 * d, d,
                         scale=(time1 * d) ** -0.5),
            "conv1_b": n(lat, scale=0.1),
            "tau": jnp.asarray([3.0, 4.5], dtype),
            "wo": n(heads * d, D, scale=(heads * d) ** -0.5)}


def _state(batch, time0, time1, dtype):
    return cca.cca_state(batch, GEO["heads"], GEO["kv_heads"],
                         GEO["head_dim"], time0, time1, dtype)


def _cache(batch, rows, dtype):
    shape = (batch, rows, GEO["kv_heads"], GEO["head_dim"])
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


CASES = [(2, 2, jnp.float32, 2e-5), (3, 2, jnp.float32, 2e-5),
         (2, 3, jnp.float32, 2e-5), (2, 2, jnp.bfloat16, 3e-2)]
IDS = ["taps2-2-f32", "taps3-2-f32", "taps2-3-f32", "taps2-2-bf16"]


@pytest.mark.parametrize("time0,time1,dtype,tol", CASES, ids=IDS)
def test_one_block_is_two_blocks_with_the_tails_carried(time0, time1,
                                                        dtype, tol):
    w = _weights(time0, time1, dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 13, D), F32).astype(
        dtype)
    one, state_one, cache_one = cca.cca_prompt(
        x, w, _state(2, time0, time1, dtype), _cache(2, 32, dtype), 0,
        **GEO)
    first, state, cache = cca.cca_prompt(
        x[:, :7], w, _state(2, time0, time1, dtype), _cache(2, 32, dtype),
        0, **GEO)
    # the second block at a TRACED position: over the cache as it lies
    second, state, cache = jax.jit(
        lambda x, s, c, pos: cca.cca_prompt(x, w, s, c, pos, **GEO))(
            x[:, 7:], state, cache, jnp.int32(7))
    np.testing.assert_allclose(
        np.concatenate([first, second], 1), one, atol=tol, rtol=0)
    for name in state_one:
        np.testing.assert_allclose(state[name].astype(F32),
                                   state_one[name].astype(F32), atol=tol)
        assert state[name].dtype == dtype
    for name in ("k", "v"):
        np.testing.assert_allclose(cache[name].astype(F32),
                                   cache_one[name].astype(F32), atol=tol)
        # the rows past the run are untouched
        assert not np.asarray(cache[name][:, 13:].astype(F32)).any()


@pytest.mark.parametrize("time0,time1,dtype,tol", CASES, ids=IDS)
def test_the_prompt_form_is_the_tick_form_token_by_token(time0, time1,
                                                         dtype, tol):
    w = _weights(time0, time1, dtype, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, D), F32).astype(
        dtype)
    # no cache: the prompt form over the run alone
    want, state_want, none = cca.cca_prompt(
        x, w, _state(2, time0, time1, dtype), None, 0, **GEO)
    assert none is None
    tick = jax.jit(lambda x, s, c, pos: cca.cca_tick(x, w, s, c, pos,
                                                     **GEO))
    state, cache, outs = _state(2, time0, time1, dtype), \
        _cache(2, 16, dtype), []
    for t in range(9):
        out, state, cache = tick(x[:, t:t + 1], state, cache,
                                 jnp.full((2, 1), t, jnp.int32))
        outs.append(out)
    np.testing.assert_allclose(np.concatenate(outs, 1), want, atol=tol,
                               rtol=0)
    for name in state_want:
        np.testing.assert_allclose(state[name].astype(F32),
                                   state_want[name].astype(F32), atol=tol)


def test_slots_at_their_own_positions_step_their_own_tails():
    """A ragged tick: slot 0 at position 5 and slot 1 at position 2 give
    what each gives alone."""
    w = _weights(2, 2, jnp.float32, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 6, D), F32)
    state, cache = _state(2, 2, 2, F32), _cache(2, 8, F32)
    alone = []
    for b, upto in ((0, 5), (1, 2)):
        _o, s, c = cca.cca_prompt(x[b:b + 1, :upto], w, _state(1, 2, 2, F32),
                                  _cache(1, 8, F32), 0, **GEO)
        alone.append(cca.cca_tick(x[b:b + 1, upto:upto + 1], w, s, c,
                                  jnp.asarray([[upto]]), **GEO))
        state = {n: state[n].at[b].set(s[n][0]) for n in state}
        cache = {n: cache[n].at[b].set(c[n][0]) for n in cache}
    step = jnp.stack([x[0, 5:6], x[1, 2:3]])
    out, state, cache = cca.cca_tick(step, w, state, cache,
                                     jnp.asarray([[5], [2]]), **GEO)
    for b, (o, s, c) in enumerate(alone):
        np.testing.assert_allclose(out[b], o[0], atol=2e-5)
        np.testing.assert_allclose(state["conv1"][b], s["conv1"][0],
                                   atol=2e-5)
        np.testing.assert_allclose(cache["k"][b], c["k"][0], atol=2e-5)


def test_the_steps_by_hand_for_two_tokens():
    """Steps 1 to 6 in numpy, token 1 reading token 0's tails."""
    heads, kv, d, rot = 4, 2, 16, 8
    w = jax.tree.map(np.asarray, _weights(2, 2, jnp.float32, seed=7))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(8), (1, 2, D), F32))
    q, k, v, state = cca.cca_qkv(
        jnp.asarray(x), w, _state(1, 2, 2, F32),
        jnp.asarray([[0, 1]]), **GEO)
    proj = x[0] @ w["w_in"]
    lat = (heads + kv) * d
    u, v1, v2 = proj[:, :lat], proj[:, lat:lat + 16], proj[:, lat + 16:]
    a0 = w["conv0_w"][1] * u[0] + w["conv0_b"]          # u_{-1} = 0
    a1 = w["conv0_w"][0] * u[0] + w["conv0_w"][1] * u[1] + w["conv0_b"]
    w1 = w["conv1_w"].reshape(heads + kv, 2, d, d)
    b1 = sum(np.einsum("hc,hcd->hd", a.reshape(heads + kv, d), w1[:, j])
             for j, a in enumerate((a0, a1))) \
        + w["conv1_b"].reshape(heads + kv, d)            # of token 1
    q_lat, k_lat = (u[1, :heads * d].reshape(heads, d),
                    u[1, heads * d:].reshape(kv, d))
    q1 = b1[:heads] + 0.5 * (q_lat + np.repeat(k_lat, 2, 0))
    k1 = b1[heads:] + 0.5 * (q_lat.reshape(kv, 2, d).mean(1) + k_lat)
    unit = lambda z: z / np.linalg.norm(z, axis=-1, keepdims=True) * 4.0
    q1, k1 = unit(q1), unit(k1) * np.asarray([3.0, 4.5])[:, None]
    inv = 1.0 / 5e6 ** (np.arange(0, rot, 2) / rot)      # position 1

    def rope(z):
        z1, z2 = z[:, :rot // 2], z[:, rot // 2:rot]
        return np.concatenate([z1 * np.cos(inv) - z2 * np.sin(inv),
                               z2 * np.cos(inv) + z1 * np.sin(inv),
                               z[:, rot:]], -1)

    np.testing.assert_allclose(q[0, 1], rope(q1), atol=2e-5)
    np.testing.assert_allclose(k[0, 1], rope(k1), atol=2e-5)
    # token 1's values: its own half, then token 0's
    np.testing.assert_allclose(v[0, 1, :, :8], v1[1].reshape(kv, 8),
                               atol=1e-6)
    np.testing.assert_allclose(v[0, 1, :, 8:], v2[0].reshape(kv, 8),
                               atol=1e-6)
    assert not np.asarray(v[0, 0, :, 8:]).any()          # v2_{-1} = 0
    np.testing.assert_allclose(state["conv0"][0, 0], u[1], atol=1e-6)
    np.testing.assert_allclose(state["conv1"][0, 0], a1, atol=1e-5)
    np.testing.assert_allclose(state["v2"][0, 0], v2[1], atol=1e-6)
    # a unit query and a key at tau: the scores are cosines times tau
    np.testing.assert_allclose(np.linalg.norm(q[0, 1], axis=-1), 4.0,
                               rtol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(k[0, 1], axis=-1),
                               [12.0, 18.0], rtol=1e-5)


def test_the_state_is_kilobytes_at_the_published_sizes():
    state = jax.eval_shape(lambda: cca.cca_state(64, 8, 2, 128, 2, 2,
                                                 jnp.bfloat16))
    assert {n: s.shape for n, s in state.items()} == {
        "conv0": (64, 1, 1280), "conv1": (64, 1, 1280), "v2": (64, 1, 128)}
    assert sum(s.size * 2 for s in state.values()) // 64 == 5376
