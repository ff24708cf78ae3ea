"""APPO, DQN, and multi-agent env runner (reference rllib/algorithms/
appo/, rllib/algorithms/dqn/, rllib/env/multi_agent_env_runner.py) —
the round-2 breadth items."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from ray_tpu.rllib import (APPO, APPOConfig, DQN, DQNConfig,
                           MultiAgentCartPole, MultiAgentEnvRunner,
                           MultiAgentPPO, ReplayBuffer)


def _learn(algo, iters, target):
    best = -np.inf
    for _ in range(iters):
        result = algo.step()
        m = result["episode_return_mean"]
        if m == m:  # not NaN
            best = max(best, m)
        if best >= target:
            break
    return best


def test_appo_learns_cartpole_local():
    algo = (APPOConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=0, num_envs_per_env_runner=16,
                         rollout_fragment_length=32)
            .training(lr=3e-3, gamma=0.99)
            .debugging(seed=0)
            .build())
    best = _learn(algo, 40, 150.0)
    assert best >= 150.0, f"APPO failed to learn CartPole: best={best}"


def test_appo_target_network_lags_then_syncs():
    algo = (APPOConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=0, num_envs_per_env_runner=4,
                         rollout_fragment_length=16)
            .training(target_update_freq=10**9)  # never sync in this test
            .debugging(seed=0)
            .build())
    before = jax.device_get(algo.target_params)
    algo.step()
    after_t = jax.device_get(algo.target_params)
    after_p = jax.device_get(algo.params)
    # target held fixed while online params moved
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after_t)):
        np.testing.assert_array_equal(a, b)
    assert any(not np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(after_t), jax.tree.leaves(after_p)))


def test_dqn_learns_cartpole_local():
    algo = (DQNConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=0, num_envs_per_env_runner=8,
                         rollout_fragment_length=32)
            .training(lr=1e-3, gamma=0.99)
            .debugging(seed=0)
            .build())
    best = _learn(algo, 120, 150.0)
    assert best >= 150.0, f"DQN failed to learn CartPole: best={best}"


def test_dqn_rejects_continuous():
    with pytest.raises(ValueError, match="discrete"):
        (DQNConfig().environment("Pendulum-v1")
         .env_runners(num_env_runners=0).build())


def test_replay_buffer_wraps_and_samples():
    buf = ReplayBuffer(capacity=100, obs_dim=4)
    T, N = 10, 3  # 30 transitions per fragment
    for frag in range(5):  # 150 > capacity: wraps
        batch = {
            "obs": np.full((T + 1, N, 4), frag, np.float32),
            "actions": np.full((T, N), frag % 2, np.int32),
            "rewards": np.full((T, N), float(frag), np.float32),
            "dones": np.zeros((T, N), np.bool_),
        }
        buf.add_fragment(batch)
    assert len(buf) == 100
    s = buf.sample(np.random.default_rng(0), 64)
    assert s["obs"].shape == (64, 4)
    # wrapped buffer holds only the newest fragments (0th was overwritten)
    assert s["rewards"].min() >= 1.0


def test_dqn_checkpoint_roundtrip():
    cfg = (DQNConfig().environment("CartPole-v1")
           .env_runners(num_env_runners=0, num_envs_per_env_runner=4,
                        rollout_fragment_length=16))
    algo = cfg.copy().build()
    algo.step()
    state = algo.save_checkpoint("/tmp/unused")
    algo2 = cfg.copy().build()
    algo2.load_checkpoint(state)
    for x, y in zip(jax.tree.leaves(algo.params),
                    jax.tree.leaves(algo2.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(algo.target_params),
                    jax.tree.leaves(algo2.target_params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------- multi-agent


def test_multi_agent_runner_per_policy_batches():
    """Per-policy batch grouping: 4 agents, 2 policies (even/odd) — each
    policy's batch concatenates its agents along the env axis."""
    runner = MultiAgentEnvRunner(
        "MultiAgentCartPole", num_envs=3, rollout_fragment_length=8,
        policy_mapping_fn=lambda aid: f"pol_{int(aid[-1]) % 2}",
        seed=0, env_config={"num_agents": 4})
    specs = runner.policies_needed()
    assert sorted(specs) == ["pol_0", "pol_1"]
    from ray_tpu.rllib import core
    params = {pid: core.policy_init(jax.random.PRNGKey(i), 4, 2)
              for i, pid in enumerate(specs)}
    batches = runner.sample(params)
    assert sorted(batches) == ["pol_0", "pol_1"]
    for pid, b in batches.items():
        # 2 agents x 3 envs = 6 env slots per policy
        assert b["obs"].shape == (9, 6, 4)
        assert b["actions"].shape == (8, 6)
        assert sorted(b["agent_ids"]) == sorted(
            a for a in [f"agent_{i}" for i in range(4)]
            if f"pol_{int(a[-1]) % 2}" == pid)


def test_multi_agent_mismatched_spaces_rejected():
    class WeirdEnv(MultiAgentCartPole):
        def agent_spec(self, agent_id):
            spec = dict(super().agent_spec(agent_id))
            if agent_id == "agent_1":
                spec["num_actions"] = 5
            return spec

    runner = MultiAgentEnvRunner(
        lambda num_envs, seed: WeirdEnv(2, num_envs, seed),
        num_envs=2, rollout_fragment_length=4,
        policy_mapping_fn=lambda aid: "shared")
    with pytest.raises(ValueError, match="mismatched"):
        runner.policies_needed()


def test_multi_agent_two_policies_learn_smoke():
    """2-policy smoke (review done-criterion): both policies improve on
    independent CartPoles."""
    algo = MultiAgentPPO(
        "MultiAgentCartPole", num_envs=16, rollout_fragment_length=64,
        policy_mapping_fn=lambda aid: aid,  # one policy per agent
        env_config={"num_agents": 2}, seed=0,
        lr=1e-3, entropy_coeff=0.01)
    best = {pid: -np.inf for pid in algo.policies}
    for _ in range(30):
        r = algo.step()
        for pid in algo.policies:
            m = r[pid]["episode_return_mean"]
            if m == m:
                best[pid] = max(best[pid], m)
        if all(b >= 80.0 for b in best.values()):
            break
    assert all(b >= 80.0 for b in best.values()), best


def test_sac_learns_pendulum():
    """SAC (twin soft-Q + squashed gaussian + auto-alpha) improves
    Pendulum well past random (~-1240) within the CI budget."""
    from ray_tpu.rllib import SACConfig

    algo = (SACConfig()
            .environment("Pendulum-v1")
            .env_runners(num_env_runners=0, num_envs_per_env_runner=8,
                         rollout_fragment_length=32)
            .training(lr=1e-3, updates_per_step=64, learning_starts=1000)
            .debugging(seed=0)
            .build())
    best = -np.inf
    for _ in range(170):
        result = algo.step()
        m = result["episode_return_mean"]
        if m == m:
            best = max(best, m)
        if best >= -400.0:
            break
    assert best >= -400.0, f"SAC failed to learn Pendulum: best={best}"


def test_sac_rejects_discrete():
    from ray_tpu.rllib import SACConfig

    with pytest.raises(ValueError, match="continuous"):
        (SACConfig().environment("CartPole-v1")
         .env_runners(num_env_runners=0).build())


def test_rllib_bench_smoke(tmp_path):
    """The env-steps/sec benchmark runs and emits well-formed records."""
    import json

    from ray_tpu.rllib.bench import main

    out = str(tmp_path / "bench.json")
    main(["--out", out, "--steps", "2"])
    with open(out) as f:
        data = json.load(f)
    algos = {r["algo"] for r in data["results"]}
    assert algos == {"ppo", "impala", "appo"}
    assert all(r["env_steps_per_sec"] > 0 for r in data["results"])


def test_connector_pipeline_units():
    """Connector math: running mean/std converges, state round-trips,
    action transforms map correctly (reference rllib/connectors/)."""
    import numpy as np

    from ray_tpu.rllib import (ClipActions, ConnectorPipeline,
                               NormalizeObservations, ScaleActions)

    rng = np.random.default_rng(0)
    norm = NormalizeObservations(clip=5.0)
    for _ in range(50):
        norm(rng.normal(3.0, 2.0, (64, 4)).astype(np.float32))
    assert np.allclose(norm.mean, 3.0, atol=0.2)
    assert np.allclose(np.sqrt(norm.m2 / norm.count), 2.0, atol=0.2)
    out = norm(np.full((2, 4), 3.0, np.float32), update=False)
    assert np.abs(out).max() < 0.2  # mean maps near zero
    # update=False must not advance the stats
    count_before = norm.count
    norm(np.zeros((8, 4), np.float32), update=False)
    assert norm.count == count_before

    pipe = ConnectorPipeline(NormalizeObservations(), )
    state = pipe.get_state()
    pipe2 = ConnectorPipeline(NormalizeObservations(), )
    pipe2.set_state(state)
    assert pipe2.connectors[0].count == 0.0

    clip = ClipActions(-2.0, 2.0)
    assert (clip(np.array([-5.0, 0.5, 9.0])) == [-2.0, 0.5, 2.0]).all()
    scale = ScaleActions(-2.0, 2.0)
    assert (scale(np.array([-1.0, 0.0, 1.0])) == [-2.0, 0.0, 2.0]).all()


def test_ppo_with_normalize_connector():
    """PPO trains through an env-to-module normalization pipeline; the
    recorded rollout obs are the transformed ones."""
    import numpy as np

    from ray_tpu.rllib import (ConnectorPipeline, NormalizeObservations,
                               PPOConfig)

    algo = (PPOConfig().environment("CartPole-v1")
            .env_runners(num_env_runners=0, num_envs_per_env_runner=16,
                         rollout_fragment_length=64,
                         env_to_module_connector=lambda:
                         ConnectorPipeline(NormalizeObservations()))
            .training(lr=1e-3).debugging(seed=0).build())
    best = -np.inf
    for _ in range(40):
        m = algo.step()["episode_return_mean"]
        if m == m:
            best = max(best, m)
        if best >= 80.0:
            break
    assert best >= 80.0, f"PPO with connector stalled at {best}"
    norm = algo.local_runner._env_to_module.connectors[0]
    assert norm.count > 0, "normalizer never updated"


def test_connector_fleet_sync_and_checkpoint():
    """Remote-runner connector stats merge into ONE statistic broadcast
    back to the fleet, and checkpoints carry the normalizer (reference
    mean-std filter sync through the driver)."""
    import numpy as np

    from ray_tpu.rllib import (ConnectorPipeline, NormalizeObservations,
                               PPOConfig)
    from ray_tpu.rllib.connectors import NormalizeObservations as NO

    # pure merge math: two disjoint runs merge to the pooled stats
    rng = np.random.default_rng(0)
    a, b = NO(), NO()
    xa = rng.normal(0.0, 1.0, (500, 3)).astype(np.float32)
    xb = rng.normal(4.0, 2.0, (500, 3)).astype(np.float32)
    a(xa); b(xb)
    merged = NO.merge_states([a.get_state(), b.get_state()])
    pooled = np.concatenate([xa, xb])
    assert np.allclose(merged["mean"], pooled.mean(0), atol=1e-4)
    assert np.allclose(np.sqrt(merged["m2"] / merged["count"]),
                       pooled.std(0), atol=1e-3)

    import ray_tpu

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    factory = lambda: ConnectorPipeline(NormalizeObservations())  # noqa: E731
    algo = (PPOConfig().environment("CartPole-v1")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                         rollout_fragment_length=16,
                         env_to_module_connector=factory)
            .debugging(seed=0).build())
    algo.step()
    algo.step()
    states = ray_tpu.get([r.get_connector_states.remote()
                          for r in algo.runners])
    counts = [s["env_to_module"][0]["count"] for s in states]
    # after the broadcast both runners carry the same merged statistic
    assert counts[0] == counts[1] > 0, counts
    # delta-based sync: the pooled count equals the samples actually
    # observed (2 steps x 2 runners x T=16 x 4 envs), not an
    # every-round re-merge of shared history
    assert counts[0] == 2 * 2 * 16 * 4, counts
    ck = algo.save_checkpoint("/tmp/conn_ck")
    assert ck["connector_states"]["env_to_module"][0]["count"] == counts[0]

    algo2 = (PPOConfig().environment("CartPole-v1")
             .env_runners(num_env_runners=0, num_envs_per_env_runner=4,
                          rollout_fragment_length=16,
                          env_to_module_connector=factory)
             .debugging(seed=1).build())
    algo2.load_checkpoint(ck)
    st = algo2.local_runner.get_connector_states()
    assert st["env_to_module"][0]["count"] == counts[0]
    algo.cleanup()
    ray_tpu.shutdown()
