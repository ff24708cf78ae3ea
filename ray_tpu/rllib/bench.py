"""RLlib throughput benchmark: env-steps/sec per algorithm.

Reference north star: the release criteria track sampler throughput
(env-steps/s) for the async algorithms on their tuned examples
(reference rllib/tuned_examples/, release/rllib_tests/). This emits the
same metric for this rebuild's PPO, IMPALA, and APPO on the native
vectorized CartPole — one JSON line per algorithm plus an aggregate
file. CPU numbers stand in until the bench env allows on-chip runs; the
jitted-update design means the learner side scales with the chip, while
these numbers are dominated by the numpy env stepping itself.

Run: `python -m ray_tpu.rllib.bench [--out RLLIB_BENCH.json]`
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict


def bench_algo(name: str, algo: Any, measure_steps: int = 8
               ) -> Dict[str, Any]:
    algo.step()  # compile + first rollout outside the window
    s0 = algo._env_steps_lifetime
    t0 = time.perf_counter()
    last: Dict[str, Any] = {}
    for _ in range(measure_steps):
        last = algo.step()
    dt = time.perf_counter() - t0
    stepped = algo._env_steps_lifetime - s0
    rec = {
        "algo": name,
        "env_steps_per_sec": round(stepped / dt, 1),
        "env_steps_measured": stepped,
        "seconds": round(dt, 2),
        "episode_return_mean": round(
            float(last.get("episode_return_mean", float("nan"))), 2),
    }
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--platform", default="",
                    help="jax platform to run on (e.g. 'cpu'), set before "
                         "the backend starts; default: what JAX picks")
    args = ap.parse_args(argv)

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from . import APPOConfig, IMPALAConfig, PPOConfig

    builders = {
        "ppo": lambda: (PPOConfig().environment("CartPole-v1")
                        .env_runners(num_env_runners=0,
                                     num_envs_per_env_runner=16,
                                     rollout_fragment_length=64)
                        .debugging(seed=0).build()),
        "impala": lambda: (IMPALAConfig().environment("CartPole-v1")
                           .env_runners(num_env_runners=0,
                                        num_envs_per_env_runner=16,
                                        rollout_fragment_length=64)
                           .debugging(seed=0).build()),
        "appo": lambda: (APPOConfig().environment("CartPole-v1")
                         .env_runners(num_env_runners=0,
                                      num_envs_per_env_runner=16,
                                      rollout_fragment_length=64)
                         .debugging(seed=0).build()),
    }
    import jax

    platform = jax.devices()[0].platform
    results = []
    for name, build in builders.items():
        rec = bench_algo(name, build(), args.steps)
        rec["platform"] = platform  # cpu stand-ins must say so
        results.append(rec)
        print(json.dumps(rec), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"platform": platform, "results": results}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
