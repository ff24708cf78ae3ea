"""The test-only path into the benchmark command: one cell at a toy size
on the CPU, everything else as on the chip. Run as

    python tests/yardstick/rehearse.py --workload <name> --seed 1 --seconds 3 --trace 0

It exists so that the tests can pin the last line without a chip; the
command proper (`benchmarks/run.py`) never reaches it."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TOY_CONF = {
    "gpt2": {"n_layer": 2, "n_embd": 128, "n_head": 4, "vocab_size": 512,
             "n_positions": 128, "n_ctx": 128},
    "llama": {"hidden_size": 128, "intermediate_size": 256,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 32, "num_hidden_layers": 2, "vocab_size": 512,
              "max_position_embeddings": 128},
}
TOY_TRAFFIC = {
    "train": {"batch": 2, "seq": 64,
              "tolerances": {"loss_abs": 0.05}},
    "serve": {"prompt_tokens": {"values": [8, 16], "weights": [1, 1]},
              "output_tokens": {"values": [4, 8], "weights": [1, 1]},
              "max_batch": 4, "max_seq_len": 64, "max_queue_depth": 16,
              "rate_rps": 6.0, "clients": 2, "pool_requests_per_s": 40,
              "drain_s": 20,
              "tolerances": {"logprob_abs": 0.2, "logprob_mean_abs": 0.2,
                             "margin_abs": 0.2}},
}


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("RAY_TPU_PALLAS_INTERPRET", "1")
    from benchmarks import run
    from benchmarks.harness import traffic
    from benchmarks.harness.configs import load_config

    argv = sys.argv[1:]
    name = argv[argv.index("--workload") + 1]
    cell = {w["name"]: w for w in run.load_benchmark()["workloads"]}[name]
    family = load_config(cell["config"])["family"]
    kind = traffic.load_json("traffic", cell["traffic"])["kind"]
    run.main(argv, rehearsal=run.Rehearsal(TOY_CONF[family],
                                           TOY_TRAFFIC[kind]))


if __name__ == "__main__":
    main()
