"""Model FLOP/s utilization: 6 N + the causal attention term per token
(recomputation not counted), times the tokens per second of this run,
over the chip's bf16 peak."""
def read(obs):
    train = obs.get("train") or {}
    if not train.get("tokens_per_s"):
        return None
    cell = obs["cell"]
    return (100.0 * train["tokens_per_s"] * cell["train_flops_per_token"]
            / (cell["peaks"]["flops_bf16"] * cell["chips"]))
