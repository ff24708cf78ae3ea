"""From a configuration file (the published keys, as run) to the
program's own objects. The file is the authority; this module holds the
ONE table of model families (what the program calls its config, init,
loss and partition specs, and the sizes the FLOP and roofline arithmetic
needs) and refuses what the program cannot honour. A new family is an
entry here; the cells' code asks the table and names no model."""
from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, NamedTuple, Tuple

from .traffic import load_json


def load_config(name: str) -> Dict[str, Any]:
    return load_json("configs", name)


def _gpt2_config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    from ray_tpu.models.gpt2 import GPT2Config

    if conf["n_embd"] % conf["n_head"]:
        raise ValueError("n_embd must divide by n_head")
    if conf.get("n_inner") not in (None, 4 * conf["n_embd"]):
        raise ValueError("the program's GPT-2 has n_inner = 4 n_embd")
    if max_seq_len > conf["n_positions"]:
        raise ValueError(
            f"max_seq_len {max_seq_len} exceeds the published "
            f"{conf['n_positions']} positions")
    return GPT2Config(vocab_size=conf["vocab_size"],
                      max_seq_len=int(max_seq_len),
                      num_layers=conf["n_layer"],
                      num_heads=conf["n_head"],
                      d_model=conf["n_embd"])


def _gpt2_shape(conf: Dict[str, Any]) -> Dict[str, int]:
    d, layers = conf["n_embd"], conf["n_layer"]
    return {"layers": layers, "heads": conf["n_head"],
            "head_dim": d // conf["n_head"], "d_model": d,
            "vocab": conf["vocab_size"],
            # N of the 6 N rule, without the position table
            "matmul_params": 12 * layers * d * d + conf["vocab_size"] * d}


def _llama_config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    from ray_tpu.models.llama import LlamaConfig

    if conf["hidden_size"] // conf["num_attention_heads"] \
            != conf["head_dim"]:
        raise ValueError("head_dim must be hidden_size / heads: the "
                         "program derives it")
    if conf["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's rms_norm has eps fixed at 1e-6")
    if conf.get("sliding_window") is not None \
            or conf.get("tie_word_embeddings"):
        raise ValueError("no sliding window and no tied head in the "
                         "program's Llama path")
    if max_seq_len > conf["max_position_embeddings"]:
        raise ValueError(
            f"max_seq_len {max_seq_len} exceeds the file's "
            f"{conf['max_position_embeddings']} positions")
    return LlamaConfig(vocab_size=conf["vocab_size"],
                       max_seq_len=int(max_seq_len),
                       num_layers=conf["num_hidden_layers"],
                       num_heads=conf["num_attention_heads"],
                       num_kv_heads=conf["num_key_value_heads"],
                       d_model=conf["hidden_size"],
                       d_ff=conf["intermediate_size"],
                       rope_theta=float(conf["rope_theta"]))


def _llama_shape(conf: Dict[str, Any]) -> Dict[str, int]:
    d, layers = conf["hidden_size"], conf["num_hidden_layers"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    per_layer = 2 * d * d + 2 * d * kv + 3 * d * conf["intermediate_size"]
    return {"layers": layers, "heads": conf["num_attention_heads"],
            "head_dim": conf["head_dim"], "d_model": d,
            "vocab": conf["vocab_size"],
            "matmul_params": layers * per_layer + conf["vocab_size"] * d}


class Family(NamedTuple):
    module: str          # the program's module of this family
    init: str            # its names in that module
    loss: str
    partition_specs: str
    # the ops a training step of this family must take through Pallas
    # (`ops/dispatch.kernel_choices()`), or the run is not `correct`
    train_kernels: Tuple[str, ...]
    config: Callable[[Dict[str, Any], int], Any]
    shape: Callable[[Dict[str, Any]], Dict[str, int]]


FAMILIES: Dict[str, Family] = {
    "gpt2": Family("ray_tpu.models.gpt2", "gpt2_init", "gpt2_loss",
                   "gpt2_partition_specs",
                   ("flash_attention", "linear_cross_entropy"),
                   _gpt2_config, _gpt2_shape),
    "llama": Family("ray_tpu.models.llama", "llama_init", "llama_loss",
                    "llama_partition_specs", ("flash_attention",),
                    _llama_config, _llama_shape),
}


def family(conf: Dict[str, Any]) -> Family:
    if conf["family"] not in FAMILIES:
        raise ValueError(f"unknown model family {conf['family']!r}")
    return FAMILIES[conf["family"]]


def _program(conf: Dict[str, Any], name: str) -> Any:
    fam = family(conf)
    return getattr(importlib.import_module(fam.module), getattr(fam, name))


def program_config(conf: Dict[str, Any], max_seq_len: int) -> Any:
    """The program's config object for `conf`, with the cell's window."""
    return family(conf).config(conf, max_seq_len)


def model_shape(conf: Dict[str, Any]) -> Dict[str, int]:
    """layers, heads, head_dim, d_model, vocab and the matmul parameters,
    under the same names whatever the family's file calls them."""
    return family(conf).shape(conf)


def train_flops_per_token(conf: Dict[str, Any], seq: int) -> float:
    """6 N + the causal attention term (fwd + bwd of QK^T and PV, halved)
    (copied from ray_tpu/observability/flops.py: param_count,
    attn_flops_per_token, train_flops_per_token)."""
    shape = model_shape(conf)
    return (6.0 * shape["matmul_params"]
            + 12.0 * shape["layers"] * shape["d_model"] * seq / 2.0)


def train_program(conf: Dict[str, Any], cfg: Any, remat: bool
                  ) -> Tuple[Callable[[Any, Dict[str, Any]], Any], Any]:
    """The family's loss over a batch {"tokens", "targets"} and its
    partition specs, as `TrainStep` takes them."""
    loss = _program(conf, "loss")

    def loss_fn(params: Any, batch: Dict[str, Any]) -> Any:
        return loss(params, batch["tokens"], batch["targets"], cfg,
                    remat=remat)

    return loss_fn, _program(conf, "partition_specs")(cfg)


def init_params(conf: Dict[str, Any], cfg: Any, seed: int) -> Any:
    """The model's own init, in ONE jitted call on the device, in the
    type the weights are served in."""
    import jax

    # --seed may exceed 32 signed bits: fold the high part in
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(_program(conf, "init"), static_argnums=(0,))(cfg, key)
