"""ray_tpu.models: flagship model families, TPU-first.

The reference ships no models of its own (Ray wraps user torch modules);
the rebuild's north-star workloads (BASELINE.md) need a flagship LM, so
GPT-2 lives here as a pure-functional JAX implementation with first-class
sharding rules for every mesh axis the parallel layer exposes.

Served by `ContinuousBatchingEngine` (`generate._model_fns`): GPT-2, the
Llama block, and Nemotron-H (`nemotron_h.py`: Mamba-2, attention and
LatentMoE layers; its slots own recurrent state, so the engine refuses
it a prefix pool, speculation, a LoRA pool and disaggregated adoption),
and Kimi-Linear (`kimi_linear.py`: delta-rule and latent-attention mixers
over dense and expert layers; beside its state a slot owns the engine's
third kind of cache entry, ONE latent row a token in a single array, from
which keys and values are both made; refused what Nemotron-H is), and
DeepSeek-V2 (`deepseek_v2.py`: rotary latent attention under YaRN over a
dense layer and group-routed expert layers; its cache is latent rows
ALONE, no values and no state, so the engine builds it no prefix pool and
refuses it adoption, speculation and a LoRA pool, each for what the pool
lacks), and SmallThinker (`smallthinker.py`: grouped-query layers that
see the whole sequence beside layers that see a window, under a router
that reads ahead of the attention; a window layer's keys and values are
the engine's fourth kind of entry, a RING shorter than `max_seq_len`
beside the global layers' full-length entries in one slab, so the engine
builds it no prefix pool and refuses it adoption, speculation and a LoRA
pool: most layers have forgotten what a block-aligned prefix would
resume), and Jamba (`jamba.py`: layers of two sublayers, a Mamba-1 or
multi-query attention mixer and a dense SwiGLU; a slot owns a
per-channel float32 state [16, 5120] and a convolution tail a Mamba
layer, stacked by RUN of layers, beside one key-value head's rows; the
head is the embedding; refused what Nemotron-H is).
`moe_transformer.py` trains and is not served.
"""
from .gpt2 import (  # noqa: F401
    GPT2Config,
    gpt2_forward,
    gpt2_init,
    gpt2_loss,
    gpt2_partition_specs,
)
from .engine import ContinuousBatchingEngine, TokenStream  # noqa: F401
from .generate import generate, stream_generate  # noqa: F401
from .kvcache import PagedKVCache  # noqa: F401
from .llama import (  # noqa: F401
    LlamaConfig,
    init_kv_cache,
    llama_forward,
    llama_forward_cached,
    llama_init,
    llama_loss,
    llama_partition_specs,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig,
    nemotron_h_forward,
    nemotron_h_init,
    nemotron_h_loss,
    nemotron_h_partition_specs,
)
from .kimi_linear import (  # noqa: F401
    KimiLinearConfig,
    kimi_linear_forward,
    kimi_linear_init,
    kimi_linear_loss,
    kimi_linear_partition_specs,
)
from .deepseek_v2 import (  # noqa: F401
    DeepseekV2Config,
    deepseek_v2_forward,
    deepseek_v2_init,
    deepseek_v2_loss,
    deepseek_v2_partition_specs,
)
from .smallthinker import (  # noqa: F401
    SmallThinkerConfig,
    smallthinker_forward,
    smallthinker_init,
    smallthinker_loss,
    smallthinker_partition_specs,
)
from .jamba import (  # noqa: F401
    JambaConfig,
    jamba_forward,
    jamba_init,
    jamba_loss,
    jamba_partition_specs,
)
from .moe_transformer import (  # noqa: F401
    MoEConfig,
    moe_forward,
    moe_init,
    moe_loss,
    moe_partition_specs,
)
