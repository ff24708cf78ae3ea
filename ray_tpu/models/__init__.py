"""ray_tpu.models: flagship model families, TPU-first.

The reference ships no models of its own (Ray wraps user torch modules);
the rebuild's north-star workloads (BASELINE.md) need flagship LMs, so
they live here as pure-functional JAX implementations with sharding
rules for every mesh axis the parallel layer exposes.

A family is ONE file that ends in `FAMILY = Family(...)`
(`family.py`: the record, the kinds of entry a cache is made of, the
slab's layout and what each kind is refused). `generate` and
`ContinuousBatchingEngine` find it from the config's class; no list
here or anywhere names a family for them. The names below are
re-exports; `dots3_note.py` (latent attention under a learned selection),
`keye_vl2.py` (grouped-query attention under one, keys and values in
pairs beside an index key in the slab), `zaya.py` (attention in a
compressed latent behind two carried convolutions, one expert a token
under an MLP router that may choose none) and `granite_hybrid.py` (a
Mamba-2 or attention mixer AND ten of 72 experts AND a shared MLP in
every layer, four multipliers) are imported where they are used.
`moe_transformer.py` trains and is not served.
"""
import time

_IMPORT_T0 = time.time()  # the package's import, first line to last

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
from ..util.compile_cache import _stamp_import  # noqa: E402

_stamp_import(__name__, _IMPORT_T0, time.time())
