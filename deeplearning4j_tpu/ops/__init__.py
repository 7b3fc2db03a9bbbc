"""TPU kernel ops.

The reference accelerates hot layers through per-layer "platform helpers"
(cuDNN/oneDNN consulted before generic impls — SURVEY.md §2.1). Here XLA is
the default platform and Pallas kernels are the optional accelerated helper,
selected through :func:`set_attention_impl` — the same pluggable-seam shape
as the reference's ``LayerHelper`` SPI, so ValidateCuDNN-style parity tests
(helper vs builtin) carry over (SURVEY.md §4).
"""

from . import helpers
from .helpers import (
    available_helpers,
    get_helper,
    helper_name,
    register_helper,
    set_helper,
)
from .flash_attention import (
    attention_impl,
    decode_attention,
    decode_attention_reference,
    decode_fetched_entries,
    decode_write_fuses,
    flash_attention,
    flash_decode_attention,
    flash_masked_cache_write,
    masked_cache_write,
    masked_cache_write_reference,
    mha_attention,
    mha_attention_reference,
    set_attention_impl,
)
from .eva_attention import (
    chunk_summaries,
    eva_decode_attention,
    eva_decode_attention_pallas,
    eva_decode_attention_reference,
    eva_prefill_attention,
)
from .grouped_matmul import (
    grouped_matmul,
    grouped_matmul_impl,
    grouped_matmul_reference,
    set_grouped_matmul_impl,
)
from .mla_attention import (
    mla_decode_attention,
    mla_decode_attention_pallas,
    mla_decode_attention_reference,
)
from .moe_dispatch import (
    DispatchPlan,
    biased_top_k_routing,
    combine_rows,
    gather_dispatch,
    held_expert_choices,
    make_dispatch_plan,
    scatter_combine,
    top_k_routing,
)
from .paged_attention import (
    pack_row_blocks,
    paged_cache_write,
    paged_decode_attention,
    paged_gather,
)

__all__ = [
    "attention_impl",
    "chunk_summaries",
    "eva_decode_attention",
    "eva_decode_attention_pallas",
    "eva_decode_attention_reference",
    "eva_prefill_attention",
    "decode_attention",
    "decode_attention_reference",
    "decode_fetched_entries",
    "decode_write_fuses",
    "flash_decode_attention",
    "flash_masked_cache_write",
    "masked_cache_write",
    "masked_cache_write_reference",
    "available_helpers",
    "get_helper",
    "helper_name",
    "helpers",
    "register_helper",
    "set_helper",
    "flash_attention",
    "mha_attention",
    "mha_attention_reference",
    "set_attention_impl",
    "DispatchPlan",
    "combine_rows",
    "gather_dispatch",
    "grouped_matmul",
    "grouped_matmul_impl",
    "grouped_matmul_reference",
    "set_grouped_matmul_impl",
    "make_dispatch_plan",
    "biased_top_k_routing",
    "held_expert_choices",
    "mla_decode_attention",
    "mla_decode_attention_pallas",
    "mla_decode_attention_reference",
    "pack_row_blocks",
    "paged_cache_write",
    "paged_decode_attention",
    "paged_gather",
    "scatter_combine",
    "top_k_routing",
]
