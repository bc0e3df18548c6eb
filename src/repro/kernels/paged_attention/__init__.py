from repro.kernels.paged_attention.ops import (  # noqa: F401
    paged_attention_kernel_supported,
    paged_attention_shard,
)
