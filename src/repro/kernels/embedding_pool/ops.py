"""Public embedding-pool wrapper."""
from __future__ import annotations

from repro.kernels.embedding_pool.kernel import embedding_pool_pallas


def embedding_pool(table, idx):
    """table: [V, D]; idx: [B, L] -> [B, D] mean-pooled bags."""
    return embedding_pool_pallas(table, idx)
