"""repro: U-HNSW (ANNS under universal Lp metrics) as a served vector index
on JAX, with Pallas kernels for the TPU.

Layers:
  repro.core       — the paper's contribution (U-HNSW, HNSW, MLSH baseline)
  repro.index      — segmented sharded U-HNSW + streaming-insert delta tier
  repro.kernels    — Pallas TPU kernels for Lp distance computation
  repro.dist       — mesh / logical-axis sharding helpers
  repro.retrieval  — serving engine, vector service, kNN-LM over the index
  repro.launch     — the retrieval serving entry point + compile cache
"""

__version__ = "0.1.0"
