"""Single-program parallel engines (fused pipeline, ring decode/attention,
tensor/sequence/expert parallelism)."""
