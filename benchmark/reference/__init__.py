"""Plain references: one module per model family, float32, no kernels."""
