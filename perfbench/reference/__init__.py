"""Plain references, one a model family. Independent of sutro_tpu."""
