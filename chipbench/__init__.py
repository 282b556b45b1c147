"""chipbench: the benchmark of alpa_tpu on the TPU (see README.md here)."""
