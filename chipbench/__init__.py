"""chipbench: the benchmark of paddle_tpu on the chip. See README.md."""
