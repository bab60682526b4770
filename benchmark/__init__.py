"""The benchmark of tpu-bft: see README.md in this directory."""
