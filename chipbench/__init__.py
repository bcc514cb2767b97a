"""chipbench — the served-path benchmark of tpu-ratelimit (see README.md)."""
