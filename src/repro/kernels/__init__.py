"""Pallas TPU kernels for the perf-critical compute hot-spots.

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling),
a jit'd wrapper in ops.py, and a pure-jnp oracle in ref.py. The wrappers
run Mosaic when a program is compiled for a TPU and the Pallas
interpreter elsewhere, which is how the CPU tests check them.

  radix_partition — the paper's RRJ software-managed-buffer partitioner:
                    the data movement of every fabric route on TPU;
                    chip_smoke.py checks it on the chip against the jnp
                    scatter
  grouped_agg     — RDMA-AGG pre-aggregation (one-hot-matmul scatter-add);
                    timed beside the aggregation path, compiled for v5e in
                    tests/test_tpu_compile.py
  cas_lock        — RSI validate+lock word arbitration (home-shard CAS)
  flash_attention — blockwise causal GQA attention (prefill hot-spot)
  ssd_scan        — Mamba2 SSD chunk scan (jamba/mamba2 hot-spot)

cas_lock, flash_attention and ssd_scan have no caller on the main path and
have never been compiled for a TPU.
"""
