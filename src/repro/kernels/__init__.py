"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package ships three modules:
  kernel.py — pl.pallas_call body with explicit BlockSpec VMEM tiling
  ops.py    — jit'd public wrapper (padding, layout, GQA handling)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels target TPU (MXU-aligned 128-blocks); tests validate them on CPU in
interpret mode. The model zoo uses the portable jnp paths by default and
routes here on TPU backends.

  flash_attention — blocked causal attention (online softmax), the memory
                    hot spot of train_4k/prefill_32k cells
  flash_decode    — single-token attention vs a long KV cache; skips
                    blocks beyond the live context (decode_32k/long_500k)
  ssd_scan        — Mamba2 chunked state-space scan (mamba2/zamba2 cells)
  knn             — blocked pairwise distances for Sizey's k-NN predictor
  ensemble_mlp    — fused (models x tasks) MLP forward for the Sizey pool
  split_hist      — row-order split histograms of Sizey's forest fit
"""
