"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package ships three modules:
  kernel.py — pl.pallas_call body with explicit BlockSpec VMEM tiling
  ops.py    — jit'd public wrapper (padding, layout, GQA handling)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels target TPU (MXU-aligned 128-blocks); tests validate them on CPU in
interpret mode. The sizing path imports three of them: ``ensemble_mlp``
(the pool MLP on a TPU), ``split_hist`` (the forest fit) and
``segment_dp`` (temporal segment boundaries). Nothing outside this
package and its tests imports the others: the model zoo runs its own jnp
attention and scan, and Sizey's k-NN runs in ``repro.core.models.knn``.

  flash_attention — blocked causal attention (online softmax), the memory
                    hot spot of train_4k/prefill_32k cells
  flash_decode    — single-token attention vs a long KV cache; skips
                    blocks beyond the live context (decode_32k/long_500k)
  ssd_scan        — Mamba2 chunked state-space scan (mamba2/zamba2 cells)
  knn             — blocked pairwise distances (no caller off the tests)
  ensemble_mlp    — fused (models x tasks) MLP forward for the Sizey pool
  split_hist      — row-order split histograms of Sizey's forest fit
  segment_dp      — segment-boundary DP of Sizey's temporal plans
"""
