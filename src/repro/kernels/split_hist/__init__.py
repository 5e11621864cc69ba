from repro.kernels.split_hist.ops import split_hist
