"""Batched serving example: decoder-only audio-token model (musicgen
backbone) with Sizey-sized KV caches.

    PYTHONPATH=src python examples/serve_lm.py
"""
import sys

from repro.launch.serve import main as serve_main
from repro.utils import enable_compilation_cache

if __name__ == "__main__":
    enable_compilation_cache()
    # forward CLI args to the serving launcher (so --help and overrides
    # work); with none, run the documented musicgen demo configuration
    argv = sys.argv[1:] or ["--arch", "musicgen-large", "--requests", "16",
                            "--max-new", "24"]
    engine = serve_main(argv)
    sizer = engine.sizer
    if sizer is not None and sizer.decisions:
        last = sizer.decisions[-1]
        print(f"KV sizing decisions: {len(sizer.decisions)} "
              f"(last source={last.source}, alloc={last.allocation_gb:.3f} GB)")
