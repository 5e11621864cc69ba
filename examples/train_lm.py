"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps
with checkpoint/restart and Sizey-sized memory (assignment deliverable b).

    PYTHONPATH=src python examples/train_lm.py            # ~100M, 300 steps
    PYTHONPATH=src python examples/train_lm.py --quick    # CPU-quick variant
"""
import sys

from repro.launch.train import main as train_main
from repro.utils import enable_compilation_cache

if __name__ == "__main__":
    enable_compilation_cache()
    if "--quick" in sys.argv:
        argv = ["--arch", "granite-3-2b", "--scale", "e2e-100m",
                "--steps", "40", "--batch", "4", "--seq", "128",
                "--ckpt-dir", "/tmp/repro_e2e_ckpt", "--sizey"]
    else:
        argv = ["--arch", "granite-3-2b", "--scale", "e2e-100m",
                "--steps", "300", "--batch", "8", "--seq", "256",
                "--ckpt-dir", "/tmp/repro_e2e_ckpt", "--sizey"]
        argv += sys.argv[1:]
    train_main(argv)
