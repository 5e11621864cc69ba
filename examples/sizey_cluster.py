"""Sizey sizing LM jobs on the TPU fleet — the paper's technique as a
first-class framework feature.

Ground truth comes from the multi-pod dry-run's compiled
memory_analysis() (results/dryrun.jsonl): each (arch x shape x mesh) cell
is a "task type" whose peak per-chip HBM Sizey learns online from cheap
job features (param GB/chip, tokens/chip, context length). Jobs stream in
repeatedly with jittered shapes; Sizey's allocation replaces the static
"reserve the whole 16 GB chip" preset, and OOM-kills follow the paper's
retry ladder.

    PYTHONPATH=src python examples/sizey_cluster.py
"""
import json
import os

import numpy as np

from repro.configs import SHAPES, get_config
from repro.core import SizeyConfig
from repro.launch.sizing import SizeyJobSizer
from repro.utils import enable_compilation_cache

DRYRUN = os.environ.get("REPRO_DRYRUN_RESULTS", "results/dryrun.jsonl")


def load_cells():
    cells = []
    for line in open(DRYRUN):
        r = json.loads(line)
        if r.get("status") == "ok":
            cells.append((r["arch"], r["shape"], r["mesh"],
                          r["memory"]["peak_gb"]))
    return cells


def main():
    import argparse
    global DRYRUN
    ap = argparse.ArgumentParser(
        description="Sizey sizing LM jobs from dry-run memory analysis")
    ap.add_argument("--dryrun", default=DRYRUN,
                    help="dry-run results JSONL (default: "
                         "$REPRO_DRYRUN_RESULTS or results/dryrun.jsonl)")
    args = ap.parse_args()
    enable_compilation_cache()
    DRYRUN = args.dryrun
    cells = load_cells()
    if not cells:
        raise SystemExit(f"no dry-run rows in {DRYRUN}; run "
                         "python -m repro.launch.dryrun first")
    hbm_cap = max(p for *_, p in cells) * 2  # fleet nodes sized for worst
    preset = hbm_cap                          # static policy: reserve cap
    sizer = SizeyJobSizer(SizeyConfig(min_history=2), hbm_cap_gb=hbm_cap,
                          preset_gb=preset)
    rng = np.random.default_rng(0)

    waste_sizey = waste_preset = 0.0
    ooms = 0
    n_jobs = 600
    for i in range(n_jobs):
        arch, shape_name, mesh, true_peak = cells[rng.integers(len(cells))]
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        chips = 256 if mesh == "single" else 512
        # jobs vary run to run (input jitter ~ the paper's input-size spread)
        peak = float(true_peak * rng.uniform(0.9, 1.1))
        runtime_h = float(rng.uniform(0.2, 2.0))

        job = sizer.size_job(arch, cfg, shape, mesh, chips)
        alloc = job.sizing.allocation_gb
        attempts = 1
        while alloc < peak:          # OOM-kill -> paper ladder
            waste_sizey += alloc * runtime_h * 0.1  # fails fast (ttf=0.1)
            ooms += 1
            alloc = sizer.retry_allocation(job, attempts, alloc)
            attempts += 1
        waste_sizey += (alloc - peak) * runtime_h
        waste_preset += (preset - peak) * runtime_h
        sizer.observe_job(job, peak, runtime_h, attempts)

    print(f"jobs: {n_jobs}  (cells: {len(cells)}, cap {hbm_cap:.0f} GB/chip)")
    print(f"static-preset wastage: {waste_preset:10.1f} GBh/chip")
    print(f"sizey wastage:         {waste_sizey:10.1f} GBh/chip "
          f"({ooms} OOM retries)")
    print(f"reduction: {100 * (1 - waste_sizey / waste_preset):.1f}%")


if __name__ == "__main__":
    main()
