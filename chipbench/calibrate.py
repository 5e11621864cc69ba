"""Readings that the limits of ``correct`` are set from (run on the chip).

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--out <file.jsonl>]

For each seed, in one process: one run of the cell as the benchmark makes
it (a measured window at the cell's own load), the check of the program
against the reference at float32 ``highest`` (the lower readings), and the
control: the reference computed with three-pass bfloat16 dot products
(``bf16x3``) put in the program's place and compared with the same
reference in the same way (the upper readings). One JSON line per seed;
the lines written to ``--out`` also hold every number of the program's
check (``control.program``) and list each sampled group's MLP gap under
both (``control.groups``: pool, rows of its fit, program gap, control
gap).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def control(journal, method, seed, numbers=None):
    """Compare the reference at bf16x3 with the reference at highest, on
    the same sampled groups as the run's own check; with the check's
    ``numbers``, also list each group's MLP gap under both."""
    import jax
    from chipbench.harness import check
    rp = check.replay(journal, method)
    picked = check.sample(rp.groups, seed)
    with jax.default_matmul_precision("highest"):
        hi, lo = ([check.reference_decisions(g, rp.pools, method, 32, prec)
                   for g in picked] for prec in ("highest", "bf16x3"))
    out = check.compare(lo, hi, method,
                        [g.m < check.YOUNG_LOG for g in picked])
    if numbers is not None:
        out["groups"] = [[list(g.key), g.fit_n, a, b] for g, a, b in zip(
            picked, numbers["mlp_by_group"], out["mlp_by_group"])]
        out["program"] = {k: v for k, v in numbers.items()
                          if k != "mlp_by_group"}
    del out["mlp_by_group"]
    return out


def main() -> int:
    from chipbench.harness import bench
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    for seed in a.seeds:
        t0 = time.perf_counter()
        args = bench.parse(["--workload", a.workload, "--seed", str(seed),
                            "--seconds", str(a.seconds), "--trace", "0"])
        out = bench.run(args, t0, after=control)
        row = {"workload": a.workload, "seed": seed,
               "correct": out["correct"],
               "program": {k: v["value"] for k, v in out["compared"].items()},
               "control": out["after"], "metrics": out["metrics"]}
        brief = dict(row, control={k: v for k, v in row["control"].items()
                                   if k not in ("groups", "program")})
        print(json.dumps(brief), flush=True)
        if a.out:
            with open(a.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
