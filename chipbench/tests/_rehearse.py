"""Run one cell end to end on the CPU at a tiny scale: the chip check is
stubbed and the persistent compilation cache left off."""
from __future__ import annotations

import time

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# per cell: Table I scale of the earlier runs and of the served run, and
# the engine steps of the window: enough that pools decide after their
# own completions, so that every planted fault can show
CELLS = {"mag-peak.warm": {"scale": 0.1, "run_scale": 0.3, "steps": 100},
         "chipseq-temporal.warm": {"scale": 0.25, "run_scale": 0.25,
                                   "steps": 300}}


def close_after(monkeypatch, steps: int) -> None:
    """End the window after ``steps`` engine steps instead of at its time,
    so that a rehearsal checks the same work however loaded the machine
    is."""
    from chipbench.harness import serve
    orig = serve.instrument

    def counted(handle, win, seconds, annotate):
        restore = orig(handle, win, seconds, annotate)
        timed = handle.engine.step

        def step():
            if len(win.steps) >= steps:
                win.end = time.perf_counter()
                raise serve.WindowClosed()
            return timed()
        handle.engine.step = step
        return restore
    monkeypatch.setattr(serve, "instrument", counted)


def rehearse(monkeypatch, name: str, seed: int = 2**31 + 17, after=None
             ) -> dict:
    from chipbench.harness import bench, spec
    monkeypatch.setattr(bench, "setup_cache", lambda: None)
    c = CELLS[name]
    close_after(monkeypatch, c["steps"])
    cell = spec.load_cell(name)
    cell.traffic = dict(cell.traffic, prior_scale=c["scale"],
                        run_scale=c["run_scale"])
    args = bench.parse(["--workload", name, "--seed", str(seed),
                        "--seconds", "600", "--trace", "0"])
    return bench.run(args, time.perf_counter(), require=lambda chips: CPU,
                     cell=cell, after=after)
