"""The reduction from a profiler trace to device metrics."""
import os
import re

import pytest

from chipbench.harness import trace_reduce as tr

MS = 1_000_000


def _trace():
    # window [0, 100) ms; chip 0 busy [10, 30) and [25, 40) (overlap),
    # [60, 70); the host is in a journal append over [40, 60)
    ops = {0: [(10 * MS, 30 * MS, "fusion.1"), (25 * MS, 40 * MS, "dot.2"),
               (60 * MS, 70 * MS, "_mlp_body"), (95 * MS, 120 * MS, "late")]}
    modules = {0: [(10 * MS, 40 * MS, "jit_observe_fn(12)"),
                   (60 * MS, 70 * MS, "jit_fn(3)"),
                   (95 * MS, 120 * MS, "jit_fn(3)")]}
    host = [(0, 100 * MS, "bench.window"),
            (5 * MS, 90 * MS, "bench.engine_step"),
            (40 * MS, 60 * MS, "bench.journal_append")]
    return tr.Trace(ops, modules, host)


def test_busy_union_and_idle_share():
    red = tr.reduce(_trace())
    assert red["window_s"] == pytest.approx(0.1)
    # [10,40) + [60,70) + [95,100) clipped to the window = 45 ms
    assert red["busy_s"] == pytest.approx(0.045)
    assert red["idle_pct"] == pytest.approx(55.0)


def test_program_time_by_name_inside_the_window():
    progs = tr.reduce(_trace())["programs"]
    assert progs["jit_observe_fn"] == (1, pytest.approx(0.03))
    # the jit_fn event that runs past the window's end is left out
    assert progs["jit_fn"] == (1, pytest.approx(0.01))


def test_idle_gaps_by_host_activity():
    # gaps [0,10) and [70,95) fall in the engine step, [40,60) in the
    # journal append nested in it
    idle = tr.reduce(_trace())["idle_by_host"]
    assert idle == {"engine_step": pytest.approx(0.035),
                    "journal_append": pytest.approx(0.02)}
    t = _trace()
    t.host = [h for h in t.host if h[2] == "bench.window"]
    assert tr.reduce(t)["idle_by_host"] == {"service": pytest.approx(0.055)}


def test_kernel_events_by_pattern():
    t = _trace()
    n, s = tr.op_times(t, 0, 100 * MS, r"_mlp_body|ensemble_mlp")
    assert (n, s) == (1, pytest.approx(0.01))


def test_reads_host_annotations_from_a_real_capture(tmp_path):
    # a profiler capture of this process: the reader finds the window and
    # the nested step annotations in the .xplane.pb it writes (the CPU
    # backend has no /device:TPU plane, so no device operations)
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load_xplane(tr.find_xplane(str(tmp_path)))
    assert [n for _, _, n in sorted(t.host)] == [
        "bench.window", "bench.engine_step", "bench.engine_step"]
    lo, hi = tr.window_of(t)
    assert all(lo <= a <= b <= hi for a, b, _ in t.host)
    red = tr.reduce(t)
    assert red["busy_s"] == 0.0 and red["window_s"] > 0


def test_extract_round_trip(tmp_path):
    p = str(tmp_path / "t.json.gz")
    tr.save_extract(_trace(), p)
    assert tr.reduce(tr.load_extract(p)) == tr.reduce(_trace())



# Pieces of two traced runs on one TPU v5 lite chip (``run.py --trace 1
# --save-trace``), cut with ``trace_reduce.cut``: 60 ms of
# chipseq-temporal.warm around a refresh, 40 ms of mag-peak.warm around a
# full-fit observe.
DATA = os.path.join(os.path.dirname(__file__), "data")
PIECES = {"tpu_v5_lite_chipseq-temporal.json.gz": {
              "jit_refresh_fn", "jit_fn", "jit__fit_cuts_jit"},
          "tpu_v5_lite_mag-peak.json.gz": {"jit_observe_fn", "jit_fn"}}


def _busy_by_sweep(events, lo, hi) -> int:
    """Nanoseconds inside [lo, hi] covered by at least one event, by a
    sweep over the event boundaries."""
    marks = sorted([(max(a, lo), 1) for a, b, _ in events if b > lo and a < hi]
                   + [(min(b, hi), -1) for a, b, _ in events
                      if b > lo and a < hi])
    busy, depth, last = 0, 0, lo
    for t, d in marks:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_tpu_trace_busy_and_idle(piece):
    t = tr.load_extract(os.path.join(DATA, piece))
    red = tr.reduce(t)
    lo, hi = red["lo"], red["hi"]
    assert list(t.ops) == [0]
    busy = _busy_by_sweep(t.ops[0], lo, hi)
    assert 0 < busy < hi - lo
    assert red["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    assert red["idle_pct"] == pytest.approx(100 * (1 - busy / (hi - lo)))
    # every idle nanosecond is charged to what the host was doing
    assert sum(red["idle_by_host"].values()) == pytest.approx(
        (hi - lo - busy) * 1e-9)


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_tpu_trace_programs_by_name(piece):
    t = tr.load_extract(os.path.join(DATA, piece))
    red = tr.reduce(t)
    lo, hi = red["lo"], red["hi"]
    want: dict = {}
    for a, b, n in t.modules[0]:
        if lo <= a and b <= hi:
            name = re.match(r"(.*?)\(\d+\)$", n).group(1)
            k, s = want.get(name, (0, 0.0))
            want[name] = (k + 1, s + (b - a) * 1e-9)
    assert PIECES[piece] <= set(red["programs"])
    assert red["programs"] == {k: (n, pytest.approx(s))
                               for k, (n, s) in want.items()}


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_tpu_trace_kernel_events_are_the_kernels_own(piece):
    from chipbench.metrics.mlp_kernel_roofline_pct import KERNEL
    t = tr.load_extract(os.path.join(DATA, piece))
    lo, hi = tr.window_of(t)
    own = [(a, b) for a, b, n in t.ops[0] if lo <= a and b <= hi
           and n.split(" = ")[0].startswith("%ensemble_mlp_forward")]
    users = [n for a, b, n in t.ops[0]
             if "%ensemble_mlp_forward" in n.split(" = ", 1)[-1]]
    assert own and users          # its output feeds other operations
    n, s = tr.op_times(t, lo, hi, KERNEL)
    assert n == len(own)
    assert s == pytest.approx(sum(b - a for a, b in own) * 1e-9)
