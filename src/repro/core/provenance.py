"""Provenance database (paper Fig. 3, phase 1/3).

Stores completed task executions per (task_type, machine) key in
fixed-capacity **device-resident** jax ring buffers that grow geometrically
(so the jitted model code sees a small, bounded set of static shapes), plus
the *prequential* prediction log used by the accuracy score and the offset
selector. Buffers are updated in place by a small set of jitted appenders
with donated arguments — the hot predict/observe path never re-uploads
history from the host. Host-side numpy survives only at the edges: JSONL
persistence and benchmark/analysis reads (``np.asarray`` on any buffer).

Persistence covers BOTH record kinds so a resumed workflow restarts warm:

  * task records   — one JSON object per completed execution (legacy lines
    without a ``kind`` field parse as these, so old checkpoint files load);
  * log records    — ``{"kind": "log", ...}`` lines carrying the per-model
    predictions, aggregate, actual and runtime of each prediction Sizey
    actually emitted, replayed into the prequential log on restore so the
    offset selector and adaptive alpha do not restart cold;
  * aux records    — any other ``kind`` (e.g. the temporal subsystem's
    ``"curve"`` usage profiles) round-trips opaquely via
    :meth:`ProvenanceDB.add_aux` and is handed back grouped by kind in
    ``ProvenanceDB.aux`` on restore — subsystem state rides the same
    checkpoint file without the core schema knowing its shape.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import tempfile
import warnings
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

INITIAL_CAP = 128
# doubling (not x4) keeps at most 2x padding overhead in every masked
# kernel over the buffers while still bounding compiles at O(log history)
GROWTH = 2


def read_jsonl_lines(path: str) -> tuple[list[str], bool]:
    """Read a checkpoint JSONL as raw lines, tolerating a torn FINAL line
    (the one failure mode of a crash mid-append on a POSIX filesystem:
    appends are sequential, so only the last record can be partial).
    Returns ``(intact_lines, truncated)``. A malformed line anywhere BUT
    the end is real corruption and raises — silently skipping it would
    desynchronize the predictor history from the journal."""
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    truncated = False
    if lines:
        try:
            json.loads(lines[-1])
        except json.JSONDecodeError:
            lines = lines[:-1]
            truncated = True
    for i, ln in enumerate(lines):
        try:
            json.loads(ln)
        except json.JSONDecodeError as e:
            raise ValueError(
                f"{path}: corrupt (non-final) checkpoint line {i + 1}: "
                f"{e}") from None
    return lines, truncated


def atomic_rewrite_jsonl(path: str, lines: list[str]) -> None:
    """Replace ``path`` with ``lines`` atomically (write-temp + fsync +
    rename): readers — and a recovery racing a crash — see either the old
    file or the complete new one, never a torn intermediate."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path) + ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            for ln in lines:
                f.write(ln + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


@dataclasses.dataclass
class TaskRecord:
    """One completed task execution."""
    task_type: str
    machine: str
    features: tuple[float, ...]   # e.g. (input_size_gb,)
    peak_mem_gb: float
    runtime_h: float
    attempts: int = 1
    workflow: str = ""

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @staticmethod
    def from_json(line: str) -> "TaskRecord":
        d = json.loads(line)
        d["features"] = tuple(d["features"])
        return TaskRecord(**d)


# In-place donated appends compose safely with model states that alias
# these buffers (e.g. KNNState's pass-through of xs/ys/mask): an append
# only writes the row at index `count`, which every live state masks out
# (its mask horizon predates the append), so aliased readers see identical
# numerics; backends that cannot honor a donation fall back to a copy.
@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _append_history(xs, ys, runtimes, mask, i, x, y, rt):
    return (xs.at[i].set(x), ys.at[i].set(y), runtimes.at[i].set(rt),
            mask.at[i].set(1.0))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _append_log(model_preds, agg, actual, runtime, mask, j, p, a, y, rt):
    return (model_preds.at[:, j].set(p), agg.at[j].set(a),
            actual.at[j].set(y), runtime.at[j].set(rt), mask.at[j].set(1.0))


def _pad_rows(arr: jnp.ndarray, new_rows: int, axis: int = 0) -> jnp.ndarray:
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, new_rows - arr.shape[axis])
    return jnp.pad(arr, pad)


def _cap_for(n: int) -> int:
    """Smallest geometric-growth capacity holding n rows."""
    cap = INITIAL_CAP
    while cap < n:
        cap *= GROWTH
    return cap


def _padded(host: np.ndarray, cap: int, axis: int = 0) -> jnp.ndarray:
    out = np.zeros((*host.shape[:axis], cap, *host.shape[axis + 1:]),
                   np.float32)
    out[(slice(None),) * axis + (slice(0, host.shape[axis]),)] = host
    return jnp.asarray(out)


class _PoolBuffers:
    """Masked, geometrically-growing device buffers for one (task_type, machine).

    All array attributes are jax arrays living on the default device; scalar
    bookkeeping (count/cap/max_seen_gb) stays host-side so the scheduler can
    branch on it without a device sync.
    """

    def __init__(self, n_features: int, n_models: int):
        self.cap = INITIAL_CAP
        self.count = 0
        self.n_models = n_models
        self.xs = jnp.zeros((self.cap, n_features), jnp.float32)
        self.ys = jnp.zeros((self.cap,), jnp.float32)
        self.runtimes = jnp.zeros((self.cap,), jnp.float32)
        self.mask = jnp.zeros((self.cap,), jnp.float32)
        # per-model in-sample predictions over the buffer, refreshed after
        # every fit/update — feeds the accuracy score (Eq. 1)
        self.insample_preds = jnp.zeros((n_models, self.cap), jnp.float32)
        # prequential prediction log (only rows where Sizey really predicted)
        self.log_cap = INITIAL_CAP
        self.log_count = 0
        self.log_model_preds = jnp.zeros((n_models, self.log_cap), jnp.float32)
        self.log_agg = jnp.zeros((self.log_cap,), jnp.float32)
        self.log_actual = jnp.zeros((self.log_cap,), jnp.float32)
        self.log_runtime = jnp.zeros((self.log_cap,), jnp.float32)
        self.log_mask = jnp.zeros((self.log_cap,), jnp.float32)
        self.max_seen_gb = 0.0

    def add(self, features: np.ndarray, y: float, runtime_h: float) -> int:
        if self.count == self.cap:
            self.cap *= GROWTH
            self.xs = _pad_rows(self.xs, self.cap)
            self.ys = _pad_rows(self.ys, self.cap)
            self.runtimes = _pad_rows(self.runtimes, self.cap)
            self.mask = _pad_rows(self.mask, self.cap)
            self.insample_preds = _pad_rows(self.insample_preds, self.cap,
                                            axis=1)
        i = self.count
        self.xs, self.ys, self.runtimes, self.mask = _append_history(
            self.xs, self.ys, self.runtimes, self.mask, i,
            jnp.asarray(features, jnp.float32), float(y), float(runtime_h))
        self.count += 1
        self.max_seen_gb = max(self.max_seen_gb, float(y))
        return i

    def bulk_load(self, feats: np.ndarray, ys: np.ndarray,
                  rts: np.ndarray) -> None:
        """Checkpoint restore: upload a whole history in one shot instead
        of one jitted append per record. Fresh pools only."""
        n = len(ys)
        if n == 0:
            return
        assert self.count == 0, "bulk_load on a non-empty pool"
        self.cap = _cap_for(n)
        self.xs = _padded(np.asarray(feats, np.float32), self.cap)
        self.ys = _padded(np.asarray(ys, np.float32), self.cap)
        self.runtimes = _padded(np.asarray(rts, np.float32), self.cap)
        self.mask = _padded(np.ones((n,), np.float32), self.cap)
        self.insample_preds = jnp.zeros((self.n_models, self.cap),
                                        jnp.float32)
        self.count = n
        self.max_seen_gb = float(np.max(ys))  # before the float32 cast

    def bulk_load_log(self, model_preds: np.ndarray, aggs: np.ndarray,
                      actuals: np.ndarray, rts: np.ndarray) -> None:
        """Checkpoint restore of the prequential log, one upload per pool."""
        n = len(aggs)
        if n == 0:
            return
        assert self.log_count == 0, "bulk_load_log on a non-empty log"
        self.log_cap = _cap_for(n)
        self.log_model_preds = _padded(np.asarray(model_preds, np.float32),
                                       self.log_cap, axis=1)
        self.log_agg = _padded(np.asarray(aggs, np.float32), self.log_cap)
        self.log_actual = _padded(np.asarray(actuals, np.float32),
                                  self.log_cap)
        self.log_runtime = _padded(np.asarray(rts, np.float32), self.log_cap)
        self.log_mask = _padded(np.ones((n,), np.float32), self.log_cap)
        self.log_count = n

    def add_log(self, model_preds, agg: float, actual: float,
                runtime_h: float) -> None:
        if self.log_count == self.log_cap:
            self.log_cap *= GROWTH
            self.log_model_preds = _pad_rows(self.log_model_preds,
                                             self.log_cap, axis=1)
            self.log_agg = _pad_rows(self.log_agg, self.log_cap)
            self.log_actual = _pad_rows(self.log_actual, self.log_cap)
            self.log_runtime = _pad_rows(self.log_runtime, self.log_cap)
            self.log_mask = _pad_rows(self.log_mask, self.log_cap)
        j = self.log_count
        (self.log_model_preds, self.log_agg, self.log_actual,
         self.log_runtime, self.log_mask) = _append_log(
            self.log_model_preds, self.log_agg, self.log_actual,
            self.log_runtime, self.log_mask, j,
            jnp.asarray(model_preds, jnp.float32), float(agg), float(actual),
            float(runtime_h))
        self.log_count += 1


class ProvenanceDB:
    """All task history, keyed by (task_type, machine)."""

    def __init__(self, n_features: int = 1, n_models: int = 4,
                 persist_path: str | None = None):
        self.n_features = n_features
        self.n_models = n_models
        self.pools: dict[tuple[str, str], _PoolBuffers] = {}
        self.records: list[TaskRecord] = []
        # non-core checkpoint rows restored from the JSONL, grouped by
        # kind (see add_aux) — e.g. the temporal predictor's usage profiles
        self.aux: dict[str, list[dict]] = {}
        self.persist_path = persist_path
        if persist_path and os.path.exists(persist_path):
            # bulk restore: group rows per pool and upload each pool's
            # buffers once — O(pools) dispatches, not O(records)
            tasks: dict[tuple[str, str], list[TaskRecord]] = {}
            logs: dict[tuple[str, str], list[dict]] = {}
            for kind, payload in self._read_jsonl(persist_path):
                if kind == "task":
                    self.records.append(payload)
                    tasks.setdefault((payload.task_type, payload.machine),
                                     []).append(payload)
                elif kind == "log":
                    logs.setdefault((payload["task_type"],
                                     payload["machine"]), []).append(payload)
                else:
                    self.aux.setdefault(kind, []).append(payload)
            for key, recs in tasks.items():
                # ys stay float64 here: bulk_load takes max_seen_gb over the
                # full-precision record values (matching the online path)
                # before the buffers are cast to float32
                self.pool(*key).bulk_load(
                    np.asarray([r.features for r in recs], np.float32),
                    np.asarray([r.peak_mem_gb for r in recs]),
                    np.asarray([r.runtime_h for r in recs], np.float32))
            for key, rows in logs.items():
                self.pool(*key).bulk_load_log(
                    np.asarray([r["model_preds"] for r in rows],
                               np.float32).T,
                    np.asarray([r["agg"] for r in rows], np.float32),
                    np.asarray([r["actual"] for r in rows], np.float32),
                    np.asarray([r["runtime_h"] for r in rows], np.float32))

    def _read_jsonl(self, path: str) -> Iterator[tuple[str, object]]:
        lines, truncated = read_jsonl_lines(path)
        if truncated:
            # a crash tore the last append mid-line; the intact prefix is
            # a consistent checkpoint (appends are sequential), so restore
            # from it — loudly, because one record was lost
            warnings.warn(f"{path}: dropped a torn final checkpoint line "
                          f"(crash mid-append); restoring from the intact "
                          f"prefix", RuntimeWarning, stacklevel=2)
        for line in lines:
            d = json.loads(line)
            kind = d.pop("kind", None)
            if kind is None or kind == "task":
                d["features"] = tuple(d["features"])
                yield "task", TaskRecord(**d)
            elif kind == "log":
                yield "log", d
            else:
                yield kind, d

    def pool(self, task_type: str, machine: str) -> _PoolBuffers:
        key = (task_type, machine)
        if key not in self.pools:
            self.pools[key] = _PoolBuffers(self.n_features, self.n_models)
        return self.pools[key]

    def _ingest(self, rec: TaskRecord) -> None:
        self.records.append(rec)
        self.pool(rec.task_type, rec.machine).add(
            np.asarray(rec.features, np.float32), rec.peak_mem_gb,
            rec.runtime_h)

    def add(self, rec: TaskRecord) -> None:
        self._ingest(rec)
        if self.persist_path:
            with open(self.persist_path, "a") as f:
                f.write(rec.to_json() + "\n")

    def add_log(self, task_type: str, machine: str, model_preds, agg: float,
                actual: float, runtime_h: float) -> None:
        """Append one prequential-log row (and persist it, if configured)."""
        self.pool(task_type, machine).add_log(model_preds, agg, actual,
                                              runtime_h)
        if self.persist_path:
            row = {"kind": "log", "task_type": task_type, "machine": machine,
                   "model_preds": [float(p) for p in np.asarray(model_preds)],
                   "agg": float(agg), "actual": float(actual),
                   "runtime_h": float(runtime_h)}
            with open(self.persist_path, "a") as f:
                f.write(json.dumps(row) + "\n")

    def add_aux(self, kind: str, payload: dict) -> int:
        """Append one subsystem-owned checkpoint row (``kind`` must not be
        ``"log"``/``"task"``). Collected into ``self.aux[kind]`` and
        persisted alongside the core rows, so e.g. temporal usage profiles
        survive the same JSONL round-trip as the history they annotate.
        Returns the bytes written (0 without a ``persist_path``)."""
        if kind in ("log", "task"):
            raise ValueError(f"aux kind {kind!r} collides with core rows")
        self.aux.setdefault(kind, []).append(payload)
        if not self.persist_path:
            return 0
        line = json.dumps({"kind": kind, **payload}) + "\n"
        with open(self.persist_path, "a") as f:
            f.write(line)
        return len(line)

    def history_size(self, task_type: str, machine: str) -> int:
        key = (task_type, machine)
        return self.pools[key].count if key in self.pools else 0
