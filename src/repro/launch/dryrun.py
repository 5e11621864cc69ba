"""Multi-pod dry-run (assignment MULTI-POD DRY-RUN).

Lowers + compiles every (architecture x input-shape) cell against the
production meshes — 16x16 = 256 chips single-pod and 2x16x16 = 512 chips
multi-pod — and records memory analysis, cost analysis, and the collective
schedule for the roofline report. No arrays are ever allocated: parameters,
optimizer state, batches, and caches are ShapeDtypeStructs.

Run:  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.dryrun \
          --out results/dryrun.jsonl

The placeholder devices are CPU host devices: 512 by default,
``REPRO_DRYRUN_DEVICES`` to shrink them (tests use 8).
"""
import argparse
import dataclasses
import json
import os
import re
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_bytes
from repro.analysis.roofline import roofline_terms
from repro.configs import ARCH_IDS, SHAPES, cell_is_applicable, get_config
from repro.distributed.sharding import (FSDP_AXES, axis_rules, batch_specs,
                                        cache_specs, param_specs)
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models.model import decode_step, params_shape, prefill
from repro.train.optimizer import make_optimizer
from repro.train.step import make_train_step

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def set_host_device_count(n: int) -> None:
    """Give the CPU backend ``n`` placeholder devices. The caller's other
    ``XLA_FLAGS`` are kept; only a count already there is replaced. Takes
    effect only before the first JAX backend use in this process."""
    flags = re.sub(rf"{_COUNT_FLAG}=\S*", "",
                   os.environ.get("XLA_FLAGS", "")).split()
    os.environ["XLA_FLAGS"] = " ".join(flags + [f"{_COUNT_FLAG}={n}"])


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _fsdp_size(mesh) -> int:
    names = set(mesh.axis_names)
    n = 1
    for a in FSDP_AXES:
        if a in names:
            n *= mesh.shape[a]
    return n


def _even_batch_specs(spec_tree, mesh):
    """Batch sharding, dropping the constraint when B doesn't divide."""
    fsdp_n = _fsdp_size(mesh)
    names = set(mesh.axis_names)
    fsdp = tuple(a for a in FSDP_AXES if a in names)

    def one(leaf):
        if leaf.shape and leaf.shape[0] % fsdp_n == 0:
            return P(fsdp, *([None] * (len(leaf.shape) - 1)))
        return P(*([None] * len(leaf.shape)))

    return jax.tree.map(one, spec_tree)


def _even_cache_specs(cache_shapes, mesh):
    specs = cache_specs(cache_shapes, mesh)
    fsdp_n = _fsdp_size(mesh)

    def fix(spec, leaf):
        # drop batch sharding when the batch dim doesn't divide (long_500k B=1)
        if len(leaf.shape) >= 2 and spec[1] is not None \
                and leaf.shape[1] % fsdp_n != 0:
            parts = list(spec)
            parts[1] = None
            return P(*parts)
        return spec

    return jax.tree.map(fix, specs, cache_shapes,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, mesh, *, optimizer="adamw",
               remat=None, cfg_override=None, param_dtype=None,
               kv_dtype=None, carry_cache=False, moe_dispatch=None,
               infer_tp=False, seq_shard=False, microbatches=1):
    """Lower one (arch x shape) cell on ``mesh``. Returns (lowered, meta)."""
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    repl = {}
    if remat is not None:
        repl["remat"] = remat
    if param_dtype is not None:
        repl["param_dtype"] = param_dtype
    if kv_dtype is not None:
        repl["kv_dtype"] = kv_dtype
    if carry_cache:
        repl["decode_carry_cache"] = True
    if moe_dispatch is not None:
        repl["moe_dispatch"] = moe_dispatch
    if seq_shard:
        repl["seq_shard"] = True
    if repl:
        cfg = dataclasses.replace(cfg, **repl)
    shape = SHAPES[shape_name]
    kind, spec = input_specs(cfg, shape)
    p_shapes = params_shape(cfg)
    # TP-only inference weights are a win only while the data-replicated
    # copy fits comfortably (grok fp32/16-way = 79 GB/chip would OOM);
    # above the threshold the ZeRO sharding stays.
    p_mode = "train"
    if infer_tp and kind != "train":
        from repro.utils.misc import tree_bytes
        model_n = mesh.shape.get("model", 1)
        if tree_bytes(p_shapes) / model_n / 1024**3 <= 8.0:
            p_mode = "inference"
    p_specs = param_specs(p_shapes, mesh, mode=p_mode)

    with axis_rules(mesh):
        if kind == "train":
            opt = make_optimizer(optimizer)
            o_shapes = jax.eval_shape(opt.init, p_shapes)
            if optimizer == "adamw":
                o_specs = {"m": p_specs, "v": p_specs, "step": P()}
            else:  # adafactor: factored moments replicate (small)
                o_specs = jax.tree.map(lambda _: P(), o_shapes)
            b_specs = _even_batch_specs(spec, mesh)
            step = make_train_step(cfg, opt, microbatches=microbatches)
            metric_specs = {"loss": P(), "grad_norm": P()}
            jitted = jax.jit(
                step,
                in_shardings=(_ns(mesh, p_specs), _ns(mesh, o_specs),
                              _ns(mesh, b_specs)),
                out_shardings=(_ns(mesh, metric_specs), _ns(mesh, p_specs),
                               _ns(mesh, o_specs)),
                donate_argnums=(0, 1))
            lowered = jitted.lower(p_shapes, o_shapes, spec)

        elif kind == "prefill":
            b_specs = _even_batch_specs(spec, mesh)
            cache_shapes = jax.eval_shape(
                lambda p, b: prefill(p, b, cfg)[1], p_shapes, spec)
            c_specs = _even_cache_specs(cache_shapes, mesh)
            logits_spec = _even_batch_specs(
                jax.eval_shape(lambda p, b: prefill(p, b, cfg)[0],
                               p_shapes, spec), mesh)
            jitted = jax.jit(
                lambda p, b: prefill(p, b, cfg),
                in_shardings=(_ns(mesh, p_specs), _ns(mesh, b_specs)),
                out_shardings=(_ns(mesh, logits_spec), _ns(mesh, c_specs)))
            lowered = jitted.lower(p_shapes, spec)

        else:  # decode
            tok_spec = _even_batch_specs(spec["tokens"], mesh)
            c_specs = _even_cache_specs(spec["cache"], mesh)
            logits_shape = jax.eval_shape(
                lambda p, c, t: decode_step(p, c, t, cfg)[0],
                p_shapes, spec["cache"], spec["tokens"])
            logits_spec = _even_batch_specs(logits_shape, mesh)
            jitted = jax.jit(
                lambda p, c, t: decode_step(p, c, t, cfg),
                in_shardings=(_ns(mesh, p_specs), _ns(mesh, c_specs),
                              _ns(mesh, tok_spec)),
                out_shardings=(_ns(mesh, logits_spec), _ns(mesh, c_specs)),
                donate_argnums=(1,))
            lowered = jitted.lower(p_shapes, spec["cache"], spec["tokens"])

    return lowered, {"cfg": cfg, "shape": shape, "kind": kind}


def _compile_costs(arch, shape_name, mesh, cfg_override=None, **lower_kw):
    """(flops, bytes_accessed, collective_bytes) of one compiled variant.

    cost_analysis() counts a scan/while body ONCE, not x trip-count, so the
    deep-stack cells are probed at depth 0 and depth ``layer_unit`` and
    extrapolated linearly (exact for the homogeneous stacks used here).
    """
    lowered, _ = lower_cell(arch, shape_name, mesh,
                            cfg_override=cfg_override, **lower_kw)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(coll["total_bytes"]), coll)


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             **lower_kw) -> dict:
    """lower + compile + analyse one cell; returns a JSON-serializable row."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_is_applicable(cfg, shape)
    row = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": mesh.size}
    if not ok:
        row.update(status="skipped", reason=reason)
        return row

    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, mesh, **lower_kw)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # memory analysis from the REAL full-depth artifact (proves it fits)
    mem = compiled.memory_analysis()
    arg_b = getattr(mem, "argument_size_in_bytes", 0)
    out_b = getattr(mem, "output_size_in_bytes", 0)
    tmp_b = getattr(mem, "temp_size_in_bytes", 0)
    alias_b = getattr(mem, "alias_size_in_bytes", 0)
    peak_gb = (arg_b + out_b + tmp_b - alias_b) / 1024**3

    # cost analysis via depth probes (scan bodies count once per trip here);
    # probes force naive attention (identical FLOPs/bytes semantics, no
    # internal lax.map/scan whose trip counts cost_analysis would drop) and
    # microbatches=1 (gradient accumulation changes memory, not total
    # FLOPs/bytes/collectives — the accumulation scan is a loop too).
    t0 = time.time()
    unit = meta["cfg"].layer_unit
    units = meta["cfg"].n_layers // unit
    probe_kw = dict(lower_kw, microbatches=1)
    probe_cfg = dataclasses.replace(meta["cfg"], attn_impl="naive")
    f1, b1, c1, coll1 = _compile_costs(arch, shape_name, mesh,
                                       cfg_override=probe_cfg.with_layers(unit),
                                       **probe_kw)
    f0, b0, c0, _ = _compile_costs(arch, shape_name, mesh,
                                   cfg_override=probe_cfg.with_layers(0),
                                   **probe_kw)
    t_probe = time.time() - t0
    flops = f0 + units * max(f1 - f0, 0.0)
    bytes_acc = b0 + units * max(b1 - b0, 0.0)
    coll_bytes = c0 + units * max(c1 - c0, 0.0)

    report = roofline_terms(arch, shape, meta["cfg"], mesh_name, mesh.size,
                            flops, bytes_acc, coll_bytes,
                            peak_memory_gb=peak_gb)
    row.update(
        status="ok", kind=meta["kind"],
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        probe_s=round(t_probe, 2),
        memory={"argument_gb": arg_b / 1024**3, "output_gb": out_b / 1024**3,
                "temp_gb": tmp_b / 1024**3, "alias_gb": alias_b / 1024**3,
                "peak_gb": peak_gb},
        cost={"flops": flops, "bytes_accessed": bytes_acc,
              "collective_bytes": coll_bytes},
        collectives_unit=coll1,
        roofline=dataclasses.asdict(report),
    )
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--remat", default=None)
    # §Perf optimization knobs (EXPERIMENTS.md hillclimb)
    ap.add_argument("--param-dtype", default=None,
                    help="e.g. bfloat16: halves FSDP weight collectives")
    ap.add_argument("--kv-dtype", default=None,
                    help="e.g. float8_e4m3fn: halves decode KV HBM")
    ap.add_argument("--carry-cache", action="store_true",
                    help="decode cache in scan carry (in-place aliasing)")
    ap.add_argument("--moe-dispatch", default=None,
                    choices=[None, "flat", "rowwise", "grouped"],
                    help="rowwise: per-sequence position-in-expert cumsum")
    ap.add_argument("--infer-tp", action="store_true",
                    help="TP-only weights for prefill/decode cells")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-parallel residual activations")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation splits (train cells)")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--tag", default="",
                    help="experiment tag copied into every row (§Perf)")
    ap.add_argument("--test-mesh", action="store_true",
                    help="scaled-down meshes (REPRO_DRYRUN_DEVICES=8)")
    args = ap.parse_args()
    set_host_device_count(int(os.environ.get("REPRO_DRYRUN_DEVICES", 512)))

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")

    if args.test_mesh:
        meshes = {"single": make_test_mesh(2, 2),
                  "multi": make_test_mesh(2, 2, pod=2)}
    else:
        meshes = {"single": make_production_mesh(multi_pod=False),
                  "multi": make_production_mesh(multi_pod=True)}
    if args.mesh != "both":
        meshes = {args.mesh: meshes[args.mesh]}

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    n_ok = n_skip = n_fail = 0
    with open(args.out, "a") as f:
        for mesh_name, mesh in meshes.items():
            for arch in archs:
                for shape_name in shapes:
                    t0 = time.time()
                    try:
                        row = run_cell(arch, shape_name, mesh, mesh_name,
                                       optimizer=args.optimizer,
                                       remat=args.remat,
                                       param_dtype=args.param_dtype,
                                       kv_dtype=args.kv_dtype,
                                       carry_cache=args.carry_cache,
                                       moe_dispatch=args.moe_dispatch,
                                       infer_tp=args.infer_tp,
                                       seq_shard=args.seq_shard,
                                       microbatches=args.microbatches)
                    except Exception as e:  # noqa: BLE001 — cell isolation
                        row = {"arch": arch, "shape": shape_name,
                               "mesh": mesh_name, "status": "error",
                               "error": f"{type(e).__name__}: {e}",
                               "traceback": traceback.format_exc()[-2000:]}
                    row["wall_s"] = round(time.time() - t0, 2)
                    if args.tag:
                        row["tag"] = args.tag
                    f.write(json.dumps(row) + "\n")
                    f.flush()
                    status = row["status"]
                    n_ok += status == "ok"
                    n_skip += status == "skipped"
                    n_fail += status == "error"
                    bn = row.get("roofline", {}).get("bottleneck", "-")
                    peak = row.get("memory", {}).get("peak_gb", 0.0)
                    print(f"[{mesh_name:6s}] {arch:22s} {shape_name:12s} "
                          f"{status:8s} {row['wall_s']:7.1f}s "
                          f"peak={peak:7.2f}GB bottleneck={bn}",
                          flush=True)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
