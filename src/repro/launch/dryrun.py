"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and dump the roofline inputs.

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
        --shape train_4k [--multi-pod] [--all] [--out artifacts/dryrun]

For each cell:  jax.jit(step, in_shardings, out_shardings).lower(SDS...)
.compile() on the 16×16 (single-pod) or 2×16×16 (multi-pod) mesh; prints
``memory_analysis()`` (fits-per-device proof) and ``cost_analysis()``
(FLOPs/bytes) and writes a JSON artifact with the parsed collective bytes —
EXPERIMENTS.md §Dry-run/§Roofline read these files."""

import os

# The dry-run simulates production meshes on CPU devices: it pins the CPU
# (it must never claim a chip another process is using) and adds the device
# count to whatever XLA_FLAGS already holds.  Both must precede jax's
# backend initialization.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512"
                           ).strip()

import argparse
import json
import time
import traceback


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, structure: str | None = None,
             kv_quant: bool = False, verbose: bool = True) -> dict:
    # imports deferred: XLA_FLAGS must be set before jax initializes
    import dataclasses
    import jax
    from repro import configs
    from repro.configs import SHAPES, get, shape_applicable
    from repro.launch.cells import lower_cell, make_cell
    from repro.launch.mesh import make_parallel, make_production_mesh
    from repro.roofline import analyze_compiled, model_flops

    cfg = get(arch_name, structure)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    if os.environ.get("REPRO_BLAST_TP") == "block":
        cfg = dataclasses.replace(
            cfg, structure=dataclasses.replace(cfg.structure, tp="block"),
            structure_ffn=(dataclasses.replace(cfg.structure_ffn, tp="block")
                           if cfg.structure_ffn else None))
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    record: dict = {"arch": arch_name, "shape": shape_name,
                    "structure": structure or cfg.structure.kind,
                    "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        record["status"] = "skipped"
        record["reason"] = why
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            tag = f"{arch_name}__{shape_name}__{record['mesh']}"
            with open(os.path.join(out_dir, tag + ".json"), "w") as f:
                json.dump(record, f, indent=1)
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    # serve layout (params TP-sharded, data-replicated — no per-token weight
    # all-gather) only when the replicated copy fits; giants like the 671B
    # keep the fully-sharded layout and amortize the gather over the batch.
    serve = False
    if shape.kind == "decode":
        import numpy as np
        from repro.models import build_model
        probe = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        param_bytes = sum(np.prod(l.shape) * l.dtype.itemsize
                          for l in jax.tree.leaves(probe))
        tp = mesh.shape.get("model", 1)
        serve = param_bytes / tp < 8e9
    parallel = make_parallel(mesh, global_batch=shape.global_batch,
                             serve=serve)
    n_dev = mesh.size
    t0 = time.time()
    try:
        cell = make_cell(cfg, shape, parallel)
        rep = cell.static.get("replication")
        if rep is not None:
            # surface the previously-silent divisibility fallbacks: leaves
            # that wanted a mesh axis but stayed replicated
            record["replication"] = {
                **{k: rep[k] for k in ("total_bytes", "replicated_bytes",
                                       "replicated_frac",
                                       "replicated_leaves")},
                "leaves": sorted(rep["leaves"],
                                 key=lambda e: -e["nbytes"])[:16],
            }
            if verbose and rep["replicated_leaves"]:
                print(f"[dryrun]   replicated (indivisible dims): "
                      f"{rep['replicated_bytes'] / 1e6:.1f} MB across "
                      f"{rep['replicated_leaves']} leaves "
                      f"({rep['replicated_frac']:.1%} of params)")
        lowered = lower_cell(cell)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        terms = analyze_compiled(compiled)
        record.update(
            status="ok", devices=n_dev,
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            roofline=terms.to_dict(),
        )
        if mem is not None:
            record["memory"] = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            }
        if verbose:
            print(f"[dryrun] {record['arch']} × {shape_name} "
                  f"({record['mesh']}): OK "
                  f"compute {terms.t_compute*1e3:.1f}ms "
                  f"memory {terms.t_memory*1e3:.1f}ms "
                  f"collective {terms.t_collective*1e3:.1f}ms "
                  f"→ {terms.dominant}-bound "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
            print(f"[dryrun]   memory_analysis: {record.get('memory')}")
    except Exception as e:  # a failure here is a bug in our sharding config
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[dryrun] {arch_name} × {shape_name}: FAILED {record['error']}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch_name}__{shape_name}__{record['mesh']}"
        if structure:
            tag += f"__{structure}"
        if kv_quant:
            tag += "__kvq"
            record["kv_quant"] = True
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(record, f, indent=1, default=float)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--structure", default=None,
                    help="dense | blast50 | low_rank50 | monarch50 | ...")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (decode cells)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every assigned (arch × shape) cell")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    from repro import configs  # deferred

    results = []
    if args.all:
        for arch in configs.ASSIGNED:
            for shape in configs.SHAPES:
                results.append(run_cell(arch, shape, multi_pod=args.multi_pod,
                                        out_dir=args.out,
                                        structure=args.structure))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        results.append(run_cell(args.arch, args.shape,
                                multi_pod=args.multi_pod, out_dir=args.out,
                                structure=args.structure,
                                kv_quant=args.kv_quant))
    bad = [r for r in results if r["status"] == "error"]
    print(f"[dryrun] {len(results)} cells: "
          f"{sum(r['status'] == 'ok' for r in results)} ok, "
          f"{sum(r['status'] == 'skipped' for r in results)} skipped, "
          f"{len(bad)} failed")
    if bad:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
