import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay the first statements of this module: jax
locks the device count at first initialization, and the production meshes
need 512 placeholder host devices ((16,16) single pod / (2,16,16) pods).

Per cell: build the abstract state + batch (ShapeDtypeStructs, never
allocated), jit with explicit in_shardings over the production mesh,
``.lower().compile()``, then record memory_analysis, cost_analysis and the
HLO collective schedule into experiments/dryrun/<cell>.json for the
roofline (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--force]
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"
# the chip the production meshes model (its device_kind keys the peaks)
TARGET_KIND = "TPU v5 lite"


def _build_lm(entry, shape, mesh):
    import jax
    from repro.training import train_step as TS

    cfg = entry.config
    serve = shape.kind in ("prefill", "decode")
    params, pspecs, opt, ospecs = TS.lm_abstract_state(cfg, mesh, serve=serve)
    if shape.kind == "train":
        batch, bspecs = TS.lm_batch_specs(cfg, shape, mesh)
        fn = TS.make_lm_train_step(cfg, mesh)
        args = (params, opt, batch, jax.ShapeDtypeStruct((), "int32"))
        in_specs = (pspecs, ospecs, bspecs, None)
        return fn, args, in_specs, (0, 1)
    if shape.kind == "prefill":
        batch, bspecs = TS.lm_batch_specs(cfg, shape, mesh)
        batch.pop("targets"), batch.pop("mask")
        bspecs.pop("targets"), bspecs.pop("mask")
        fn = TS.make_lm_prefill(cfg, mesh)
        return fn, (params, batch), (pspecs, bspecs), ()
    if shape.kind == "decode":
        import jax.numpy as jnp
        from repro.distributed import sharding as shd
        from jax.sharding import PartitionSpec as P
        caches, cspecs = TS.lm_cache_abstract(cfg, shape, mesh)
        B = shape.global_batch
        lengths = jax.ShapeDtypeStruct((B,), jnp.int32)
        last = jax.ShapeDtypeStruct((B,), jnp.int32)
        dp = shd.dp_spec(mesh)
        fn = TS.make_lm_decode(cfg, mesh)
        return (fn, (params, caches, lengths, last),
                (pspecs, cspecs, P(dp), P(dp)), (1,))
    raise ValueError(shape.kind)


def _build_gnn(entry, shape, mesh):
    import jax
    from repro.training import train_step as TS
    import dataclasses

    batch, bspecs, task, n_graphs, d_feat = TS.gnn_abstract_batch(
        entry.config, shape, mesh)
    cfg = entry.config
    if task == "node_class" and d_feat:
        cfg = dataclasses.replace(cfg, d_feat_in=d_feat)
    params, pspecs, opt, ospecs = TS.gnn_abstract_state(cfg, mesh)
    fn = TS.make_gnn_train_step(cfg, mesh, task, n_graphs)
    args = (params, opt, batch, jax.ShapeDtypeStruct((), "int32"))
    return fn, args, (pspecs, ospecs, bspecs, None), (0, 1)


def _build_recsys(entry, shape, mesh):
    import jax
    from repro.training import train_step as TS

    cfg = entry.config
    params, pspecs, opt, ospecs = TS.recsys_abstract_state(cfg, mesh)
    if shape.kind == "recsys_train":
        batch, bspecs = TS.recsys_abstract_batch(cfg, shape, mesh)
        fn = TS.make_recsys_train_step(cfg, mesh)
        args = (params, opt, batch, jax.ShapeDtypeStruct((), "int32"))
        return fn, args, (pspecs, ospecs, bspecs, None), (0, 1)
    if shape.kind == "recsys_retrieval" and cfg.model == "two_tower":
        batch, bspecs = TS.two_tower_retrieval_batch(cfg, shape, mesh)
        fn = TS.make_two_tower_retrieval_step(cfg, mesh)
        return fn, (params, batch), (pspecs, bspecs), ()
    batch, bspecs = TS.recsys_abstract_batch(cfg, shape, mesh)
    batch.pop("labels", None), bspecs.pop("labels", None)
    fn = TS.make_recsys_serve_step(cfg, mesh)
    return fn, (params, batch), (pspecs, bspecs), ()


def _build_index(entry, shape, mesh):
    """The paper's own pipeline on the production mesh: shard_map
    invert -> all-to-all term shuffle -> postings -> PFor pack."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.indexer import make_index_step
    from repro.distributed import sharding as shd

    cfg = entry.config
    n_dev = mesh.devices.size
    docs = shape.global_batch * n_dev  # docs per step, global
    fn = make_index_step(cfg, mesh, doc_len=shape.seq_len)
    tokens = jax.ShapeDtypeStruct((docs, shape.seq_len), jnp.int32)
    full = P((*shd.dp_axes(mesh), "model"))
    return fn, (tokens,), (full,), ()


def build_cell(entry, shape, mesh):
    fam = entry.family
    if fam == "lm":
        return _build_lm(entry, shape, mesh)
    if fam == "gnn":
        return _build_gnn(entry, shape, mesh)
    if fam == "recsys":
        return _build_recsys(entry, shape, mesh)
    if fam == "index":
        return _build_index(entry, shape, mesh)
    raise ValueError(fam)


def _compile_and_measure(entry, shape, mesh, t0):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import roofline as RL

    fn, args, in_specs, donate = build_cell(entry, shape, mesh)

    def to_shard(spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
            spec_tree, is_leaf=lambda x: isinstance(x, P) or x is None)

    in_shardings = tuple(to_shard(s) for s in in_specs)
    jitted = jax.jit(fn, in_shardings=in_shardings,
                     donate_argnums=donate or ())
    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_info = {"error": str(e)}

    cost_raw = compiled.cost_analysis()
    cost = cost_raw[0] if isinstance(cost_raw, (list, tuple)) else cost_raw
    cost = {k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float))}
    hlo = compiled.as_text()
    coll = RL.collective_bytes(hlo)
    return {
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem_info, "cost": cost, "collectives": coll,
        "hlo_lines": hlo.count("\n"),
    }


# XLA cost_analysis counts a while/scan body ONCE regardless of trip count
# (verified empirically; see EXPERIMENTS.md §Dry-run). All inner loops in
# the models are python-unrolled except the LM layer scan and the DIEN GRU
# time scan; those cells also compile python-unrolled variants at two
# trip counts and extrapolate: cost(n) = base + per_iter * n.
_UNROLL_POINTS = (2, 4)


def _correction_plan(entry):
    """-> (field, unroll_flag_field, full_count) or None."""
    if entry.family == "lm":
        return ("n_layers", "scan_layers", entry.config.n_layers)
    if entry.family == "recsys" and entry.config.model == "dien":
        return ("seq_len", "scan_gru", entry.config.seq_len)
    return None


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None) -> dict:
    import dataclasses
    from repro.configs.registry import get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.launch import roofline as RL

    t0 = time.time()
    entry = get_arch(arch_id)
    if overrides:
        entry = dataclasses.replace(
            entry, config=dataclasses.replace(entry.config, **overrides))
    shape = entry.shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    # 1) the full production program: compile proof + memory analysis
    full = _compile_and_measure(entry, shape, mesh, t0)

    # 2) scan correction (flops/bytes/collectives)
    cost, coll = dict(full["cost"]), full["collectives"]
    correction = "none"
    plan = _correction_plan(entry)
    if plan:
        field, flag, nL = plan
        L1, L2 = _UNROLL_POINTS
        measured = {}
        for L in (L1, L2):
            cfg_u = dataclasses.replace(entry.config,
                                        **{field: L, flag: False})
            entry_u = dataclasses.replace(entry, config=cfg_u)
            measured[L] = _compile_and_measure(entry_u, shape, mesh,
                                               time.time())

        def extrapolate(get):
            y1, y2 = get(measured[L1]), get(measured[L2])
            per_layer = (y2 - y1) / (L2 - L1)
            return max(y1 + per_layer * (nL - L1), 0.0)

        cost["flops"] = extrapolate(lambda m: m["cost"].get("flops", 0.0))
        cost["bytes accessed"] = extrapolate(
            lambda m: m["cost"].get("bytes accessed", 0.0))
        coll = {"total_bytes": extrapolate(
            lambda m: float(m["collectives"]["total_bytes"])),
            "bytes": full["collectives"]["bytes"],
            "counts": full["collectives"]["counts"],
            "unroll_points": {str(L): measured[L]["collectives"]["total_bytes"]
                              for L in (L1, L2)}}
        correction = f"unroll-extrapolated L={L1},{L2}->{nL}"

    model_flops = 0.0
    mem_analytic = 0.0
    if entry.family == "lm":
        model_flops = RL.lm_model_flops(entry.config, shape)
        mem_analytic = RL.lm_memory_bytes(entry.config, shape, n_chips)
    terms = RL.roofline_terms(cost, coll, n_chips, model_flops, mem_analytic,
                              device_kind=TARGET_KIND)

    return {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "ok": True,
        "lower_s": full["lower_s"], "compile_s": full["compile_s"],
        "memory_analysis": full["memory_analysis"],
        "cost_analysis": {k: cost.get(k) for k in
                          ("flops", "bytes accessed", "transcendentals")},
        "cost_raw_scan_body_once": {k: full["cost"].get(k) for k in
                                    ("flops", "bytes accessed")},
        "collectives": coll,
        "scan_correction": correction,
        "roofline": terms,
        "hlo_lines": full["hlo_lines"],
    }


def cell_path(arch_id, shape_name, multi_pod) -> Path:
    mesh = "multi" if multi_pod else "single"
    return OUT_DIR / f"{arch_id}__{shape_name}__{mesh}.json"


def orchestrate(mesh_mode: str, force: bool, only_arch: str | None = None):
    """Run every cell in a subprocess (isolates device-count env + OOM)."""
    from repro.configs.registry import iter_cells, get_arch

    cells = [(e.arch_id, s.name) for e, s, skipped in iter_cells()
             if not skipped]
    cells += [("lucene-envelope", s.name)
              for s in get_arch("lucene-envelope").shapes]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[mesh_mode]
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    results = []
    for arch_id, shape_name in cells:
        if only_arch and arch_id != only_arch:
            continue
        for multi in meshes:
            out = cell_path(arch_id, shape_name, multi)
            if out.exists() and not force:
                print(f"skip (cached): {out.name}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch_id, "--shape", shape_name,
                   "--mesh", "multi" if multi else "single"]
            print(f"=== {arch_id} / {shape_name} / "
                  f"{'multi' if multi else 'single'}", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=7200)
            if r.returncode != 0:
                # the subprocess writes its own JSON (with traceback) unless
                # it died hard (OOM/kill) before getting there
                if not out.exists():
                    err = {"arch": arch_id, "shape": shape_name,
                           "mesh": "multi" if multi else "single",
                           "ok": False,
                           "error": (r.stderr[-4000:] or
                                     f"hard exit rc={r.returncode}")}
                    out.write_text(json.dumps(err, indent=1))
                msg = json.loads(out.read_text()).get("error", "?")
                print(f"  FAIL: {msg.strip().splitlines()[-1][:220]}")
            else:
                print(r.stdout.strip().splitlines()[-1])
            results.append(out)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override, e.g. moe_impl=shard_map; "
                         "result saved with a __<tag> suffix")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    if args.all:
        orchestrate(args.mesh, args.force, args.arch)
        return

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v

    assert args.arch and args.shape, "--arch/--shape or --all"
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    for multi in ([False, True] if args.mesh == "both"
                  else [args.mesh == "multi"]):
        try:
            res = run_cell(args.arch, args.shape, multi, overrides or None)
            if overrides:
                res["overrides"] = overrides
        except Exception:
            res = {"arch": args.arch, "shape": args.shape,
                   "mesh": "2x16x16" if multi else "16x16", "ok": False,
                   "error": traceback.format_exc()[-4000:]}
        out = cell_path(args.arch, args.shape, multi)
        if args.tag:
            out = out.with_name(out.stem + f"__{args.tag}.json")
        out.write_text(json.dumps(res, indent=1))
        if res["ok"]:
            r = res["roofline"]
            print(f"OK {args.arch}/{args.shape}/{res['mesh']}: "
                  f"compile {res['compile_s']}s, dominant={r['dominant']}, "
                  f"compute={r['compute_s']:.2e}s memory={r['memory_s']:.2e}s "
                  f"collective={r['collective_s']:.2e}s")
        else:
            print(f"FAIL {args.arch}/{args.shape}: "
                  f"{res['error'].splitlines()[-1][:300]}")
            sys.exit(1)


if __name__ == "__main__":
    main()
