import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  * jax.jit(step).lower(**ShapeDtypeStructs).compile() must succeed on
    the (16,16) single-pod mesh and the (2,16,16) multi-pod mesh;
  * memory_analysis() proves it fits; cost_analysis() + HLO collective
    parse feed the roofline table (EXPERIMENTS.md).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite_3_2b \
        --shape train_4k --mesh single --out experiments/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.distributed.sharding import batch_pspec
from repro.launch.analysis import (model_flops_estimate,
                                   roofline_from_compiled)
from repro.launch.jaxpr_cost import traced_cost
from repro.launch.mesh import make_production_mesh
from repro.launch import specs as SP
from repro.nn import transformer as T
from repro.nn.sharding import Constrainer, make_rules, param_pspecs
from repro.nn.train_step import make_train_step
from repro.training.optimizer import init_opt_state


def _ns(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape: str, mesh, *, q_chunk=512, loss_chunk=256,
               seq_override=None, batch_override=None, rules=None):
    """Build + lower one cell.  Returns (lowered, meta)."""
    cfg = get_config(arch)
    info = SP.SHAPES[shape]
    kind = info["kind"]
    seq = seq_override or info["seq"]
    batch = batch_override or info["batch"]
    ok, why = SP.shape_applicable(cfg, shape)
    if not ok:
        return None, {"skipped": why}, None

    rules = rules or make_rules(mesh)
    sc = Constrainer(mesh, rules)
    pparams = param_pspecs(cfg, mesh, rules)
    aparams = T.abstract_params(cfg)

    if kind == "train":
        batch_sds = SP.train_batch_specs(cfg, seq, batch)
        batch_ps = SP.train_batch_pspecs(cfg, mesh, rules)
        aopt = jax.eval_shape(init_opt_state, aparams)
        popt = {"m": pparams, "v": pparams, "count": P()}
        step = make_train_step(cfg, sc=sc, q_chunk=q_chunk,
                               loss_chunk=loss_chunk)
        fn = jax.jit(
            step,
            in_shardings=(_ns(mesh, pparams), _ns(mesh, popt),
                          _ns(mesh, batch_ps)),
            out_shardings=(_ns(mesh, pparams), _ns(mesh, popt), None),
            donate_argnums=(0, 1),
        )
        with mesh:
            lowered = fn.lower(aparams, aopt, batch_sds)
        trace = lambda: traced_cost(step, aparams, aopt, batch_sds)
    elif kind == "prefill":
        batch_sds = SP.train_batch_specs(cfg, seq, batch)
        extras_sds = batch_sds.get("extras")
        tok_ps = batch_pspec(mesh, 2, seq_axis=1, rules=rules,
                             shape=(batch, seq))

        def fn_(params, tokens, extras):
            return T.prefill(cfg, params, tokens, extras, sc, q_chunk)

        ex_ps = SP.train_batch_pspecs(cfg, mesh, rules).get("extras")
        fn = jax.jit(fn_, in_shardings=(
            _ns(mesh, pparams), NamedSharding(mesh, tok_ps),
            _ns(mesh, ex_ps) if extras_sds else None))
        with mesh:
            lowered = fn.lower(aparams, batch_sds["tokens"], extras_sds)
        trace = lambda: traced_cost(fn_, aparams, batch_sds["tokens"],
                                    extras_sds)
    elif kind == "decode":
        state_sds = SP.decode_state_specs(cfg, batch, seq)
        state_ps = SP.decode_state_pspecs(cfg, state_sds, mesh, rules)
        tok_ps = batch_pspec(mesh, 2, rules=rules, shape=(batch, 1))
        tok_sds = SP.sds((batch, 1), jnp.int32)

        def fn_(params, state, tokens):
            return T.decode_step(cfg, params, state, tokens, sc)

        fn = jax.jit(
            fn_,
            in_shardings=(_ns(mesh, pparams), _ns(mesh, state_ps),
                          NamedSharding(mesh, tok_ps)),
            out_shardings=(None, _ns(mesh, state_ps)),
            donate_argnums=(1,),
        )
        with mesh:
            lowered = fn.lower(aparams, state_sds, tok_sds)
        trace = lambda: traced_cost(fn_, aparams, state_sds, tok_sds)
    else:
        raise ValueError(kind)
    meta = {"arch": arch, "shape": shape, "kind": kind, "seq": seq,
            "batch": batch}
    return lowered, meta, trace


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: Path,
             **kw) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = mesh.devices.size
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "chips": chips}
    try:
        lowered, meta, trace = lower_cell(arch, shape, mesh, **kw)
        if lowered is None:
            rec.update(meta)
            rec["status"] = "skipped"
            out_dir.mkdir(parents=True, exist_ok=True)
            (out_dir / f"{arch}__{shape}__{mesh_name}.json").write_text(
                json.dumps(rec, indent=2))
            return rec
        rec.update(meta)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        mem = compiled.memory_analysis()
        gcost = trace()
        roof = roofline_from_compiled(compiled, chips, global_cost=gcost)
        cfg = get_config(arch)
        mf = model_flops_estimate(cfg, meta["kind"], meta["seq"],
                                  meta["batch"])
        rec.update({
            "status": "ok",
            "lower_s": round(t1 - t0, 1),
            "compile_s": round(t2 - t1, 1),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", None),
            },
            "roofline": roof.as_dict(),
            "model_flops_global": mf,
            "model_flops_ratio": mf / max(roof.flops * chips, 1e-30),
            "jaxpr_flops_global": gcost.flops,
            "jaxpr_bytes_global": gcost.bytes,
        })
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    finally:
        rec["total_s"] = round(time.time() - t0, 1)
    out_dir.mkdir(parents=True, exist_ok=True)
    fn = out_dir / f"{arch}__{shape}__{mesh_name}.json"
    fn.write_text(json.dumps(rec, indent=2, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SP.SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    out_dir = Path(args.out)

    results = []
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                rec = run_cell(arch, shape, mesh_name, out_dir)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f"dom={r['dominant']} "
                             f"frac={r['roofline_fraction']:.2f} "
                             f"compile={rec['compile_s']}s")
                elif status == "error":
                    extra = rec["error"][:120]
                print(f"[{status:7s}] {arch:28s} {shape:12s} {mesh_name:6s} "
                      f"{extra}", flush=True)
                results.append(rec)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
