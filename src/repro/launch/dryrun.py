import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST run before any jax import (jax locks the device count at first init).
# Everything below may import jax.

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402


def _early_device_override(argv):
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={argv[i + 1]}"
            )


_early_device_override(sys.argv)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from ..configs import ARCHS, SHAPES, get_config, input_specs, shape_supported  # noqa: E402
from ..models.config import ModelConfig  # noqa: E402
from ..train.step import make_prefill_bundle, make_serve_bundle, make_train_bundle  # noqa: E402
from .analysis import parse_collectives, roofline_terms, summarize_collectives  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

DEFAULT_OUT = pathlib.Path("/root/repo/results/dryrun")


def model_flops_for(cfg: ModelConfig, shape) -> float:
    """MODEL_FLOPS: 6·N_active·D for train (fwd+bwd), 2·N_active·D for
    inference-like steps; D = processed tokens."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * (
            shape.seq_len if cfg.family != "encdec" else shape.seq_len + cfg.dec_len
        )
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def build_bundle(cfg, shape, mesh, multi_pod, rules=None):
    if shape.kind == "train":
        return make_train_bundle(cfg, shape, mesh=mesh, multi_pod=multi_pod, rules=rules)
    if shape.kind == "prefill":
        return make_prefill_bundle(cfg, shape, mesh=mesh, multi_pod=multi_pod, rules=rules)
    return make_serve_bundle(cfg, shape, mesh=mesh, multi_pod=multi_pod, rules=rules)


# --------------------------------------------------------------------------
# Loop-aware cost extraction.
#
# XLA's cost_analysis counts while-loop bodies ONCE (verified by calibration:
# scan(8 matmuls) reports 1 matmul of flops) and reports PER-DEVICE numbers
# for SPMD executables. We therefore compile tiny fully-unrolled layer-count
# variants (scans replaced by unrolled bodies) and extrapolate linearly:
# cost(L) = base + L*delta. The full scanned compile remains the deliverable
# artifact (memory analysis, compile proof, collective schedule).
# --------------------------------------------------------------------------
def _measure_cfg(cfg: ModelConfig, shape, **layer_kw) -> ModelConfig:
    S = shape.seq_len
    kw = dict(layer_kw, unroll_scans=True)
    kw["q_chunk"] = max(cfg.q_chunk, min(S, 512), S // 8)
    kw["kv_chunk"] = max(cfg.kv_chunk, min(S, 1024), S // 8)
    if shape.kind == "train":
        tok_per_seq = (
            cfg.dec_len if cfg.family == "encdec"
            else (S - cfg.prefix_len if cfg.family == "vlm" else S)
        )
        T = shape.global_batch * tok_per_seq
        kw["xent_chunk"] = max(T // 8, min(T, 2048))
    kw["ssm_scan_chunk"] = max(cfg.ssm_scan_chunk, S // 8, 64)
    return cfg.replace(**kw)


def _points_and_weights(cfg: ModelConfig, kind: str):
    """[(layer_kwargs, weight)] with sum_i w_i*cost_i = full-model cost."""
    if cfg.family == "encdec" and kind != "decode":
        Le, Ld = cfg.n_enc_layers, cfg.n_layers
        return [
            ({"n_enc_layers": 1, "n_layers": 1}, 1.0 - (Le - 1) - (Ld - 1)),
            ({"n_enc_layers": 2, "n_layers": 1}, float(Le - 1)),
            ({"n_enc_layers": 1, "n_layers": 2}, float(Ld - 1)),
        ]
    if cfg.local_global_period > 0:
        p = cfg.local_global_period
        n_super = cfg.n_layers // p
        tail = cfg.n_layers - n_super * p
        pts = [
            ({"n_layers": p}, 1.0 - (n_super - 1) - (1.0 if tail else 0.0)),
            ({"n_layers": 2 * p}, float(n_super - 1)),
        ]
        if tail:
            pts.append(({"n_layers": p + tail}, 1.0))
        return pts
    if cfg.family == "hybrid":
        p = cfg.attn_period
        n_super = cfg.n_layers // p
        return [
            ({"n_layers": p}, 1.0 - (n_super - 1)),
            ({"n_layers": 2 * p}, float(n_super - 1)),
        ]
    L = cfg.n_layers
    return [({"n_layers": 1}, 2.0 - L), ({"n_layers": 2}, float(L - 1))]


def _measure_point(cfg_v, shape, mesh, multi_pod, rules):
    from jax.sharding import NamedSharding, PartitionSpec as _P

    bundle = build_bundle(cfg_v, shape, mesh, multi_pod, rules)

    def _named(tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                            is_leaf=lambda x: isinstance(x, _P))

    with mesh:
        compiled = (
            jax.jit(bundle.fn, in_shardings=_named(bundle.in_shardings),
                    out_shardings=_named(bundle.out_shardings))
            .lower(*bundle.abstract_inputs)
            .compile()
        )
    cost = compiled.cost_analysis()
    colls = parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire": float(sum(o.wire_bytes for o in colls)),
        "collectives": summarize_collectives(colls),
    }


def extrapolate_cost(cfg, shape, mesh, multi_pod, rules=None):
    pts = _points_and_weights(cfg, shape.kind)
    total = {"flops": 0.0, "bytes": 0.0, "wire": 0.0}
    coll_total: dict = {}
    for layer_kw, w in pts:
        cfg_v = _measure_cfg(cfg, shape, **layer_kw)
        m = _measure_point(cfg_v, shape, mesh, multi_pod, rules)
        for k in total:
            total[k] += w * m[k]
        for op, d in m["collectives"].items():
            acc = coll_total.setdefault(op, {"count": 0.0, "wire_bytes": 0.0})
            acc["count"] += w * d["count"]
            acc["wire_bytes"] += w * d["wire_bytes"]
    total = {k: max(v, 0.0) for k, v in total.items()}
    total["collectives"] = {
        op: {k2: max(v2, 0.0) for k2, v2 in d.items()} for op, d in coll_total.items()
    }
    return total


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: pathlib.Path,
             verbose: bool = True, rules_override=None, tag: str = "baseline",
             cfg_override=None):
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_supported(cfg, shape_name)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": "2x16x16" if multi_pod else "16x16",
        "tag": tag, "status": "skipped", "skip_reason": why,
    }
    if not ok:
        out_dir.mkdir(parents=True, exist_ok=True)
        fname = f"{arch}__{shape_name}__{rec['mesh'].replace('x', '_')}__{tag}.json"
        (out_dir / fname).write_text(json.dumps(rec, indent=2))
        if verbose:
            print(f"[{rec['mesh']}] {arch} x {shape_name}: SKIP ({why})")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    try:
        bundle = build_bundle(cfg, shape, mesh, multi_pod, rules_override)

        from jax.sharding import NamedSharding, PartitionSpec as _P

        def _named(tree):
            return jax.tree.map(
                lambda s: NamedSharding(mesh, s), tree,
                is_leaf=lambda x: isinstance(x, _P),
            )

        with mesh:
            jitted = jax.jit(
                bundle.fn,
                in_shardings=_named(bundle.in_shardings),
                out_shardings=_named(bundle.out_shardings),
                donate_argnums=bundle.donate_argnums,
            )
            lowered = jitted.lower(*bundle.abstract_inputs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        colls = parse_collectives(hlo)
        csum = summarize_collectives(colls)
        wire_raw = sum(o.wire_bytes for o in colls)

        # loop-aware extrapolated costs (see module docstring)
        rules = getattr(bundle.ctx, "rules", None)
        extr = extrapolate_cost(cfg, shape, mesh, multi_pod, rules)
        mf = model_flops_for(cfg, shape)
        terms = roofline_terms(
            extr["flops"], extr["bytes"], extr["wire"], model_flops=mf, n_chips=n_chips
        )

        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=dict(
                argument_bytes=getattr(mem, "argument_size_in_bytes", None),
                output_bytes=getattr(mem, "output_size_in_bytes", None),
                temp_bytes=getattr(mem, "temp_size_in_bytes", None),
                peak_bytes=(
                    getattr(mem, "argument_size_in_bytes", 0) or 0
                ) + (getattr(mem, "temp_size_in_bytes", 0) or 0),
            ),
            raw_cost={"flops_loopbody_once": float(cost.get("flops", 0.0)),
                      "bytes_loopbody_once": float(cost.get("bytes accessed", 0.0)),
                      "wire_loopbody_once": wire_raw},
            cost={"flops": extr["flops"], "bytes_accessed": extr["bytes"],
                  "wire_bytes": extr["wire"]},
            collectives_schedule_sample=csum,
            collectives=extr["collectives"],
            roofline=terms,
        )
        if verbose:
            print(f"[{rec['mesh']}] {arch} x {shape_name} ({tag}): OK "
                  f"compile={t_compile:.0f}s flops={extr['flops']:.3e} "
                  f"bytes={extr['bytes']:.3e} wire={extr['wire']:.3e} "
                  f"bottleneck={terms['bottleneck']}"
                  f" roofline_frac={terms.get('roofline_fraction', 0):.3f}")
    except Exception as e:  # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        if verbose:
            print(f"[{rec['mesh']}] {arch} x {shape_name}: FAIL {type(e).__name__}: {e}")

    out_dir.mkdir(parents=True, exist_ok=True)
    fname = f"{arch}__{shape_name}__{rec['mesh'].replace('x', '_')}__{tag}.json"
    (out_dir / fname).write_text(json.dumps(rec, indent=2, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run: lower+compile every (arch x shape x mesh)")
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--devices", default=None, help="(handled pre-import)")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    out_dir = pathlib.Path(args.out)

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, out_dir, tag=args.tag)
                if rec["status"] == "error":
                    n_fail += 1
    print(f"done; failures: {n_fail}")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
