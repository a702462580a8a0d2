import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax-importing module: jax locks the
device count at first backend init, and the dry-run needs 512 placeholder
host devices to build the production meshes ((16,16) and (2,16,16)).
Everything else (tests, benches, examples) keeps seeing 1 CPU device.

Per cell this lowers the *real* step function (train_step with AdamW+ZeRO-1
for train shapes; prefill/serve steps for inference shapes), compiles it,
prints ``memory_analysis()`` (proof-of-fit) and ``cost_analysis()``, parses
the collective mix out of the optimized HLO, and appends everything to a
resumable JSON results file.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --out results/dryrun.json
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import SHAPES, all_configs, get_config, input_specs, shape_applicable
from ..distributed.sharding import default_rules
from ..models.model import build_model
from ..train.optimizer import AdamWConfig, init_opt_state
from ..train.train_step import batch_shardings, make_train_step, opt_state_shardings, param_shardings
from ..serve.serve_step import make_serve_steps
from .mesh import make_production_mesh
from .roofline import collective_wire_bytes, model_flops, roofline_terms


from ..distributed.sharding import batch_partition as _batch_sharding_for


def _layer_variants(cfg):
    """Two reduced-layer configs for per-layer cost extrapolation.

    XLA's cost analysis counts while-loop (scan) bodies once, so raw
    cost_analysis under-reports per-step flops/bytes by ~n_layers. Lowering
    the same cell at two small layer counts and extrapolating linearly
    recovers the true totals (§Roofline methodology).
    """
    import dataclasses

    if cfg.slstm_every:  # xlstm: layer count quantized to groups
        g = cfg.slstm_every
        return (
            dataclasses.replace(cfg, n_layers=g, scan_unroll=True),
            dataclasses.replace(cfg, n_layers=2 * g, scan_unroll=True),
            cfg.n_layers,
            g,
            2 * g,
        )
    if cfg.encoder_layers:  # whisper: encoder+decoder scale together
        return (
            dataclasses.replace(cfg, n_layers=1, encoder_layers=1, scan_unroll=True),
            dataclasses.replace(cfg, n_layers=2, encoder_layers=2, scan_unroll=True),
            cfg.n_layers,
            1,
            2,
        )
    fd = cfg.first_dense_layers
    return (
        dataclasses.replace(cfg, n_layers=fd + 1, scan_unroll=True),
        dataclasses.replace(cfg, n_layers=fd + 2, scan_unroll=True),
        cfg.n_layers,
        fd + 1,
        fd + 2,
    )


def _measure(cfg, shape, mesh, rules, n_chips) -> Dict[str, float]:
    """Lower+compile one variant; return (flops, bytes, wire) per chip."""
    model = build_model(cfg)
    params_abs = model.abstract()
    specs = input_specs(cfg, shape)
    if shape.kind == "train":
        jit_step, _ = make_train_step(model, mesh, rules, AdamWConfig(total_steps=1000))
        opt_abs = jax.eval_shape(init_opt_state, params_abs)
        lowered = jit_step.lower(params_abs, opt_abs, specs)
    elif shape.kind == "prefill":
        from ..models.transformer import ModelContext

        ctx = ModelContext(mesh, rules)
        p_shard = param_shardings(model, mesh, rules)
        b_shard = {
            k: NamedSharding(
                mesh,
                P(*(list(_batch_sharding_for(mesh, v.shape[0])) + [None] * (len(v.shape) - 1))),
            )
            for k, v in specs.items()
        }
        fn = jax.jit(lambda p, b: model.prefill(p, b, ctx), in_shardings=(p_shard, b_shard))
        lowered = fn.lower(params_abs, specs)
    else:
        _, jit_decode, caches_abs, _ = make_serve_steps(
            model, mesh, rules, batch=shape.global_batch, max_len=shape.seq_len
        )
        lowered = jit_decode.lower(
            params_abs, specs["tokens"], caches_abs, jax.ShapeDtypeStruct((), jnp.int32)
        )
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    wire = collective_wire_bytes(compiled.as_text(), default_group=n_chips)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire": float(wire["total"]),
    }


def calibrate_cell(arch: str, shape_name: str, *, multi_pod: bool) -> Dict[str, Any]:
    """Per-layer extrapolated roofline terms (see _layer_variants)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    rules = default_rules(mesh)
    cfg1, cfg2, L, l1, l2 = _layer_variants(cfg)
    m1 = _measure(cfg1, shape, mesh, rules, n_chips)
    m2 = _measure(cfg2, shape, mesh, rules, n_chips)
    out: Dict[str, Any] = {}
    for k in ("flops", "bytes", "wire"):
        per_layer = max(0.0, (m2[k] - m1[k]) / (l2 - l1))
        out[k] = m2[k] + per_layer * (L - l2)
        out[k + "_per_layer"] = per_layer
    terms = roofline_terms(
        {"flops": out["flops"], "bytes accessed": out["bytes"]}, {"total": out["wire"]}
    )
    out["roofline"] = {k: (v if isinstance(v, str) else float(v)) for k, v in terms.items()}
    return out


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    cell: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
    }
    if not ok:
        cell["status"] = "skipped"
        cell["reason"] = reason
        return cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    rules = default_rules(mesh)
    model = build_model(cfg)
    params_abs = model.abstract()
    specs = input_specs(cfg, shape)

    t0 = time.time()
    if shape.kind == "train":
        jit_step, shardings = make_train_step(
            model, mesh, rules, AdamWConfig(total_steps=1000), grad_accum=1
        )
        opt_abs = jax.eval_shape(init_opt_state, params_abs)
        lowered = jit_step.lower(params_abs, opt_abs, specs)
    elif shape.kind == "prefill":
        from ..models.transformer import ModelContext

        ctx = ModelContext(mesh, rules)
        p_shard = param_shardings(model, mesh, rules)
        b_shard = {
            k: NamedSharding(
                mesh,
                P(*(list(_batch_sharding_for(mesh, v.shape[0])) + [None] * (len(v.shape) - 1))),
            )
            for k, v in specs.items()
        }
        fn = jax.jit(
            lambda p, b: model.prefill(p, b, ctx), in_shardings=(p_shard, b_shard)
        )
        lowered = fn.lower(params_abs, specs)
    else:  # decode
        _, jit_decode, caches_abs, _ = make_serve_steps(
            model, mesh, rules, batch=shape.global_batch, max_len=shape.seq_len
        )
        tokens_abs = specs["tokens"]
        pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = jit_decode.lower(params_abs, tokens_abs, caches_abs, pos_abs)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    hlo = compiled.as_text()
    wire = collective_wire_bytes(hlo, default_group=n_chips)
    counts = wire.pop("counts")
    terms = roofline_terms(cost, wire)

    mflops = model_flops(cfg, shape)
    per_chip_model_flops = mflops / n_chips
    cell.update(
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory={
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(mem, k)
        },
        cost={k: float(v) for k, v in cost.items() if isinstance(v, (int, float))},
        collectives={k: float(v) for k, v in wire.items()},
        collective_counts=counts,
        roofline={k: (v if isinstance(v, str) else float(v)) for k, v in terms.items()},
        model_flops_total=float(mflops),
        model_flops_per_chip=float(per_chip_model_flops),
        useful_flops_fraction=(
            per_chip_model_flops / terms["flops"] if terms["flops"] else 0.0
        ),
        n_chips=n_chips,
    )
    return cell


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--force", action="store_true")
    ap.add_argument(
        "--calibrate",
        action="store_true",
        help="add per-layer-extrapolated roofline terms to existing ok cells",
    )
    args = ap.parse_args()

    archs = sorted(all_configs()) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results: Dict[str, Any] = {}
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            results = json.load(f)

    for multi_pod in meshes:
        for arch in archs:
            for shape_name in shapes:
                key = f"{arch}|{shape_name}|{'multi' if multi_pod else 'single'}"
                if args.calibrate:
                    cell = results.get(key)
                    if cell is None or cell.get("status") != "ok":
                        continue
                    if "calibrated" in cell and not args.force:
                        print(f"[dryrun] {key}: calibrated (cached)")
                        continue
                    print(f"[dryrun] {key}: calibrating...", flush=True)
                    try:
                        cell["calibrated"] = calibrate_cell(arch, shape_name, multi_pod=multi_pod)
                        r = cell["calibrated"]["roofline"]
                        print(
                            f"[dryrun] {key}: calibrated compute={r['t_compute']:.3e}s "
                            f"memory={r['t_memory']:.3e}s collective={r['t_collective']:.3e}s "
                            f"dominant={r['dominant']}",
                            flush=True,
                        )
                    except Exception as exc:  # noqa: BLE001
                        cell["calibrated"] = {"error": f"{type(exc).__name__}: {exc}"}
                        print(f"[dryrun] {key}: calibration error {exc}", flush=True)
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1)
                    continue
                if key in results and results[key].get("status") in ("ok", "skipped") and not args.force:
                    print(f"[dryrun] {key}: cached ({results[key]['status']})")
                    continue
                print(f"[dryrun] {key}: lowering...", flush=True)
                try:
                    cell = lower_cell(arch, shape_name, multi_pod=multi_pod)
                except Exception as exc:  # noqa: BLE001 — recorded, not fatal
                    cell = {
                        "arch": arch,
                        "shape": shape_name,
                        "mesh": "2x16x16" if multi_pod else "16x16",
                        "status": "error",
                        "error": f"{type(exc).__name__}: {exc}",
                        "traceback": traceback.format_exc()[-2000:],
                    }
                results[key] = cell
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
                status = cell["status"]
                extra = ""
                if status == "ok":
                    r = cell["roofline"]
                    extra = (
                        f" compute={r['t_compute']:.3e}s memory={r['t_memory']:.3e}s "
                        f"collective={r['t_collective']:.3e}s dominant={r['dominant']} "
                        f"compile={cell['compile_s']:.0f}s"
                    )
                print(f"[dryrun] {key}: {status}{extra}", flush=True)

    n_ok = sum(1 for c in results.values() if c["status"] == "ok")
    n_skip = sum(1 for c in results.values() if c["status"] == "skipped")
    n_err = sum(1 for c in results.values() if c["status"] == "error")
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped-by-design, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
