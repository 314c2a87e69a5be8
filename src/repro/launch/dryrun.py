import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST precede every other import (jax locks the
# device count at first init).  Everything below is ordinary code.

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.configs import ARCHS, SHAPES, get_arch, input_specs  # noqa: E402
from repro.distributed.api import sharding_context  # noqa: E402
from repro.distributed.sharding import default_rules, shapes_shardings_from_axes  # noqa: E402
from repro.hwgen.hlo_analysis import parse_collectives, total_collective_bytes  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.lm import LM  # noqa: E402
from repro.nn.types import split  # noqa: E402
from repro.train.optimizer import Optimizer, OptimizerConfig  # noqa: E402
from repro.train.step import make_decode_step, make_prefill_step, make_train_step  # noqa: E402

DEFAULT_OUT = "results/dryrun"

# per-arch microbatch counts for the train_4k cell (activation memory)
TRAIN_MICROBATCHES = {
    "nemotron-4-340b": 8,
    "dbrx-132b": 4,
    "arctic-480b": 4,
    "whisper-medium": 2,
}

# Layer-pattern period for the cost extrapolation (archs whose layer mix
# repeats in units > 1: zamba2 = 5 Mamba2 + 1 hybrid, 9 units of its 54
# layers; xlstm = 7 mLSTM + 1 sLSTM).
PATTERN_UNITS = {
    "zamba2-2.7b": 6,
    "xlstm-1.3b": 8,
}


def _cell_id(arch: str, shape: str, mesh: str) -> str:
    return f"{arch}__{shape}__{mesh}"


def _mem_stats(compiled):
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes_per_device": int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes
            ),
        }
    except Exception as e:  # pragma: no cover
        return {"error": repr(e)}


def _cost_stats(compiled):
    try:
        ca = compiled.cost_analysis() or {}
        return {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
        }
    except Exception as e:  # pragma: no cover
        return {"error": repr(e)}


def _slice_units(spec, arch_name: str, k: int):
    """k layer-pattern units (cost extrapolation).  Where a unit is more
    than one layer, each holds the model's mix of layers (cost per layer
    is additive): a prefix need not, as zamba2's hybrid layers lie 6
    apart, then 5, 4 and 3, none among the first 6."""
    unit = PATTERN_UNITS.get(arch_name, 1)
    layers = tuple(spec.layers[: unit * k])
    if unit > 1:
        n_units = len(spec.layers) // unit
        counts = collections.Counter(spec.layers)
        assert all(c % n_units == 0 for c in counts.values()), (arch_name, unit)
        mix = tuple(layer for layer in counts for _ in range(counts[layer] // n_units))
        layers = mix * k
    enc = tuple(spec.encoder_layers[:k]) if spec.encoder_layers else ()
    return dataclasses.replace(spec, layers=layers, encoder_layers=enc)


def _map_sub_cfg(layers, kinds, **fields):
    out = []
    for layer in layers:
        subs = tuple(
            dataclasses.replace(s, cfg=dataclasses.replace(s.cfg, **fields))
            if s.kind in kinds else s
            for s in layer.subs
        )
        out.append(dataclasses.replace(layer, subs=subs))
    return tuple(out)


def _map_attention_cfg(layers, **fields):
    return _map_sub_cfg(layers, ("attention",), **fields)


def _map_attention(spec, **fields):
    """``spec`` with ``fields`` set on every attention config: its
    layers', its encoder's and its shared blocks'."""
    shared = spec.shared and dataclasses.replace(
        spec.shared, layer=_map_attention_cfg((spec.shared.layer,), **fields)[0])
    return dataclasses.replace(
        spec, layers=_map_attention_cfg(spec.layers, **fields),
        encoder_layers=_map_attention_cfg(spec.encoder_layers, **fields), shared=shared)


def _map_moe_cfg(layers, **fields):
    out = []
    for layer in layers:
        subs = tuple(
            dataclasses.replace(s, cfg=dataclasses.replace(s.cfg, **fields))
            if s.kind == "moe" else s
            for s in layer.subs
        )
        out.append(dataclasses.replace(layer, subs=subs))
    return tuple(out)


def apply_variant(spec, variant):
    """§Perf hillclimb knobs, applied on top of the baseline spec.

    Comma-separated flags: chunked_attn | remat_dots | no_remat.
    (chunked_loss is a train-step knob handled in build_cell.)
    """
    if "chunked_attn" in variant:
        spec = _map_attention(spec, impl="xla_chunked")
    if "remat_dots" in variant:
        spec = dataclasses.replace(spec, remat_policy="dots")
    if "no_remat" in variant:
        spec = dataclasses.replace(spec, remat=False)
    if "moe_2d" in variant:
        spec = dataclasses.replace(spec, layers=_map_moe_cfg(spec.layers, shard_ff=True))
    if "seq_shard" in variant:
        spec = _map_attention(spec, seq_shard=True)
    for flag in variant.split(","):
        if flag.startswith("kvc") and flag[3:].isdigit():
            spec = _map_attention(spec, kv_chunk=int(flag[3:]))
    return spec


def build_cell(arch_name: str, shape_name: str, multi_pod: bool, *, cost_variant: bool,
               overrides=None, n_units=None, variant=""):
    """Construct (step_fn, example_args, in_shardings, out_shardings, meta)."""
    arch = get_arch(arch_name)
    cell = SHAPES[shape_name]
    spec = arch.spec(long_context=cell.long_context)
    if variant:
        spec = apply_variant(spec, variant)
    if cost_variant:
        spec = dataclasses.replace(
            spec,
            scan_layers=False,
            # unroll inner attention kv-chunk scans — honest HloCostAnalysis
            # flops.  The mLSTM chunk scan and sLSTM time scan stay while
            # loops (unrolling 256 chunk bodies x 16 layers is a compile-
            # time explosion); their flops undercount is handled by the
            # roofline's max(HLO_FLOPs, MODEL_FLOPS) compute-term floor,
            # and their collectives are trip-count-corrected by the parser.
            layers=_map_attention_cfg(spec.layers, scan_unroll=True),
            encoder_layers=_map_attention_cfg(spec.encoder_layers, scan_unroll=True),
        )
    if n_units is not None:
        spec = _slice_units(spec, arch_name, n_units)
    if overrides:
        spec = dataclasses.replace(spec, **overrides)
    model = LM(spec)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(mesh)
    rep = NamedSharding(mesh, PartitionSpec())

    annotated = jax.eval_shape(
        functools.partial(model.init, dtype=jnp.bfloat16), jax.random.PRNGKey(0)
    )
    param_sds, axes = split(annotated)
    param_sh = shapes_shardings_from_axes(param_sds, axes, mesh, rules)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(param_sds))

    batch, batch_axes = input_specs(arch, cell, spec)
    batch_sh = shapes_shardings_from_axes(batch, batch_axes, mesh, rules)
    meta = {"n_params": n_params, "mesh_shape": tuple(mesh.devices.shape),
            "seq": cell.seq, "batch": cell.batch, "kind": cell.kind}

    if cell.kind == "train":
        microbatches = 1 if cost_variant else TRAIN_MICROBATCHES.get(arch_name, 1)
        for flag in variant.split(","):
            if flag.startswith("mb") and flag[2:].isdigit() and not cost_variant:
                microbatches = int(flag[2:])
        opt = Optimizer(OptimizerConfig(name="adamw"))
        opt_sds = jax.eval_shape(opt.init, param_sds)
        opt_sh = {"step": rep, "mu": param_sh, "nu": param_sh}
        loss_chunk = 1024 if "chunked_loss" in variant else 0
        step = make_train_step(model, opt, microbatches=microbatches,
                               loss_chunk=loss_chunk, loss_unroll=cost_variant)
        meta["microbatches"] = microbatches
        return (
            step,
            (param_sds, opt_sds, batch),
            (param_sh, opt_sh, batch_sh),
            (param_sh, opt_sh, None),
            mesh,
            meta,
        )

    if cell.kind == "prefill":
        step = make_prefill_step(model, last_only="last_logit" in variant)
        return step, (param_sds, batch), (param_sh, batch_sh), None, mesh, meta

    # decode
    enc_out = None
    if arch.batch_kind == "encdec":
        enc_out = jax.ShapeDtypeStruct((cell.batch, arch.enc_context, spec.d_model), jnp.bfloat16)
    if enc_out is not None:
        cache_sds = jax.eval_shape(
            lambda p, e: model.init_cache(p, batch=cell.batch, max_seq=cell.seq,
                                          enc_out=e, dtype=jnp.bfloat16),
            param_sds, enc_out,
        )
    else:
        cache_sds = jax.eval_shape(
            functools.partial(model.init_cache, batch=cell.batch,
                              max_seq=cell.seq, dtype=jnp.bfloat16),
            param_sds,
        )
    cache_sh = shapes_shardings_from_axes(cache_sds, model.cache_axes(), mesh, rules)
    step = make_decode_step(model)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    return (
        step,
        (param_sds, cache_sds, batch["tokens"], pos_sds),
        (param_sh, cache_sh, batch_sh["tokens"], rep),
        (None, cache_sh),
        mesh,
        meta,
    )


def run_cell(arch_name: str, shape_name: str, multi_pod: bool, *,
             with_cost: bool = True, overrides=None, variant: str = "") -> dict:
    mesh_name = "multi" if multi_pod else "single"
    record = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_name,
        "cell": _cell_id(arch_name, shape_name, mesh_name),
        "variant": variant or "baseline",
    }
    arch = get_arch(arch_name)
    cell = SHAPES[shape_name]
    ok, reason = arch.cell_supported(cell)
    if not ok:
        record.update(status="skipped", reason=reason)
        return record

    t0 = time.time()
    step, args, in_sh, out_sh, mesh, meta = build_cell(
        arch_name, shape_name, multi_pod, cost_variant=False, overrides=overrides,
        variant=variant,
    )
    record.update(meta)
    with mesh, sharding_context(mesh, default_rules(mesh)):
        lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
        record["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)
    record["memory"] = _mem_stats(compiled)
    # collectives of the production (scanned) program, for reference
    record["collectives_scanned"] = parse_collectives(compiled.as_text())
    del compiled, lowered

    if with_cost:
        # Cost variant: layers unrolled so HloCostAnalysis sees every layer.
        # Full unroll is too slow for 96-layer archs on one host core, and
        # per-layer cost is exactly additive, so we lower at two depths
        # (k1, k2 pattern units), solve q(k) = base + k*unit, extrapolate.
        t2 = time.time()
        unit = PATTERN_UNITS.get(arch_name, 1)
        spec_full = arch.spec(long_context=cell.long_context)
        full_units = len(spec_full.layers) // unit
        k1, k2 = (2, 4) if full_units >= 4 else (1, 2)
        measures = []
        for kk in (k1, k2):
            step, args, in_sh, out_sh, mesh, _ = build_cell(
                arch_name, shape_name, multi_pod, cost_variant=True,
                overrides=overrides, n_units=kk, variant=variant,
            )
            with mesh, sharding_context(mesh, default_rules(mesh)):
                lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
                compiled = lowered.compile()
            cost = _cost_stats(compiled)
            coll = parse_collectives(compiled.as_text())
            measures.append({
                "flops": cost.get("flops", 0.0),
                "bytes_accessed": cost.get("bytes_accessed", 0.0),
                "transcendentals": cost.get("transcendentals", 0.0),
                "collective_bytes": total_collective_bytes(coll),
                "collectives": coll,
            })
            del compiled, lowered

        def extrap(q1, q2):
            u = (q2 - q1) / (k2 - k1)
            return max(0.0, q1 - k1 * u + full_units * u)

        m1, m2 = measures
        record["cost"] = {
            k: extrap(m1[k], m2[k])
            for k in ("flops", "bytes_accessed", "transcendentals")
        }
        record["collective_bytes"] = extrap(m1["collective_bytes"], m2["collective_bytes"])
        record["collectives"] = {
            kind: {
                "count": extrap(m1["collectives"][kind]["count"], m2["collectives"][kind]["count"]),
                "bytes": extrap(m1["collectives"][kind]["bytes"], m2["collectives"][kind]["bytes"]),
            }
            for kind in m1["collectives"]
        }
        record["cost_mode"] = f"extrapolated(k=({k1},{k2}),units={full_units},unit={unit})"
        record["cost_compile_s"] = round(time.time() - t2, 2)

    record["status"] = "ok"
    record["total_s"] = round(time.time() - t0, 2)
    return record


def optimized_variant(arch_name: str, shape_name: str) -> str:
    """The beyond-paper optimized configuration per cell kind (§Perf):
    derived from the three hillclimbs and applied table-wide."""
    cell = SHAPES[shape_name]
    v = []
    if cell.kind == "train":
        v += ["chunked_loss", "remat_dots", "seq_shard"]
    elif cell.kind == "prefill":
        v += ["chunked_attn", "last_logit", "seq_shard"]
    if get_arch(arch_name).family == "moe":
        v.append("moe_2d")
    return ",".join(v)


def all_cells():
    for arch in ARCHS:
        for shape in SHAPES:
            for mesh in ("single", "multi"):
                yield arch, shape, mesh


def main() -> int:
    p = argparse.ArgumentParser(description="Multi-pod dry-run: lower+compile every (arch x shape x mesh) cell")
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None, choices=[*SHAPES, None])
    p.add_argument("--mesh", default="single", choices=["single", "multi"])
    p.add_argument("--all", action="store_true", help="run every cell via subprocesses (resumable)")
    p.add_argument("--out", default=DEFAULT_OUT)
    p.add_argument("--no-cost", action="store_true")
    p.add_argument("--variant", default="", help="comma-separated §Perf knobs: chunked_attn,chunked_loss,remat_dots,seq_shard,moe_2d,last_logit,mbN,kvcN")
    p.add_argument("--opt", action="store_true",
                   help="with --all: use the optimized per-kind variant for every cell")
    p.add_argument("--timeout", type=int, default=3600)
    args = p.parse_args()

    os.makedirs(args.out, exist_ok=True)

    if args.all:
        failures = 0
        for arch, shape, mesh in all_cells():
            if args.opt and mesh == "multi":
                continue  # optimized table is single-pod (§Roofline)
            variant = optimized_variant(arch, shape) if args.opt else args.variant
            suffix = f"__{variant.replace(',', '+')}" if variant else ""
            path = os.path.join(args.out, _cell_id(arch, shape, mesh) + suffix + ".json")
            if os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("status") in ("ok", "skipped"):
                        continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh, "--out", args.out]
            if variant:
                cmd += ["--variant", variant]
            # §Roofline is single-pod only; the multi-pod pass proves the
            # "pod" axis shards (compile success + memory), so skip the
            # expensive unrolled cost lowering there.
            if args.no_cost or mesh == "multi":
                cmd.append("--no-cost")
            print(f"[dryrun] {arch} x {shape} x {mesh}", flush=True)
            try:
                r = subprocess.run(cmd, timeout=args.timeout)
                if r.returncode != 0:
                    failures += 1
            except subprocess.TimeoutExpired:
                failures += 1
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape, "mesh": mesh,
                               "status": "timeout"}, f)
        print(f"[dryrun] complete, failures={failures}")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch/--shape required (or --all)"
    suffix = f"__{args.variant.replace(',', '+')}" if args.variant else ""
    path = os.path.join(args.out, _cell_id(args.arch, args.shape, args.mesh) + suffix + ".json")
    try:
        record = run_cell(args.arch, args.shape, args.mesh == "multi",
                          with_cost=not args.no_cost, variant=args.variant)
    except Exception:
        record = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
                  "status": "error", "traceback": traceback.format_exc()}
    with open(path, "w") as f:
        json.dump(record, f, indent=2, default=str)
    status = record.get("status")
    print(json.dumps({k: v for k, v in record.items() if k not in ("collectives", "collectives_scanned", "traceback")}, default=str))
    if status == "error":
        print(record["traceback"][-2000:], file=sys.stderr)
    return 0 if status in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
