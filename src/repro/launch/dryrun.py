"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Run as:   PYTHONPATH=src python -m repro.launch.dryrun --all
          PYTHONPATH=src python -m repro.launch.dryrun --arch mamba2-1.3b \
              --shape train_4k --mesh multi

For every cell this lowers the appropriate step function (train_step /
prefill / serve_step) against ShapeDtypeStruct inputs (no allocation),
compiles it for the production mesh, and records:

  * ``memory_analysis`` (per-device argument/output/temp bytes -- proves fit),
  * ``cost_analysis`` (per-device HLO FLOPs and bytes accessed),
  * per-collective byte counts parsed from the optimized HLO
    (all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute, with replica-group-aware wire-byte estimates),

into ``artifacts/dryrun/<mesh>/<arch>__<shape>[__tag].json`` for the
roofline analysis (benchmarks/roofline.py, EXPERIMENTS.md §Roofline).

The 512 devices are forced host-CPU devices, and the backend is pinned to
the CPU (``JAX_PLATFORMS=cpu``): on a TPU host jax would otherwise take the
chip as its default backend.  The costs recorded are therefore those of
CPU-backend HLO, not of a TPU compile.
"""

# The VERY FIRST lines, before ANY other import (jax locks the platform and
# the device count on first initialization):
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import (  # noqa: E402
    SHAPES,
    input_specs,
    shape_applicable,
    sync_policy_choices,
)
from repro.configs.registry import get_config, list_archs  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_num_chips  # noqa: E402

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(segment):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-collective (count, result bytes, estimated wire bytes per device)."""
    stats = {k: {"count": 0, "result_bytes": 0, "wire_bytes": 0.0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if " = " not in stripped:
            continue
        lhs, rhs = stripped.split(" = ", 1)
        kind = None
        for c in _COLLECTIVES:
            # match op name at the start of the rhs expression, e.g.
            # "bf16[8]{0} all-reduce(", including -start/-done variants
            if re.match(rf"[^a-z]*{c}(-start)?\(", rhs.split(")")[0] + ")") or re.search(
                rf"\b{c}(-start)?\(", rhs.split("(")[0] + "("
            ):
                kind = c
                break
        if kind is None:
            continue
        # result shapes live between '=' and the op name
        result_seg = rhs.split(kind)[0]
        rb = _shape_bytes(result_seg)
        if rb == 0:
            continue
        m = _GROUPS_RE.search(rhs)
        g = int(m.group(2)) if m else 2  # group size; conservative default
        if kind == "all-reduce":
            wire = 2.0 * rb * (g - 1) / g
        elif kind == "all-gather":
            wire = rb * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = rb * (g - 1)  # operand = result * g
        elif kind == "all-to-all":
            wire = rb * (g - 1) / g
        else:  # collective-permute
            wire = float(rb)
        s = stats[kind]
        s["count"] += 1
        s["result_bytes"] += rb
        s["wire_bytes"] += wire
    return stats


def apply_variant(cfg, variant: str):
    """§Perf hillclimb variants: (cfg transform, train-config overrides)."""
    import dataclasses

    tkw = {}
    if not variant:
        return cfg, tkw
    for v in variant.split("+"):
        if v.startswith("ssdchunk"):
            cfg = dataclasses.replace(
                cfg, ssm=dataclasses.replace(cfg.ssm, chunk=int(v[len("ssdchunk"):]))
            )
        elif v == "moehints":
            cfg = dataclasses.replace(cfg, moe_shard_hints=True)
        elif v == "nosp":
            tkw["sequence_parallel"] = False
        elif v.startswith("accum"):
            tkw["grad_accum"] = int(v[len("accum"):])
        elif v:
            raise ValueError(f"unknown variant {v!r}")
    return cfg, tkw


def build_cell(arch: str, shape_name: str, mesh, *, sync_strategy: str = "scu",
               remat_policy: str = "full", variant: str = "", compression: str = "none"):
    """Returns (fn, jit_kwargs, args) ready to lower."""
    cfg, tkw = apply_variant(get_config(arch), variant)
    shape = SHAPES[shape_name]
    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        from repro.train.optimizer import OptConfig
        from repro.train.step import TrainConfig, make_train_step

        # activation-memory knob for the very large archs
        n = cfg.n_params()
        accum = 8 if n > 90e9 else (4 if n > 20e9 else 1)
        tcfg = TrainConfig(
            sync_strategy=sync_strategy, remat_policy=remat_policy,
            grad_accum=tkw.get("grad_accum", accum),
            sequence_parallel=tkw.get("sequence_parallel", True),
            opt=OptConfig(compression=compression),
        )
        step_fn, (in_sh, batch_sh_fn), out_sh, params_sds = make_train_step(
            cfg, tcfg, mesh
        )

        # abstract optimizer state
        opt_sds = {
            "master": jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params_sds
            ),
            "m": jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params_sds
            ),
            "v": jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params_sds
            ),
        }
        step_sds = jax.ShapeDtypeStruct((), jnp.int32)
        batch_sh = batch_sh_fn(specs)
        jit_kwargs = dict(
            in_shardings=(in_sh[0], in_sh[1], in_sh[2], batch_sh),
            out_shardings=(out_sh[0], out_sh[1], out_sh[2], None),
            donate_argnums=(0, 1),  # params + optimizer state alias in/out
        )
        args = (params_sds, opt_sds, step_sds, specs)
        return step_fn, jit_kwargs, args

    if shape.kind == "prefill":
        from repro.serve.decode import make_prefill

        prefill_fn, in_sh, out_sh, params_sds = make_prefill(
            cfg, mesh, shape.global_batch, shape.seq_len
        )
        from repro.parallel.sharding import batch_spec
        from jax.sharding import NamedSharding

        batch_sh = {
            k: NamedSharding(mesh, batch_spec(mesh, v.ndim - 1))
            for k, v in specs.items()
        }
        jit_kwargs = dict(in_shardings=(in_sh[0], batch_sh), out_shardings=out_sh)
        return prefill_fn, jit_kwargs, (params_sds, specs)

    # decode
    from repro.serve.decode import cache_shapes, make_serve_step

    serve_fn, in_sh, out_sh, params_sds = make_serve_step(
        cfg, mesh, shape.global_batch, shape.seq_len
    )
    cache_sds = cache_shapes(cfg, shape.global_batch, shape.seq_len)
    args = (params_sds, cache_sds, specs["tokens"], specs["position"])
    jit_kwargs = dict(in_shardings=in_sh, out_shardings=out_sh)
    return serve_fn, jit_kwargs, args


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             sync_strategy: str = "scu", remat_policy: str = "full",
             tag: str = "", save_hlo: bool = False, variant: str = "",
             compression: str = "none") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "sync_strategy": sync_strategy,
        "remat_policy": remat_policy,
        "applicable": ok,
    }
    suffix = f"__{tag}" if tag else ""
    out_path = out_dir / mesh_kind / f"{arch}__{shape_name}{suffix}.json"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    if not ok:
        rec["skip_reason"] = why
        out_path.write_text(json.dumps(rec, indent=2))
        print(f"[skip] {arch} x {shape_name} ({mesh_kind}): {why}")
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    try:
        fn, jit_kwargs, args = build_cell(
            arch, shape_name, mesh, sync_strategy=sync_strategy,
            remat_policy=remat_policy, variant=variant, compression=compression,
        )
        with mesh:
            lowered = jax.jit(fn, **jit_kwargs).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            coll = parse_collectives(hlo)
            from repro.launch.hlo_analysis import analyze_hlo

            hs = analyze_hlo(hlo)

        rec.update(
            status="ok",
            chips=mesh_num_chips(mesh),
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
            },
            cost={
                "flops_per_device": ca.get("flops"),
                "bytes_accessed_per_device": ca.get("bytes accessed"),
                "transcendentals": ca.get("transcendentals"),
            },
            collectives=coll,
            hlo_analysis={
                "dot_flops_per_device": hs.dot_flops,
                "bytes_accessed_per_device": hs.bytes_accessed,
                "transcendental_elems": hs.transcendental_elems,
                "collectives": hs.collectives,
                "wire_bytes_per_device": hs.total_wire_bytes,
                "collective_count": hs.total_collective_count,
                "while_trip_counts": hs.while_trip_counts,
            },
            model={
                "n_params": cfg.n_params(),
                "n_active_params": cfg.n_active_params(),
                "seq_len": shape.seq_len,
                "global_batch": shape.global_batch,
                "kind": shape.kind,
            },
        )
        if save_hlo:
            (out_path.with_suffix(".hlo.txt")).write_text(hlo)
        print(
            f"[ok]   {arch} x {shape_name} ({mesh_kind}/{sync_strategy}): "
            f"compile {t_compile:.1f}s, "
            f"flops/dev {ca.get('flops', 0):.3e}, "
            f"temp {ma.temp_size_in_bytes/2**30:.2f} GiB"
        )
    except Exception as e:  # noqa: BLE001 - record and continue
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[FAIL] {arch} x {shape_name} ({mesh_kind}): {type(e).__name__}: {e}")
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="all archs x shapes")
    ap.add_argument("--sync", default="scu", choices=list(sync_policy_choices()))
    ap.add_argument("--remat", default="full")
    ap.add_argument("--tag", default="")
    ap.add_argument("--variant", default="", help="e.g. ssdchunk128, moehints")
    ap.add_argument("--compression", default="none", choices=["none", "int8"])
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_fail = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_cell(
                    arch, shape, mesh_kind, out_dir,
                    sync_strategy=args.sync, remat_policy=args.remat,
                    tag=args.tag, save_hlo=args.save_hlo,
                    variant=args.variant, compression=args.compression,
                )
                if rec.get("status") == "error":
                    n_fail += 1
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
