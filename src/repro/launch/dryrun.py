import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture × input shape × mesh) cell: ``.lower()`` +
``.compile()`` the step function on the production mesh (single-pod 16×16
and multi-pod 2×16×16 of host-platform placeholder devices), then record

  * ``compiled.memory_analysis()``  — per-device bytes (proves it fits),
  * ``compiled.cost_analysis()``    — per-device FLOPs / bytes accessed,
  * collective bytes parsed from the optimized HLO,

into ``experiments/dryrun/<arch>__<shape>__<mesh>.json`` for
EXPERIMENTS.md §Dry-run and the roofline analysis.

Usage::

    python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
    python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""  # noqa: E402

import argparse    # noqa: E402
import json        # noqa: E402
import re          # noqa: E402
import time        # noqa: E402
import traceback   # noqa: E402
from pathlib import Path  # noqa: E402

import jax         # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import lower_cell  # noqa: E402

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _bytes_of_shape(s: str) -> int:
    """'bf16[8,128]{1,0}' → byte count; tuples handled by caller."""
    total = 0
    for m in _SHAPE_RE.finditer(s):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_INSTR_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\S+))\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|"
    r"collective-permute)\(")


def collective_stats(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in the optimized HLO.

    ``cost_analysis()`` does not report collective traffic, so we parse the
    module text: instruction lines look like
    ``%ag = bf16[16,512]{1,0} all-gather(%p), replica_groups=...`` and the
    result shape bounds the bytes moved per device (all-gather: output;
    all-reduce/reduce-scatter: within 2× of the wire bytes — adequate for a
    roofline term).  ``*-done`` ops are not matched, so async pairs count
    once.  Collectives inside while (scan) bodies appear once; the roofline
    harness multiplies per-layer deltas by layer count (block-delta
    costing, see benchmarks/roofline.py).
    """
    stats = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    for m in _INSTR_RE.finditer(hlo_text):
        shape_s, op = m.group(1), m.group(2)
        base = op.replace("-start", "")
        stats[base]["count"] += 1
        stats[base]["bytes"] += _bytes_of_shape(shape_s)
    stats["total_bytes"] = sum(v["bytes"] for v in stats.values()
                               if isinstance(v, dict))
    stats["total_count"] = sum(v["count"] for v in stats.values()
                               if isinstance(v, dict))
    return stats


def _tree_bytes(tree) -> int:
    return int(sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree.leaves(tree)))


def paged_kv_pool_bytes(cfg, *, num_pages: int, page_size: int) -> int:
    """Bytes the serving engine's *paged* KV pool allocates for a
    ``num_pages``-page pool: ``num_pages`` usable pages plus the reserved
    scratch page 0 (``ServingEngine._init_paged`` builds
    ``init_paged_cache(num_pages + 1, page_size)``).  Shape inference
    only — no arrays materialize.  Raises ``ValueError`` for models whose
    KV is not positionally sliceable (they have no paged layout)."""
    from repro.models.model import Model
    model = Model(cfg)
    shaped = jax.eval_shape(
        lambda: model.init_paged_cache(num_pages + 1, page_size))
    return _tree_bytes(shaped)


def contiguous_kv_bytes(cfg, *, max_slots: int, max_len: int) -> int:
    """Bytes of the contiguous per-slot slab cache (the pre-paged serving
    layout, still used by recurrent/hybrid/int8/windowed models)."""
    from repro.models.model import Model
    shaped = Model(cfg).init_cache(max_slots, max_len, abstract=True)
    return _tree_bytes(shaped)


def serving_kv_estimate(cfg, *, max_slots: int, max_len: int,
                        page_size: int = 16) -> dict:
    """HBM estimate for a decode cell's serving KV at the engine's default
    pool sizing (``num_pages = max_slots · max_len / page_size``), for
    both layouts — the dry-run report matches what the engine actually
    allocates (tests assert agreement with ``tree_nbytes(kv_pages)``)."""
    out = {
        "max_slots": max_slots,
        "max_len": max_len,
        "contiguous_bytes": contiguous_kv_bytes(
            cfg, max_slots=max_slots, max_len=max_len),
    }
    try:
        num_pages = max_slots * (max_len // page_size)
        out.update({
            "layout": "paged",
            "page_size": page_size,
            "num_pages": num_pages,
            "paged_bytes": paged_kv_pool_bytes(
                cfg, num_pages=num_pages, page_size=page_size),
        })
    except ValueError as e:  # non-sliceable KV: contiguous slab only
        out["layout"] = "contiguous"
        out["paged_unsupported"] = str(e)
    return out


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             out_dir: Path) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind}
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec
    t0 = time.time()
    try:
        lowered, model, rls = lower_cell(cfg, shape, mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ca = compiled.cost_analysis() or {}
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        coll = collective_stats(hlo)
        rec.update({
            "status": "ok",
            "tp_strategy": rls.tp_strategy,
            "n_devices": mesh.devices.size,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "flops_per_device": ca.get("flops"),
            "bytes_accessed_per_device": ca.get("bytes accessed"),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_estimate_bytes": (ma.argument_size_in_bytes
                                        + ma.output_size_in_bytes
                                        + ma.temp_size_in_bytes
                                        - ma.alias_size_in_bytes),
            },
            "collectives": coll,
            "num_params": model.num_params(),
        })
        if shape.kind == "decode":
            # serving-cache HBM at the engine's default pool sizing, both
            # layouts — this is the number the serving engine allocates
            rec["serving_kv"] = serving_kv_estimate(
                cfg, max_slots=shape.global_batch, max_len=shape.seq_len)
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    path.write_text(json.dumps(rec, indent=1, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multipod2x16x16",
                       make_production_mesh(multi_pod=True)))

    n_ok = n_skip = n_err = 0
    for mesh_name, mesh in meshes:
        for arch in archs:
            for shape_name in shapes:
                rec = run_cell(arch, shape_name, mesh, mesh_name, out_dir)
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_err += st == "error"
                if st == "ok":
                    m = rec["memory"]["peak_estimate_bytes"] / 2**30
                    print(f"[ok]   {mesh_name:16s} {arch:22s} "
                          f"{shape_name:12s} {rec['tp_strategy']:8s} "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"mem/dev={m:.2f}GiB "
                          f"coll={rec['collectives']['total_bytes']/2**20:.1f}MiB "
                          f"compile={rec['compile_s']}s", flush=True)
                elif st == "skipped":
                    print(f"[skip] {mesh_name:16s} {arch:22s} "
                          f"{shape_name:12s} {rec['reason'][:60]}",
                          flush=True)
                else:
                    print(f"[ERR]  {mesh_name:16s} {arch:22s} "
                          f"{shape_name:12s} {rec['error'][:200]}",
                          flush=True)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped (documented), "
          f"{n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
