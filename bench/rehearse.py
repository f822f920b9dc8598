"""Compile a configuration's serving programs for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config yi-34b-8l \
        [--num-pages N]

Nothing runs: the TPU compiler, which is installed beside JAX, compiles
the engine's paged decode step and its largest prefill for one chip of a
described ``v5e:2x2`` and prints ``memory_analysis()`` of each, for
the one-engine layout of ``bench/backends/local.py``.  Use it to
size ``num_pages`` before a chip run: weights + pool + the decode step's
temporaries must fit the chip's memory.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--num-pages", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import spec
    from repro.models import build_model

    jax.config.update("jax_enable_compilation_cache", False)
    conf = spec.load_config(args.config)
    cfg = spec.model_config(conf)
    eng = conf["engine"]
    model = build_model(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    params = shaped(model.abstract_params(cfg.serve_param_dtype))
    B, C, ps = eng["max_slots"], eng["max_len"], eng["page_size"]
    P = (args.num_pages or eng["num_pages"]) + 1
    pools = shaped(jax.eval_shape(lambda: model.init_paged_cache(P, ps)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    out = {}

    def report(name, compiled):
        m = compiled.memory_analysis()
        out[name] = {k: getattr(m, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes")}
        print(name, json.dumps(out[name]), flush=True)

    dec = jax.jit(model.decode_step_paged, donate_argnums=(1,))
    report("decode", dec.lower(params, pools, i32(B, 1), i32(B),
                               i32(B, C // ps)).compile())
    buckets = eng["prefill_buckets"]
    S, T = buckets[-1], buckets[-2]
    pfx = shaped(jax.eval_shape(lambda: model.init_cache(1, T)))

    def px(p, toks, pfx, plen, lidx):
        return model.prefill(p, {"tokens": toks}, capacity=toks.shape[1],
                             prefix=pfx, prefix_len=plen, last_index=lidx)

    report(f"prefill_{T}+{S}", jax.jit(px).lower(
        params, i32(1, S), pfx, i32(), i32()).compile())
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pools))
    print(json.dumps({"weights_bytes": weights, "pool_bytes": pool,
                      "pages": P - 1, **out}))


if __name__ == "__main__":
    main()
