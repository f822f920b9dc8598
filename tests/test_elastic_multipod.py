"""Elasticity drill: a checkpoint written under one mesh restores onto a
different mesh shape (single-pod → multi-pod layout), in a subprocess with
its own device count — the restart path a real pod-failure/upsize takes."""

import subprocess
import sys
import textwrap

DRILL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import sys
    sys.path.insert(0, "src")
    import tempfile
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import build_model
    from repro.sharding import rules as R
    from repro.training import checkpoint as ckpt
    from repro.training.optimizer import AdamW
    from repro.launch.steps import init_train_state, train_state_pspecs

    cfg = get_config("stablelm-3b").reduced().replace(
        d_model=64, num_heads=4, num_kv_heads=4)
    model = build_model(cfg)
    opt = AdamW()

    # "pod A": 4×2 mesh
    mesh_a = jax.make_mesh((4, 2), ("data", "model"),
                           devices=jax.devices()[:8],
                           axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rls_a = R.make_rules(mesh_a, cfg)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 5, state)

        # "pod B": different shape (2×2×4 multi-pod-style), different devices
        mesh_b = jax.make_mesh((2, 2, 4), ("pod", "data", "model"),
                               axis_types=(jax.sharding.AxisType.Auto,) * 3)
        rls_b = R.make_rules(mesh_b, cfg)
        specs = train_state_pspecs(rls_b, model, opt)
        shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(rls_b.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        restored, step = ckpt.restore(d, state, shardings=shardings)
    assert step == 5
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    leaf = jax.tree.leaves(restored)[0]
    assert set(leaf.sharding.mesh.axis_names) == {"pod", "data", "model"}
    print("OK elastic restore across mesh shapes")
""")


def test_elastic_restore_across_mesh_shapes():
    r = subprocess.run([sys.executable, "-c", DRILL], capture_output=True,
                       text=True, cwd=".", timeout=420)
    assert "OK elastic restore" in r.stdout, r.stderr[-2500:]
