"""Distribution smoke tests: the dry-run machinery on a small (4×2) host
mesh in a subprocess (so the main test process keeps 1 device), plus
sharding-rule unit tests."""

import subprocess
import sys
import textwrap

import pytest

DRYRUN_SMALL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax
    from repro.configs import get_config, SHAPES, ShapeSpec
    from repro.launch.steps import lower_cell

    from jax.sharding import AxisType
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("{arch}").reduced().replace(vocab_size=512)
    shape = ShapeSpec("t", {seq}, {batch}, "{kind}")
    lowered, model, rls = lower_cell(cfg, shape, mesh)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    assert ca.get("flops", 0) > 0
    print("OK", rls.tp_strategy, int(ca["flops"]))
""")


@pytest.mark.parametrize("arch,kind", [
    ("qwen3-14b", "train"),
    ("olmoe-1b-7b", "train"),
    ("mamba2-2.7b", "train"),
    ("whisper-medium", "train"),
    ("recurrentgemma-9b", "decode"),
    ("stablelm-3b", "decode"),
    ("qwen3-14b", "prefill"),
])
def test_small_mesh_cell_compiles(arch, kind):
    seq, batch = (64, 8) if kind != "decode" else (64, 8)
    code = DRYRUN_SMALL.format(arch=arch, seq=seq, batch=batch, kind=kind)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=".", timeout=420)
    assert "OK" in r.stdout, f"{arch}/{kind}:\n{r.stderr[-2500:]}"


def test_sharding_rules_divisibility_fallback():
    import os
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.sharding import rules as R

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("qwen3-14b")
    rls = R.make_rules(mesh, cfg)
    # everything divides by 1 → specs resolve
    spec = R.param_pspec(rls, ("embed", "heads", "head_dim"),
                         (5120, 40, 128))
    assert isinstance(spec, P)


def test_strategy_auto_selection():
    """heads strategy iff num_heads divides the model axis (40 → ulysses;
    32 → heads)."""
    import numpy as np
    import jax
    from repro.configs import get_config
    from repro.sharding import rules as R

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((2, 4))  # model=4

    assert R.make_rules(FakeMesh(), get_config("qwen3-14b")).tp_strategy \
        == "heads"  # 40 % 4 == 0

    class FakeMesh16:
        axis_names = ("data", "model")
        devices = np.empty((2, 16))

    assert R.make_rules(FakeMesh16(),
                        get_config("qwen3-14b")).tp_strategy == "ulysses_sp"
    assert R.make_rules(FakeMesh16(),
                        get_config("stablelm-3b")).tp_strategy == "heads"
    assert R.make_rules(FakeMesh16(),
                        get_config("mamba2-2.7b")).tp_strategy == "heads"


def test_collective_parser():
    from repro.launch.dryrun import collective_stats
    hlo = """
  %ag = bf16[16,512]{1,0} all-gather(%p), replica_groups={{0,1}}
  %ar.1 = f32[1024]{0} all-reduce(%x), to_apply=%sum
  %rs = bf16[8,256]{1,0} reduce-scatter(%y), dimensions={0}
  %other = f32[2,2]{1,0} add(%a, %b)
"""
    st = collective_stats(hlo)
    assert st["all-gather"]["count"] == 1
    assert st["all-gather"]["bytes"] == 16 * 512 * 2
    assert st["all-reduce"]["bytes"] == 1024 * 4
    assert st["reduce-scatter"]["count"] == 1
    assert st["total_count"] == 3
