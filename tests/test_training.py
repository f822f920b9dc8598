"""Training substrate tests: loss decreases, checkpoint atomicity/roundtrip,
failure-injection restart, elastic restore, int8-EF gradient compression."""

import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.training import checkpoint as ckpt
from repro.training.data import LMDataset
from repro.training.optimizer import AdamW, cosine_schedule
from repro.training.train_loop import TrainConfig, train


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("stablelm-3b").reduced()
    return build_model(cfg)


def test_loss_decreases(tiny, tmp_path):
    tcfg = TrainConfig(steps=30, ckpt_every=30, log_every=5,
                       ckpt_dir=str(tmp_path / "ck"), async_ckpt=False)
    logs = []
    state, history = train(tiny, tcfg, log=logs.append)
    first = history[0][1]
    last = history[-1][1]
    assert last < first * 0.9, f"loss did not decrease: {history}"


def test_checkpoint_roundtrip(tiny, tmp_path):
    opt = AdamW(learning_rate=1e-3)
    from repro.launch.steps import init_train_state
    state = init_train_state(tiny, opt, jax.random.PRNGKey(0))
    ckpt.save(tmp_path / "ck", 7, state)
    restored, step = ckpt.restore(tmp_path / "ck", state)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_failure_injection_resumes_identically(tiny, tmp_path):
    """A crash at step 25 must resume from step 20 and reach the same final
    state as an uninterrupted run (deterministic data ⇒ bitwise equal)."""
    common = dict(steps=40, ckpt_every=10, log_every=40, async_ckpt=False)
    s_clean, _ = train(tiny, TrainConfig(
        ckpt_dir=str(tmp_path / "clean"), **common), log=lambda *_: None)
    s_faulty, _ = train(tiny, TrainConfig(
        ckpt_dir=str(tmp_path / "faulty"), fail_at_step=25, **common),
        log=lambda *_: None)
    for a, b in zip(jax.tree.leaves(s_clean), jax.tree.leaves(s_faulty)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0, atol=0)


def test_dataset_deterministic_and_sharded():
    d = LMDataset(vocab_size=512, batch_size=8, seq_len=16, seed=3)
    b1 = d.batch(5)
    b2 = d.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # host shards partition the global batch
    shards = [LMDataset(vocab_size=512, batch_size=8, seq_len=16, seed=3,
                        host_id=i, num_hosts=2).batch(5)["tokens"]
              for i in range(2)]
    np.testing.assert_array_equal(np.concatenate(shards), b1["tokens"])


def test_cosine_schedule():
    lr = cosine_schedule(1e-3, warmup_steps=10, total_steps=100)
    assert float(lr(0)) == 0.0
    assert abs(float(lr(10)) - 1e-3) < 1e-9
    assert float(lr(100)) < float(lr(50)) < float(lr(10))


def test_elastic_restore_new_sharding(tiny, tmp_path):
    """Restore maps logical arrays onto whatever mesh the new job has."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    opt = AdamW()
    from repro.launch.steps import init_train_state
    state = init_train_state(tiny, opt, jax.random.PRNGKey(1))
    ckpt.save(tmp_path / "ck", 3, state)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shardings = jax.tree.map(
        lambda _: NamedSharding(mesh, P()), state)
    restored, _ = ckpt.restore(tmp_path / "ck", state, shardings=shardings)
    leaf = jax.tree.leaves(restored)[0]
    assert leaf.sharding.mesh.shape == {"data": 1, "model": 1}


COMPRESSION_DRILL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.training.compression import (
        make_compressed_dp_allreduce, init_error_buffers, ef_compress_psum)
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    grads = {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(128,)), jnp.float32)}
    errs = init_error_buffers(grads)
    reduce = make_compressed_dp_allreduce(mesh, "data")
    total_err = 0.0
    # replicated grads → compressed mean must approximate the value itself,
    # and error feedback must push the *accumulated* bias toward zero
    acc = jnp.zeros_like(grads["w"])
    exact_acc = jnp.zeros_like(grads["w"])
    for step in range(20):
        mean, errs = reduce(grads, errs)
        acc = acc + mean["w"]
        exact_acc = exact_acc + grads["w"]
    rel = float(jnp.linalg.norm(acc - exact_acc) / jnp.linalg.norm(exact_acc))
    print("REL", rel)
    assert rel < 2e-3, rel
    print("OK")
""")


def test_int8_ef_compression_numerics():
    r = subprocess.run([sys.executable, "-c", COMPRESSION_DRILL],
                       capture_output=True, text=True, cwd=".",
                       timeout=300)
    assert "OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-2000:]}"
