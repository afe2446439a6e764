"""Substrate tests: data determinism, checkpoint roundtrip + resume,
fault-tolerant restart (real process kill), gradient compression, elastic
planning, serving engine end-to-end."""

import json
import os
import pathlib
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import AsyncSaver, latest_step, restore, save
from repro.data.pipeline import DataConfig, TokenDataset, synthetic_tokens
from repro.launch.elastic import ElasticController, shrink_plan
from repro.optim import compressed_psum, dequantize_int8, quantize_int8


# ------------------------------------------------------------------- data

def test_data_deterministic_and_host_sharded():
    ds = TokenDataset(synthetic_tokens(100_000, 1000),
                      DataConfig(seq_len=64, global_batch=8))
    a1, l1 = ds.batch_for_step(7, host=0, n_hosts=4)
    a2, _ = ds.batch_for_step(7, host=0, n_hosts=4)
    np.testing.assert_array_equal(a1, a2)            # pure function of step
    assert a1.shape == (2, 64)
    np.testing.assert_array_equal(a1[:, 1:], l1[:, :-1])  # labels shifted
    # all hosts' shards together form the global batch, disjoint
    rows = [ds.batch_for_step(7, h, 4)[0] for h in range(4)]
    allrows = np.concatenate(rows)
    assert allrows.shape == (8, 64)


def test_any_host_can_recompute_any_shard():
    """The straggler/elastic invariant: shard content depends only on
    (step, shard index), not on which host computes it."""
    ds = TokenDataset(synthetic_tokens(50_000, 500),
                      DataConfig(seq_len=32, global_batch=8))
    t_h1, _ = ds.batch_for_step(3, host=1, n_hosts=4)
    # host 1's shard = samples [step*gb + 1*per .. +2*per)
    t_all = np.concatenate([ds.batch_for_step(3, h, 4)[0] for h in range(4)])
    t_again = np.concatenate([ds.batch_for_step(3, h, 8)[0] for h in range(8)])
    np.testing.assert_array_equal(t_all, t_again)    # mesh-width independent


# --------------------------------------------------------------- checkpoint

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32)}}
    save(tree, tmp_path, 3)
    like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    got, step = restore(like, tmp_path, None)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(got["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_async_checkpoint_and_commit_protocol(tmp_path):
    saver = AsyncSaver()
    tree = {"w": jnp.ones((100, 100))}
    saver.save_async(tree, tmp_path, 1)
    saver.wait()
    assert latest_step(tmp_path) == 1
    # partial (uncommitted) checkpoints are invisible
    d = tmp_path / "step_00000005"
    d.mkdir()
    (d / "w__full.npy").write_bytes(b"junk")
    assert latest_step(tmp_path) == 1   # no manifest -> not committed


def test_fault_tolerant_restart(tmp_path):
    """Kill a real training process mid-run; restart must resume from the
    last committed checkpoint and finish."""
    ckpt = str(tmp_path / "ck")
    code = f"""
import sys
sys.path.insert(0, "src")
from repro.configs import get_smoke_config
from repro.data.pipeline import DataConfig, TokenDataset, synthetic_tokens
from repro.train.trainer import Trainer, TrainerConfig
from repro.launch.steps import TrainConfig
cfg = get_smoke_config("qwen2-0.5b")
ds = TokenDataset(synthetic_tokens(200_000, cfg.vocab),
                  DataConfig(seq_len=32, global_batch=4, vocab=cfg.vocab))
tc = TrainerConfig(steps=16, ckpt_every=4, ckpt_dir={ckpt!r},
                   fail_at_step={{fail}}, log_every=4,
                   train=TrainConfig(remat="none"))
tr = Trainer(cfg, tc, ds)
out = tr.run()
print("FINAL", out["losses"][-1][0])
"""
    env = dict(os.environ, PYTHONPATH="src")
    # first run crashes at step 10 (after the step-8 checkpoint committed)
    r1 = subprocess.run([sys.executable, "-c", code.replace("{fail}", "10")],
                        capture_output=True, text=True, cwd="/root/repo",
                        env=env, timeout=600)
    assert r1.returncode != 0 and "injected failure" in r1.stderr
    assert latest_step(ckpt) is not None
    resumed_from = latest_step(ckpt)
    assert resumed_from >= 4
    # second run resumes and completes
    r2 = subprocess.run([sys.executable, "-c", code.replace("{fail}", "None")],
                        capture_output=True, text=True, cwd="/root/repo",
                        env=env, timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "FINAL 15" in r2.stdout


# -------------------------------------------------------------- compression

def test_int8_quantization_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(10_240) * 3.0, jnp.float32)
    q, s = quantize_int8(x)
    y = dequantize_int8(q, s, x.shape, x.dtype)
    blockmax = np.abs(np.asarray(x)).reshape(-1, 256).max(axis=1)
    tol = (blockmax / 127.0 * 0.51 + 1e-6).repeat(256)
    assert (np.abs(np.asarray(y) - np.asarray(x)) <= tol).all()


def test_stochastic_rounding_unbiased():
    x = jnp.full((256,), 0.3, jnp.float32)
    outs = []
    for i in range(200):
        q, s = quantize_int8(x, rng=jax.random.key(i))
        outs.append(np.asarray(dequantize_int8(q, s, x.shape, x.dtype)))
    est = np.mean(outs)
    assert abs(est - 0.3) < 0.005, est


def test_compressed_psum_matches_fp32():
    """shard_map over a fake 4-way axis: compressed allreduce approximates
    the exact sum."""
    from jax.sharding import PartitionSpec as P
    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Explicit,))
    x = jnp.asarray(np.random.default_rng(1).standard_normal((4, 256)),
                    jnp.float32)

    def f(xs):
        return compressed_psum(xs, "pod")

    out = jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                        out_specs=P("pod"))(x)
    # single shard: psum over 1 device = identity (quantize/dequant error only)
    err = np.abs(np.asarray(out) - np.asarray(x)).max()
    assert err < np.abs(np.asarray(x)).max() / 127 + 1e-5


# ------------------------------------------------------------------ elastic

def test_shrink_plan():
    assert shrink_plan(16, 0) == 16
    assert shrink_plan(16, 1) == 8
    assert shrink_plan(16, 8) == 8
    assert shrink_plan(16, 9) == 4


def test_elastic_reassignment_covers_all_shards():
    ec = ElasticController(8)
    ec.fail(3, step=10)
    ec.mark_slow(5, step=10)
    asg = ec.assignment(step=11)
    shards = sorted(s for lst in asg.values() for s in lst)
    assert shards == list(range(shrink_plan(8, 1)))
    assert 3 not in asg and 5 not in asg      # dead + slow excluded


# ------------------------------------------------------------------ serving

def test_serving_engine_end_to_end():
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.serving.engine import EngineConfig, Request, ServingEngine

    cfg = get_smoke_config("qwen2-0.5b")
    params = init_params(cfg, jax.random.key(0))
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_seq=64))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=100 + i,
                    prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
                    max_new=4) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    for r in reqs:
        assert r.done and len(r.generated) == 4
    # all pages returned to the pool
    assert len(eng.pool.free) == eng.ecfg.n_pages
    # the session store actually served lookups
    st = eng.sessions.stats()
    assert eng.steps >= 10
