"""Training-loop integration: loss decreases, microbatching is exact,
grad compression converges, FPM schedule picks sensible configs."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import TrainCfg
from repro.data.pipeline import SyntheticTokenPipeline, make_batch
from repro.models.registry import get_smoke_config
from repro.optim.grad_compress import (compressed_psum, error_feedback_update,
                                       int8_compress, int8_decompress,
                                       topk_compress, topk_decompress)
from repro.optim.schedule import cosine_warmup
from repro.train.fpm_schedule import build_step_fpm, choose_schedule, fpm_batch_partition
from repro.train.step import init_train_state, make_train_step


def test_train_loss_decreases():
    cfg = get_smoke_config("internlm2_1_8b")
    tcfg = TrainCfg(lr=1e-2, microbatches=2, total_steps=60, warmup=3)
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
    pipe = SyntheticTokenPipeline(cfg, batch=16, seq=32, seed=0)
    losses = []
    for _ in range(60):
        state, m = step(state, pipe.next())
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses
    assert np.isfinite(losses).all()


def test_microbatching_matches_full_batch_grads():
    """sum of microbatch grads / n == full-batch grad (loss is a mean)."""
    cfg = get_smoke_config("qwen2_5_3b")
    from repro.models.transformer import loss_fn
    key = jax.random.PRNGKey(1)
    from repro.models.transformer import init_params
    params = init_params(key, cfg)
    batch = make_batch(cfg, 4, 16, seed=0, step=0)

    def loss_of(p, b):
        return loss_fn(p, b, cfg, vocab_chunk=16)[0]

    g_full = jax.grad(loss_of)(params, batch)
    halves = [jax.tree.map(lambda x: x[:2], batch),
              jax.tree.map(lambda x: x[2:], batch)]
    g_mb = jax.tree.map(
        lambda a, b: (a.astype(jnp.float32) + b.astype(jnp.float32)) / 2,
        jax.grad(loss_of)(params, halves[0]),
        jax.grad(loss_of)(params, halves[1]))
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_mb)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-2)


def test_train_with_int8_compression_still_learns():
    cfg = get_smoke_config("internlm2_1_8b")
    tcfg = TrainCfg(lr=1e-2, microbatches=1, total_steps=60, warmup=3,
                    grad_compress="int8")
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    assert state.residual  # error-feedback buffers allocated
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
    pipe = SyntheticTokenPipeline(cfg, batch=16, seq=32, seed=0)
    losses = []
    for _ in range(60):
        state, m = step(state, pipe.next())
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.4, losses


# --------------------------------------------------------------- codecs

def test_int8_codec_bounded_error(rng):
    g = jnp.asarray(rng.standard_normal(1000).astype(np.float32))
    q, s = int8_compress(g)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(int8_decompress(q, s)) - np.asarray(g))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_topk_codec_keeps_largest(rng):
    g = jnp.asarray(rng.standard_normal(256).astype(np.float32))
    v, i, shp = topk_compress(g, k_frac=0.1)
    dec = np.asarray(topk_decompress(v, i, shp))
    kept = np.nonzero(dec)[0]
    thresh = np.sort(np.abs(np.asarray(g)))[-len(kept)]
    assert np.all(np.abs(np.asarray(g)[kept]) >= thresh - 1e-6)


def test_error_feedback_residual_is_exact(rng):
    g = jnp.asarray(rng.standard_normal(512).astype(np.float32))
    r = jnp.zeros_like(g)
    dec, r2 = error_feedback_update(g, r, codec="int8")
    np.testing.assert_allclose(np.asarray(dec + r2), np.asarray(g), atol=1e-5)


def test_compressed_psum_multidevice_equivalence():
    """int8 psum over a fake 'pods' axis approximates the exact psum."""
    from jax.sharding import Mesh, PartitionSpec as P
    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    mesh = Mesh(np.array(devs[:1]), ("pods",))
    g = jnp.linspace(-1, 1, 128)

    f = jax.shard_map(
        lambda x: compressed_psum(x, "pods"), mesh=mesh,
        in_specs=P(), out_specs=P())
    out = f(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=2e-2)


# --------------------------------------------------------------- schedules

def test_cosine_warmup_shape():
    lr = [float(cosine_warmup(jnp.int32(s), lr=1.0, warmup=10, total=100))
          for s in range(100)]
    assert lr[0] < lr[9] <= 1.0
    assert lr[-1] < lr[50] < lr[11]


def test_choose_schedule_prefers_fast_padded_size():
    # seq 100 is slow; padded 128 is 4x faster per flop
    def timer(mb, seq):
        base = mb * seq * 1e-6
        return base * (4.0 if seq % 128 else 1.0)
    fpm = build_step_fpm(timer, [1, 2, 4], [100, 128, 256])
    mb, pad = choose_schedule(fpm, tokens_per_device=512, seq_len=100,
                              pad_candidates=[128, 256])
    assert pad == 128


def test_fpm_batch_partition_heterogeneous():
    from repro.core.fpm import FPMSet, SpeedFunction
    xs = np.array([1, 8, 16, 32])
    ys = np.array([64, 128])
    v = np.outer(xs, [1.0, 1.1]) + 1
    fpms = FPMSet([SpeedFunction(xs, ys, v), SpeedFunction(xs, ys, 3 * v)])
    res = fpm_batch_partition(fpms, 32, 128)
    assert res.d.sum() == 32
    assert res.d[1] > res.d[0]
