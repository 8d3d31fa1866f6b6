"""Full training-step tests: dp x tp x sp composition on the CPU mesh.

The gold test is gradient parity: the sharded step over (dp=2, tp=2, sp=2)
must produce the same synced gradients as an unsharded single-device
computation of the global mean loss.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.models.train import (
    TrainConfig,
    make_grad_step,
    make_train_state,
    make_train_step,
)
from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
    transformer_apply,
)
from akka_allreduce_tpu.parallel.mesh import MeshSpec, make_device_mesh
from akka_allreduce_tpu.parallel.ring_attention import local_causal_attention

MCFG = TransformerConfig(vocab_size=61, d_model=32, n_heads=4, n_layers=2,
                         d_ff=64, max_seq=64)


def reference_mean_loss(params, tokens, cfg):
    """Unsharded global mean next-token loss (last token has no target)."""
    logits = transformer_apply(params, tokens, cfg,
                               jnp.arange(tokens.shape[1]),
                               local_causal_attention, None)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -ll.sum() / ll.size


def make_tokens(b, t, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, MCFG.vocab_size, size=(b, t),
                                    dtype=np.int32))


@pytest.mark.slow
class TestGradParity:
    @pytest.mark.parametrize("spec", [
        MeshSpec(dp=8), MeshSpec(dp=2, tp=2, sp=2), MeshSpec(dp=4, sp=2),
        MeshSpec(dp=4, tp=2),
    ])
    def test_sharded_grads_match_unsharded(self, spec):
        mesh = make_device_mesh(spec)
        cfg = TrainConfig(model=MCFG, bucket_elems=256)
        tokens = make_tokens(b=8, t=32)

        key = jax.random.key(0)
        full_params = init_transformer(key, MCFG, tp=spec.tp)
        ref_grads = jax.grad(
            lambda p: reference_mean_loss(p, tokens, MCFG))(full_params)

        from akka_allreduce_tpu.models.train import param_specs, shard_params
        params = shard_params(full_params, param_specs(MCFG), mesh)
        grad_step = make_grad_step(cfg, mesh)
        grads, metrics = jax.jit(grad_step)(params, tokens)

        ref_loss = reference_mean_loss(full_params, tokens, MCFG)
        np.testing.assert_allclose(float(metrics["loss"]), float(ref_loss),
                                   rtol=1e-4)

        got = jax.tree.leaves(grads)
        want = jax.tree.leaves(ref_grads)
        paths = [p for p, _ in jax.tree.flatten_with_path(ref_grads)[0]]
        for path, g, w in zip(paths, got, want):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=5e-3, atol=1e-5,
                err_msg=f"grad mismatch at {path}")

    def test_min_bucket_count_reports_group_size(self):
        spec = MeshSpec(dp=4, sp=2)
        mesh = make_device_mesh(spec)
        cfg = TrainConfig(model=MCFG, bucket_elems=256)
        params, opt_state, opt = make_train_state(jax.random.key(1), cfg,
                                                  mesh)
        grad_step = make_grad_step(cfg, mesh)
        _, metrics = jax.jit(grad_step)(params, make_tokens(8, 32))
        assert int(metrics["min_bucket_count"]) == 8  # dp*sp contributors


@pytest.mark.slow
class TestTraining:
    def test_loss_decreases_on_copy_task(self):
        """30 steps on a deterministic repeating-token task: the full
        dp x tp x sp step must actually learn."""
        spec = MeshSpec(dp=2, tp=2, sp=2)
        mesh = make_device_mesh(spec)
        cfg = TrainConfig(model=MCFG, learning_rate=3e-3, bucket_elems=256)
        params, opt_state, opt = make_train_state(jax.random.key(2), cfg,
                                                  mesh)
        step = make_train_step(cfg, mesh, opt)
        # periodic sequence -> easily learnable next-token structure
        base = np.tile(np.arange(8, dtype=np.int32), 8)[:32]
        tokens = jnp.asarray(np.tile(base, (8, 1)))
        losses = []
        for _ in range(30):
            params, opt_state, metrics = step(params, opt_state, tokens)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] * 0.5, losses

    def test_straggler_masked_step_still_trains(self):
        """valid_buckets masking one bucket: counts report the gap and the
        update still applies (lossy round semantics end-to-end)."""
        spec = MeshSpec(dp=8)
        mesh = make_device_mesh(spec)
        cfg = TrainConfig(model=MCFG, bucket_elems=256)
        params, opt_state, opt = make_train_state(jax.random.key(3), cfg,
                                                  mesh)
        # mask this rank's first bucket on every rank except rank 0:
        # simulate via per-rank masks passed as a sharded argument is
        # overkill here — a uniform mask of bucket 0 on all ranks drops the
        # bucket entirely (count 0 -> grads 0 there, rescale keeps zeros)
        from akka_allreduce_tpu.ops.bucketing import bucketize
        _, spec_b = bucketize(params, cfg.bucket_elems)
        valid = jnp.ones((spec_b.num_buckets,), jnp.int32).at[0].set(0)
        grad_step = make_grad_step(cfg, mesh, valid_buckets=valid)
        grads, metrics = jax.jit(grad_step)(params, make_tokens(8, 32))
        assert int(metrics["min_bucket_count"]) == 0
        # bucket 0 covers the embedding head: its synced grads are zeros
        flat = jax.tree.leaves(grads)[0]  # 'embed' (sorted first... dict)
        # embed is under key 'embed': leaves sorted -> embed first
        assert float(jnp.abs(flat[:4]).max()) == 0.0


class TestSyncLayoutsAgree:
    def test_exact_step_equals_all_ones_masked_step(self):
        """The exact path reduces the leaves where they lie; an all-ones
        ``valid`` mask takes the bucket matrix through the masked psum.
        Same elements, same ranks, same honest counts: the two steps may
        differ by f32 summation order and nothing else, so the layouts
        cannot drift apart unseen."""
        from akka_allreduce_tpu.models.train import dense_bucket_count
        mesh = make_device_mesh(MeshSpec(dp=4), devices=jax.devices()[:4])
        cfg = TrainConfig(model=MCFG, bucket_elems=256)
        params, opt_state, opt = make_train_state(jax.random.key(6), cfg,
                                                  mesh)
        ones = jnp.ones((dense_bucket_count(cfg, mesh, params),), jnp.int32)
        tokens = make_tokens(8, 32, seed=9)
        exact = make_train_step(cfg, mesh, opt)
        masked = make_train_step(cfg, mesh, opt, valid_buckets=ones)
        p_e, _, m_e = exact(params, opt_state, tokens)
        p_m, _, m_m = masked(params, opt_state, tokens)
        assert int(m_e["min_bucket_count"]) == 4
        assert int(m_m["min_bucket_count"]) == 4
        np.testing.assert_allclose(float(m_e["loss"]), float(m_m["loss"]),
                                   rtol=1e-6)
        moved = 0.0
        for (path, a), b, start in zip(jax.tree.flatten_with_path(p_e)[0],
                                       jax.tree.leaves(p_m),
                                       jax.tree.leaves(params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7,
                                       err_msg=str(path))
            moved = max(moved, float(jnp.abs(a - start).max()))
        assert moved > 1e-4   # the step did update what is compared


class TestCompileStability:
    """ISSUE 3 satellite: the train step's compile-cache stability,
    asserted with the compile-counting guard (analysis/recompile.py).
    One program per shape is the contract that makes --compile-cache
    warm restarts and long runs possible; a step that silently
    recompiles per step would still pass the loss tests."""

    def test_multi_step_run_compiles_once(self):
        """30-step runs already exist above (loss test); here the same
        loop shape is pinned to EXACTLY one compile: the first step
        builds `step`, every later step is a cache hit."""
        from akka_allreduce_tpu.analysis.recompile import (
            CompileLog, no_recompiles)
        spec = MeshSpec(dp=8)
        mesh = make_device_mesh(spec)
        cfg = TrainConfig(model=MCFG, bucket_elems=256)
        params, opt_state, opt = make_train_state(jax.random.key(4),
                                                  cfg, mesh)
        step = make_train_step(cfg, mesh, opt)
        tokens = make_tokens(8, 32, seed=5)
        with CompileLog() as warm:
            params, opt_state, _ = step(params, opt_state, tokens)
        # exactly one step program (first-use dispatch helpers like
        # _multi_slice may ride along in the warmup window)
        assert warm.compiled.count("step") == 1, warm.compiled
        with no_recompiles("warmed train step x4"):
            for _ in range(4):
                params, opt_state, metrics = step(params, opt_state,
                                                  tokens)
        assert np.isfinite(float(metrics["loss"]))

    def test_chunked_multi_step_compiles_once_per_chunk_length(self):
        """make_multi_step (the --steps-per-dispatch path): one compile
        serves every chunk of the same length — dispatch 2 runs under
        the zero-compile guard."""
        from akka_allreduce_tpu.analysis.recompile import (
            CompileLog, no_recompiles)
        from akka_allreduce_tpu.models.train import make_multi_step
        spec = MeshSpec(dp=8)
        mesh = make_device_mesh(spec)
        cfg = TrainConfig(model=MCFG, bucket_elems=256)
        params, opt_state, opt = make_train_state(jax.random.key(5),
                                                  cfg, mesh)
        run_chunk = make_multi_step(cfg, mesh, opt)
        stacked = jnp.stack([make_tokens(8, 32, seed=s)
                             for s in (0, 1)])
        with CompileLog() as warm:
            params, opt_state, _ = run_chunk(params, opt_state, stacked)
        assert warm.compiled.count("run_chunk") == 1, warm.compiled
        stacked2 = jnp.stack([make_tokens(8, 32, seed=s)
                              for s in (2, 3)])
        with no_recompiles("warmed chunked dispatch"):
            params, opt_state, metrics = run_chunk(params, opt_state,
                                                   stacked2)
        assert metrics["loss"].shape == (2,)
