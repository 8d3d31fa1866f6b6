"""Dispatch-amortized training: make_multi_step's scanned chunk must be
step-for-step the per-step loop's program.

The scan body IS make_train_step's step (same gradient sync, optimizer
chain, int8 quant seeding from the adam counter), so k chunked steps over
a stacked batch must reproduce k sequential per-step calls over the same
batches — params, opt state, and the per-step loss trail, with fresh
data each tick.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.models.train import (
    TrainConfig,
    make_multi_step,
    make_train_state,
    make_train_step,
)
from akka_allreduce_tpu.models.transformer import TransformerConfig
from akka_allreduce_tpu.parallel.mesh import MeshSpec, make_device_mesh

# 1 layer: chunked-vs-sequential parity is layer-count-agnostic and this
# file compiles both the per-step and the scan program on the fast tier
MCFG = TransformerConfig(vocab_size=61, d_model=32, n_heads=4, n_layers=1,
                         d_ff=64, max_seq=16)


def _stacked_tokens(k, b, t, seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, MCFG.vocab_size, size=(k, b, t),
                                    dtype=np.int32))


class TestMultiStepParity:
    def test_chunked_matches_sequential_steps(self):
        mesh = make_device_mesh(MeshSpec(dp=2),
                                devices=jax.devices()[:2])
        cfg = TrainConfig(model=MCFG, bucket_elems=256,
                          learning_rate=1e-2)
        k, b, t = 4, 4, 16
        stacked = _stacked_tokens(k, b, t)

        params, opt_state, opt = make_train_state(jax.random.key(1), cfg,
                                                  mesh)
        step = make_train_step(cfg, mesh, opt, donate=False)
        p_seq, o_seq = params, opt_state
        losses_seq = []
        for i in range(k):
            p_seq, o_seq, m = step(p_seq, o_seq, stacked[i])
            losses_seq.append(float(m["loss"]))

        params2, opt_state2, opt2 = make_train_state(jax.random.key(1),
                                                     cfg, mesh)
        multi = make_multi_step(cfg, mesh, opt2)
        p_chk, o_chk, ms = multi(params2, opt_state2, stacked)

        # metrics stack along the step axis, one row per scan tick
        assert ms["loss"].shape == (k,)
        assert np.isfinite(np.asarray(ms["loss"])).all()
        np.testing.assert_allclose(np.asarray(ms["loss"]),
                                   np.asarray(losses_seq),
                                   rtol=1e-5, atol=1e-6)
        for (path, a), bb in zip(
                jax.tree.flatten_with_path(p_seq)[0],
                jax.tree.leaves(p_chk)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=str(path))
        # the optimizer advanced identically (adam counter drives the
        # int8 quant seed, so it must track the per-step loop exactly)
        cnt = [np.asarray(x) for x in jax.tree.leaves(o_chk)
               if np.asarray(x).dtype == np.int32]
        assert any((c == k).all() for c in cnt)


@pytest.mark.slow
class TestXprofTrace:
    """train --xprof-dir writes a TensorBoard-viewable device trace
    (the device-plane sibling of --trace-file's host protocol events;
    SURVEY §5 tracing row)."""

    def test_trace_written_and_crash_safe_window(self, monkeypatch,
                                                 tmp_path, capsys):
        from akka_allreduce_tpu.cli import main
        monkeypatch.setattr(sys, "argv", [
            "aat", "train", "--steps", "4", "--xprof-steps", "2",
            "--xprof-dir", str(tmp_path / "prof"), "--d-model", "16",
            "--n-layers", "1", "--d-ff", "32", "--vocab", "31", "--seq",
            "8", "--batch", "8", "--log-every", "100"])
        assert main() == 0
        capsys.readouterr()
        runs = list((tmp_path / "prof" / "plugins" / "profile").iterdir())
        assert len(runs) == 1
        names = {p.name for p in runs[0].iterdir()}
        assert any(n.endswith(".xplane.pb") for n in names), names


@pytest.mark.slow
class TestChunkedCliCheckpoints:
    """cli train --steps-per-dispatch: checkpoints land at chunk
    boundaries whenever a chunk crosses a --ckpt-every line (the plain
    step%interval gate would never fire on boundary indices), and a
    resumed run continues from the saved frontier."""

    BASE = ["aat", "train", "--d-model", "16", "--n-layers", "1",
            "--d-ff", "32", "--vocab", "31", "--seq", "8", "--batch",
            "8", "--log-every", "100", "--ckpt-every", "10",
            "--steps-per-dispatch", "4"]

    def _run(self, monkeypatch, ckpt_dir, steps, capsys):
        from akka_allreduce_tpu.cli import main
        monkeypatch.setattr(sys, "argv", self.BASE + [
            "--ckpt-dir", str(ckpt_dir), "--steps", str(steps)])
        assert main() == 0
        return capsys.readouterr().out

    def test_chunk_boundary_saves_and_resume(self, monkeypatch, tmp_path,
                                             capsys):
        # chunks [0-3] [4-7] [8-11]: only the third crosses a multiple
        # of 10, saving at its boundary step 11 (also the final step)
        self._run(monkeypatch, tmp_path, 12, capsys)
        steps = {int(d) for d in (p.name for p in tmp_path.iterdir())
                 if d.isdigit()}
        assert steps == {11}
        # resume: chunks [12-15] [16-19], tail [20-21] per-step; the
        # second chunk crosses 20 -> saves at 19; the final forced save
        # lands at 21
        out = self._run(monkeypatch, tmp_path, 22, capsys)
        assert "resumed from step 11" in out
        steps = {int(d) for d in (p.name for p in tmp_path.iterdir())
                 if d.isdigit()}
        assert {19, 21} <= steps

