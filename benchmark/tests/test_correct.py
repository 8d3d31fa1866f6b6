"""``correct`` can fail. Each case skips the harness's look for a chip
(``--rehearse-cpu``, toy sizes, the toy limits of ``limits/<cell>.json``)
and drives the rest of a run:

* a sound run comes out correct;
* the control - the reference in the precision below the configuration's
  (fp8 for bf16) - put in the program's place and judged by the same
  comparison, comes out not correct (``control_correct`` false);
* with the timed path broken underneath - a step that returns its state
  unchanged; half of the batch left out, the mean taken over the rest; the
  exchange between chips left out; a served token altered where it is
  produced - ``correct`` comes out false.
"""

import json

import pytest

from benchmark import run as run_mod


def _run(capsys, workload, plant=None, extra=()):
    rc = run_mod.main(["--workload", workload, "--seed", "2147483659",
                       "--seconds", "1", "--trace", "0", "--rehearse-cpu",
                       *extra], plant=plant)
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    # every number compared is printed beside its limit, last on stderr
    for name in result["compared"]:
        assert f"compared {name}:" in out.err
    assert list(result)[-1] == "compared"
    return result


def _failed(result):
    return sorted(k for k, c in result["compared"].items() if not c["ok"])


@pytest.mark.parametrize("workload", ["serve-chat", "serve-flood",
                                      "train-1chip", "train-dp4"])
def test_sound_run_is_correct(capsys, workload):
    r = _run(capsys, workload)
    assert r["correct"] is True and r["rehearsal"] is True, _failed(r)
    assert r["metrics"] == {} and r["failed"] == 0


@pytest.mark.parametrize("workload", ["serve-chat", "serve-flood",
                                      "train-1chip", "train-dp4"])
def test_control_comes_out_not_correct(capsys, workload):
    """The control is put in the program's place and judged by the cell's
    own comparison (``common.compare_numbers``): its verdict is false."""
    r = _run(capsys, workload, extra=("--control", "fp8"))
    assert r["control_correct"] is False
    failed = [k for k in _failed(r) if k.startswith("control.")]
    assert failed, r["compared"]
    for k in failed:      # held to the very limit the program is held to
        assert r["compared"][k]["limit"] == \
            r["compared"][k[len("control."):]]["limit"]
    assert r["correct"] is True       # the program itself stayed sound
    assert not [k for k in _failed(r) if "." not in k]


@pytest.mark.parametrize("workload,faults", [
    ("train-1chip", ["fault.half_batch"]),
    ("train-dp4", ["fault.half_batch", "fault.no_exchange"])])
def test_fault_in_the_reference_comes_out_not_correct(capsys, workload,
                                                      faults):
    """The faults planted in the reference put in the program's place (how
    they are read on the chip, ``readings.py``) fail the same comparison."""
    r = _run(capsys, workload, extra=("--control", "fp8"))
    for f in faults:
        assert r[f + "_correct"] is False
        assert [k for k in _failed(r) if k.startswith(f + ".")]


def _unchanged_state(loop):
    import jax
    import jax.numpy as jnp
    real = loop.st["step"]

    def step(params, opt_state, tokens):
        keep = jax.tree.map(jnp.copy, (params, opt_state))
        _p, _o, metrics = real(params, opt_state, tokens)
        return keep[0], keep[1], metrics
    loop.st["step"] = step


def _half_batch(loop):
    loop.alter = lambda rows: rows[:len(rows) // 2]


def _no_exchange(monkeypatch):
    """Every chip goes on with chip 0's gradient alone."""
    import jax.numpy as jnp
    from jax import lax
    from akka_allreduce_tpu.parallel import dp
    real = dp.psum_all

    def lonely(x, axis_name):
        if getattr(x, "ndim", 0) == 2 and jnp.issubdtype(x.dtype,
                                                         jnp.floating):
            mine = jnp.where(lax.axis_index("dp") == 0, x, 0.0)
            return real(mine, axis_name) * lax.axis_size("dp")
        return real(x, axis_name)
    monkeypatch.setattr(dp, "psum_all", lonely)


def test_fault_state_unchanged(capsys):
    r = _run(capsys, "train-1chip", plant=_unchanged_state)
    assert r["correct"] is False
    assert "delta_worst_leaf_gap" in _failed(r)
    assert "grad1_worst_leaf_gap" in _failed(r)


def test_fault_half_batch_left_out(capsys):
    r = _run(capsys, "train-1chip", plant=_half_batch)
    assert r["correct"] is False
    assert "grad1_worst_leaf_gap" in _failed(r)


def test_fault_exchange_left_out(capsys, monkeypatch):
    _no_exchange(monkeypatch)
    r = _run(capsys, "train-dp4")
    assert r["correct"] is False
    assert "grad1_worst_leaf_gap" in _failed(r)


def test_fault_token_altered(capsys):
    def plant(drv):
        # the served stream says another token than the engine picked
        drv.alter = lambda rid, toks: [(t + 1) % 256 for t in toks]
    r = _run(capsys, "serve-chat", plant=plant)
    assert r["correct"] is False
    assert _failed(r) == ["served_gap"]


def test_no_chip_no_result(capsys, monkeypatch):
    """Off the chip, and not a rehearsal: exits non-zero, prints no result."""
    with pytest.raises(SystemExit) as e:
        run_mod.main(["--workload", "serve-chat", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out
