"""``trace_reduce`` on planes built by hand (every quantity checked
against a count made on paper) and on the small trace recorded on the
chip that is kept in ``tests/data``."""

import os

import pytest

from benchmark import trace_reduce as tr

MS = 1_000_000  # ns


def _planes():
    host = ("/host:CPU", [("main", [
        (tr.WINDOW, 0, 100 * MS),
        ("bench.engine.step", 0, 40 * MS),
        ("bench.scheduler.wait", 40 * MS, 70 * MS),
        ("bench.engine.step", 70 * MS, 100 * MS),
        ("other", 0, 100 * MS),
    ])])
    dev0 = ("/device:TPU:0", [
        ("XLA Ops", [
            ("fusion.1", 0, 10 * MS),
            ("while.2", 10 * MS, 30 * MS),          # parent of two
            ("fusion.3", 10 * MS, 18 * MS),
            ("all-reduce.4", 18 * MS, 30 * MS),
            ("fusion.1", 75 * MS, 95 * MS),
            ("fusion.9", 95 * MS, 120 * MS),        # runs past the window
        ]),
        ("XLA Modules", [
            ("jit_step(7)", 0, 30 * MS),
            ("jit_step(7)", 75 * MS, 95 * MS),
            ("jit_other(8)", 95 * MS, 120 * MS),    # not wholly inside
        ]),
    ])
    dev1 = ("/device:TPU:1", [("XLA Ops", [
        ("fusion.1", 0, 50 * MS),
        ("all-reduce.4", 40 * MS, 60 * MS),         # half under compute
    ])])
    return [host, dev0, dev1]


def test_by_hand():
    r = tr.reduce_planes(_planes())
    assert r.n_devices == 2 and r.window_s == pytest.approx(0.1)
    # dev0 busy: [0,30] + [75,100] = 55 ms; dev1: [0,60] = 60 ms
    assert r.busy_s == pytest.approx((0.055 + 0.060) / 2)
    # self times on dev0: while.2 = 20 - 8 - 12 = 0; fusion.1 = 10 + 20;
    # fusion.9 clipped to 5; dev1 adds all-reduce 20 and fusion.1 50 less
    # the 10 the all-reduce overlaps (time is charged once, to the later)
    assert r.op_seconds["while.2"] == pytest.approx(0.0)
    assert r.op_seconds["fusion.1"] == pytest.approx((0.030 + 0.040) / 2)
    assert r.op_seconds["fusion.3"] == pytest.approx(0.008 / 2)
    assert r.op_seconds["fusion.9"] == pytest.approx(0.005 / 2)
    assert r.op_seconds["all-reduce.4"] == pytest.approx(
        (0.012 + 0.020) / 2)
    assert r.top_ops(1)[0][0] == "fusion.1"
    # idle gaps. dev0: [30,75] -> 10 under engine.step, 30 under wait, 5
    # under the second step: the largest cover owns it (wait, 45 ms);
    # dev1: [60,100] -> wait covers 10, the second step 30 (40 ms)
    assert r.gap_seconds == {
        "bench.scheduler.wait": pytest.approx(0.045 / 2),
        "bench.engine.step": pytest.approx(0.040 / 2)}
    # collectives: dev0 12 ms with no compute leaf beside it; dev1 20 ms of
    # which [40,50] runs under fusion.1 -> 10 exposed
    assert r.collective_s == pytest.approx((0.012 + 0.020) / 2)
    assert r.exposed_collective_s == pytest.approx((0.012 + 0.010) / 2)
    # programs of device 0 wholly inside the window
    assert r.module_durations("jit_step") == [pytest.approx(0.030),
                                              pytest.approx(0.020)]
    assert r.module_durations("jit_other") == []
    evs = r.module_events("jit_step")
    assert evs[1][0] - (evs[0][0] + evs[0][1]) == pytest.approx(0.045)


def test_gap_with_no_span_is_named_so():
    planes = _planes()
    planes[0] = ("/host:CPU", [("main", [(tr.WINDOW, 0, 100 * MS)])])
    r = tr.reduce_planes(planes)
    assert set(r.gap_seconds) == {tr.NO_SPAN}


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_planes([("/host:CPU", [("main", [("x", 0, 5)])])])


def test_interval_helpers():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr._subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr._subtract([(0, 4), (6, 9)], []) == [(0, 4), (6, 9)]


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "train_1chip.xplane.pb")


def test_recorded_trace():
    """One traced step of ``train-1chip`` (3 x 4096 tokens, 4 layers)
    recorded on a TPU v5e in this PR and cut to what the reduction reads:
    the window is the second of the three traced steps with the host's
    read of its loss, the device plane keeps its ``XLA Ops`` and ``XLA
    Modules`` lines, the host plane its thread lines; the planes of HLO
    metadata, the per-event stats and the ``Async XLA Ops`` line are gone
    (3.1 MB to 0.4 MB). The numbers are what the reduction read in the
    whole recording for this step, cross-checked by hand against the
    file's own lines: one ``jit_step`` execution of 359.6 ms, the only
    idle being the host's read of the loss after it."""
    r = tr.reduce_file(RECORDED)
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(0.36309, abs=1e-4)
    assert r.busy_s == pytest.approx(0.35962, abs=1e-4)
    steps = r.module_durations("jit_step")
    assert len(steps) == 1
    assert steps[0] == pytest.approx(0.35963, abs=1e-4)
    # idle gaps lie under the benchmark's own spans
    gaps = dict(r.top_gaps(10))
    assert max(gaps, key=gaps.get) == "bench.train.readback"
    assert gaps["bench.train.readback"] == pytest.approx(0.003418, abs=1e-5)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s,
                                               abs=1e-6)
    # self times add up to the busy time (one chip: nothing overlaps)
    assert sum(r.op_seconds.values()) == pytest.approx(r.busy_s, rel=1e-3)
    # the Mosaic flash kernels: 4 layers x (forward, dq, dkv)
    assert r.op_seconds_matching("tpu_custom_call") == pytest.approx(
        0.03461, abs=1e-4)
    # one chip: no collective
    assert r.collective_s == 0 and r.exposed_collective_s == 0
