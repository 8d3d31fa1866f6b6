"""``program_trace`` on planes built by hand (every number counted on
paper) and on the two small traces recorded on the chip in PR 24."""

import os

import pytest

from benchmark import program_trace as pt
from benchmark import trace_reduce as tr

MS = 1_000_000  # ns
SPANS = {"serve_step", "serve_step.upload", "serve_step.dispatch",
         "serve_step.readback", "serve_step.commit", "serve_admit",
         "serve_prefill", "serve_admit.commit", "sched_pop_ready"}
DATA = os.path.join(os.path.dirname(__file__), "data")


def _serve_planes():
    """Two decode programs, [0,10] and [20,30] ms, and the host between
    them: the first step's readback ends at 12 and its commit at 14, the
    driver's loop has the device to itself until 15, the next step uploads
    until 17 and its dispatch returns at 21, a millisecond after the device
    started."""
    host = ("/host:CPU", [("python3", [
        (tr.WINDOW, 0, 40 * MS),
        ("bench.engine.step", 0, 14 * MS),            # not the program's
        ("serve_step", 0, 14 * MS),
        ("serve_step.readback", 1 * MS, 12 * MS),
        ("serve_step.commit", 12 * MS, 14 * MS),
        ("serve_step", 15 * MS, 36 * MS),
        ("serve_step.upload", 15 * MS, 17 * MS),
        ("serve_step.dispatch", 17 * MS, 21 * MS),
        ("serve_step.readback", 21 * MS, 34 * MS),
        ("serve_step.commit", 34 * MS, 36 * MS),
    ])])
    dev = ("/device:TPU:0", [
        ("XLA Ops", [("%fusion.1 = f32[] fusion()", 0, 10 * MS),
                     ("%fusion.1 = f32[] fusion()", 20 * MS, 30 * MS)]),
        ("XLA Modules", [("jit__engine_step(7)", 0, 10 * MS),
                         ("jit__engine_step(7)", 20 * MS, 30 * MS)]),
    ])
    return [host, dev]


def test_one_gap_is_divided_five_ways():
    t = pt.ProgramTrace(_serve_planes(), {}, SPANS)
    assert t.window_s == pytest.approx(0.040)
    assert t.busy_s == pytest.approx(0.020)
    # the gap [10, 20]: readback 2, commit 2, nothing 1, upload 2, and 3
    # of dispatch, which opened inside the gap and outlasts it: launch
    # the gap [30, 40]: readback 4, commit 2, nothing 4
    assert t.idle_seconds == {
        "serve_step.readback": pytest.approx(0.006),
        "serve_step.commit": pytest.approx(0.004),
        pt.OUTSIDE: pytest.approx(0.005),
        "serve_step.upload": pytest.approx(0.002),
        "serve_step.dispatch" + pt.LAUNCH: pytest.approx(0.003),
    }
    assert sum(t.idle_seconds.values()) == pytest.approx(
        t.window_s - t.busy_s)
    q = pt.QUANTITIES
    read = lambda n: getattr(t, q[n][1])(**q[n][2])  # noqa: E731
    assert read("flood_idle_launch_ms") == pytest.approx(2.5)   # 5 ms / 2
    assert read("flood_idle_readback_ms") == pytest.approx(3.0)
    assert read("flood_idle_commit_ms") == pytest.approx(2.0)
    assert read("flood_idle_outside_ms") == pytest.approx(2.5)
    assert read("chat_step_idle_ms") == pytest.approx(7.5)
    assert read("chat_admit_idle_ms") is None     # no prefill program


def test_clock_check_reads_both_slacks_and_flags_a_skewed_session():
    t = pt.ProgramTrace(_serve_planes(), {}, SPANS)
    # the second program starts 3 ms after its dispatch opened and its
    # readback returns 4 ms after it ended; the first has no dispatch
    assert t.clock_check() == {
        "start_after_dispatch_opened_ms": [pytest.approx(3.0)] * 2,
        "readback_back_after_end_ms": [pytest.approx(4.0)] * 2}
    # the same host with the device's clock 4 ms early: the program now
    # starts before the span that launched it, which cannot be
    planes = _serve_planes()
    planes[1] = ("/device:TPU:0", [(ln, [(n, s - 4 * MS, e - 4 * MS)
                                         for n, s, e in evs if s > 0])
                                   for ln, evs in planes[1][1]])
    skewed = pt.ProgramTrace(planes, {}, SPANS).clock_check()
    assert skewed["start_after_dispatch_opened_ms"][0] == pytest.approx(-1.0)
    assert pt.ProgramTrace(_serve_planes(), {}, set()).clock_check() == {}


def test_the_innermost_span_owns_and_bench_spans_do_not():
    planes = _serve_planes()
    # the whole window under serve_admit, a prefill inside it over [10, 13]
    planes[0][1][0][1].extend([("serve_admit", 0, 40 * MS),
                               ("serve_prefill", 10 * MS, 13 * MS)])
    t = pt.ProgramTrace(planes, {}, SPANS)
    # [10, 13] lies under readback, which started at 1, and under the
    # prefill, which started at 10: the later start is the innermost
    assert t.idle_seconds["serve_prefill"] == pytest.approx(0.002)
    assert t.idle_seconds["serve_step.readback"] == pytest.approx(0.004)
    assert pt.OUTSIDE not in t.idle_seconds
    assert t.idle_seconds["serve_admit"] == pytest.approx(0.005)
    assert not any(k.startswith("bench.") for k in t.idle_seconds)


def test_no_program_span_reads_nothing():
    """A commit before the spans existed: every gap is outside, and the
    readers return None rather than a zero."""
    t = pt.ProgramTrace(_serve_planes(), {}, set())
    assert set(t.idle_seconds) == {pt.OUTSIDE}
    for name, (_cells, reader, args) in pt.QUANTITIES.items():
        assert getattr(t, reader)(**args) is None, name


def test_scope_of():
    scopes = ("grad_sync/pack", "grad_sync/reduce", "lm_head_loss",
              "attention", "optimizer")
    for op, want in [
            ("jit(step)/shard_map/grad_sync/reduce/psum", "grad_sync/reduce"),
            ("jit(step)/shard_map/transpose(jvp(lm_head_loss))/dot_general",
             "lm_head_loss"),
            ("jit(step)/jvp(attention)/jit(_where)/select_n", "attention"),
            ("jit(step)/shard_map/checkpoint/rematted_computation/"
             "transpose(jvp(attention))/pallas_call", "attention"),
            ("jit(step)/optimizer/integer_pow", "optimizer"),
            ("jit(step)/shard_map/transpose(jvp())/dot_general", None),
            ("jit(step)/my_attention/mul", None),
            ("", None), (None, None)]:
        assert pt.scope_of(op, scopes) == want, op


def test_a_nameless_instruction_inherits():
    """(name, op_name, id, operands, called computations, computation):
    an entry computation 1 with a named pad, a nameless while over it
    whose body (computation 2) holds nameless ops, a nameless broadcast
    that only the while uses, a named matmul outside every scope and a
    nameless copy of its result."""
    ins = [pt.Instruction(*i) for i in (
        ("pad.2", "jit(step)/grad_sync/pack/pad", 10, [], [], 1),
        ("broadcast.7", None, 11, [], [], 1),
        ("while.3", None, 12, [10, 11], [2], 1),
        ("dynamic-update-slice.85", None, 20, [], [], 2),
        ("fusion.9", "jit(step)/transpose(jvp())/dot_general", 13, [], [], 1),
        ("copy.4", None, 14, [13], [], 1),
        ("psum.36", "jit(step)/grad_sync/reduce/psum", 15, [12], [], 1),
    )]
    got = pt.instruction_scopes(ins, ("grad_sync/pack", "grad_sync/reduce"))
    assert got["pad.2"] == ("grad_sync/pack", False)
    assert got["while.3"] == ("grad_sync/pack", True)       # its operand
    assert got["dynamic-update-slice.85"] == ("grad_sync/pack", True)
    assert got["broadcast.7"] == ("grad_sync/pack", True)   # its user
    assert got["fusion.9"] == (None, False)     # named, and not ours
    assert got["copy.4"] == (None, True)
    assert got["psum.36"] == ("grad_sync/reduce", False)


def _train_planes():
    dev = ("/device:TPU:0", [
        ("XLA Ops", [
            ("%fusion.1 = f32[8] fusion()", 0, 40 * MS),
            ("%while.3 = () while()", 40 * MS, 60 * MS),
            ("%dynamic-update-slice.85 = f32[8] d-u-s()", 40 * MS, 55 * MS),
            ("%psum.36 = f32[8] all-reduce(f32[8] %x)", 60 * MS, 70 * MS),
            ("%fusion.2 = f32[8] fusion()", 70 * MS, 100 * MS),
        ]),
        ("XLA Modules", [("jit_step(9)", 0, 100 * MS)]),
    ])
    host = ("/host:CPU", [("python3", [(tr.WINDOW, 0, 100 * MS)])])
    scopes = {"jit_step(9)": {
        "fusion.1": ("lm_head_loss", False),
        "while.3": ("grad_sync/pack", True),
        "dynamic-update-slice.85": ("grad_sync/pack", True),
        "psum.36": ("grad_sync/reduce", False),
        "fusion.2": (None, False)}}
    return [host, dev], scopes


def test_scope_times_by_hand():
    planes, scopes = _train_planes()
    t = pt.ProgramTrace(planes, scopes, set())
    assert t.scope_seconds == {
        ("lm_head_loss", "named"): pytest.approx(0.040),
        ("grad_sync/pack", "inherited"): pytest.approx(0.020),  # 5 + 15
        ("grad_sync/reduce", "wire"): pytest.approx(0.010),
        (pt.UNSCOPED, "named"): pytest.approx(0.030)}
    assert t.unscoped_ops == {"%fusion.2 = f32[8] fusion()":
                              pytest.approx(0.030)}
    assert t.scope_device_pct("grad_sync") == pytest.approx(30.0)
    assert t.scope_device_pct("lm_head_loss") == pytest.approx(40.0)
    assert t.scope_device_ms_per_step("grad_sync", "without") \
        == pytest.approx(20.0)
    assert t.scope_device_ms_per_step("grad_sync", "only") \
        == pytest.approx(10.0)
    assert t.scope_device_pct("optimizer") is None
    # a program of the same instruction names that is not the step
    planes[1][1][1][1][0] = ("jit_other(3)", 0, 100 * MS)
    assert pt.ProgramTrace(planes, scopes, set()).scope_device_pct(
        "grad_sync") is None


def test_quantities_name_what_the_program_marks():
    spans, scopes = pt.program_tables()
    assert spans and scopes
    for name, (cells, reader, args) in pt.QUANTITIES.items():
        assert cells and hasattr(pt.ProgramTrace, reader), name
        for s in args.get("spans", []):
            assert s == pt.OUTSIDE or s.removesuffix(pt.LAUNCH) in spans, s
        if "scope" in args:
            assert any(sc.startswith(args["scope"]) for sc in scopes)


def test_recorded_serve_flood():
    """Three decode steps and one prefill of ``serve-flood`` (Mistral-7B
    widths, 16 layers, 32 lanes) recorded on a TPU v5e in PR 24 and cut by
    ``cut_trace.py``: the window runs from the end of one decode program
    to the end of the fourth after it. The numbers are what the reduction
    read; checked by hand against the file's lines: the decode program
    takes 15.95 ms, the host is back from ``np.asarray`` 1.3 ms after the
    device is done, and the prefill's program starts 1.9 ms after its
    ``serve_prefill`` span opened (two one-element ``convert_element_type``
    programs go first)."""
    t = pt.load(os.path.join(DATA, "serve_flood.xplane.pb.gz"))
    assert t.window_s == pytest.approx(0.0695236, abs=1e-6)
    assert t.busy_s == pytest.approx(0.0608168, abs=1e-6)
    assert len(t.programs(pt.DECODE)) == 3
    assert len(t.programs(pt.PREFILL)) == 1
    assert t.programs(pt.DECODE)[0] == pytest.approx(0.01595, abs=2e-5)
    assert sum(t.idle_seconds.values()) == pytest.approx(
        t.window_s - t.busy_s, abs=1e-9)
    q = pt.QUANTITIES
    got = {n: getattr(t, q[n][1])(**q[n][2]) for n in q
           if "serve-flood" in q[n][0]}
    assert got["flood_idle_readback_ms"] == pytest.approx(1.2916, abs=1e-3)
    assert got["flood_idle_launch_ms"] == pytest.approx(0.5647, abs=1e-3)
    assert got["flood_idle_commit_ms"] == pytest.approx(0.1194, abs=1e-3)
    assert got["flood_idle_outside_ms"] == pytest.approx(0.9266, abs=1e-3)
    # the four are the idle time, per decode program
    assert sum(got.values()) == pytest.approx(
        1e3 * (t.window_s - t.busy_s) / 3, rel=1e-6)
    # the device starts while dispatch is still open, not under readback,
    # and the session's alignment passes both of its checks
    check = t.clock_check()
    assert check["start_after_dispatch_opened_ms"][0] == pytest.approx(
        0.376, abs=0.01)
    assert check["readback_back_after_end_ms"][1] == pytest.approx(
        1.112, abs=0.01)
    assert t.idle_seconds["serve_step.readback" + pt.LAUNCH] < 1e-5
    assert t.idle_seconds["serve_prefill" + pt.LAUNCH] \
        == pytest.approx(0.001871, abs=1e-5)


def test_recorded_train_step():
    """One step of ``train-1chip`` (InternLM2-1.8B widths, 4 layers, 3 x
    4096 tokens) recorded on a TPU v5e in PR 24 with the program's HLO
    proto kept (stripped to names, ``op_name``, ids, operands and called
    computations). The whole recording read the same shares to three
    digits on every one of its six steps."""
    t = pt.load(os.path.join(DATA, "train_1chip_scoped.xplane.pb.gz"))
    (step,) = t.programs(pt.STEP)
    assert step == pytest.approx(0.359578, abs=1e-5)
    by_scope = {}
    for (sc, _kind), v in t.scope_seconds.items():
        by_scope[sc] = by_scope.get(sc, 0.0) + v
    assert sum(by_scope.values()) == pytest.approx(t.busy_s, rel=1e-6)
    assert by_scope["lm_head_loss"] == pytest.approx(0.09956, abs=1e-4)
    assert by_scope["attention"] == pytest.approx(0.03950, abs=1e-4)
    assert by_scope["optimizer"] == pytest.approx(0.03011, abs=1e-4)
    assert by_scope["grad_sync/pack"] == pytest.approx(0.01638, abs=1e-4)
    assert by_scope["grad_sync/unpack"] == pytest.approx(0.02316, abs=1e-4)
    assert "grad_sync/reduce" not in by_scope     # one chip: no wire
    assert t.scope_seconds[("grad_sync/pack", "inherited")] \
        == pytest.approx(0.006355, abs=1e-5)
    q = pt.QUANTITIES
    got = {n: getattr(t, q[n][1])(**q[n][2]) for n in q
           if "train-1chip" in q[n][0]}
    assert got["sync_device_pct"] == pytest.approx(10.997, abs=0.01)
    assert got["sync_staging_ms"] == pytest.approx(39.542, abs=0.01)
    assert got["head_loss_device_pct"] == pytest.approx(27.689, abs=0.01)
    # staging + wire is the sync's share of the step's device time
    wire = t.scope_device_ms_per_step("grad_sync", "only") or 0.0
    assert got["sync_staging_ms"] + wire == pytest.approx(
        got["sync_device_pct"] / 100 * step * 1e3, rel=1e-6)
    # the flash kernels, forward and backward, lie under ``attention``
    flash = sum(v for k, v in t.unscoped_ops.items()
                if "tpu_custom_call" in k)
    assert flash == 0


def test_recorded_dp4_step():
    """One step of ``train-dp4`` (the same model on a dp=4 mesh, four
    device planes) recorded in PR 24. The sync is 45% of the step's device
    time: the one ``all-reduce`` 44.8 ms, the staging round it 242 ms, of
    which 137 ms are loops the TPU compiler made of the bucket matrix's
    reshape and gave no ``op_name``; they inherit ``grad_sync/pack`` from
    the ``pad`` they continue (`instruction_scopes`)."""
    t = pt.load(os.path.join(DATA, "train_dp4_scoped.xplane.pb.gz"))
    (step,) = t.programs(pt.STEP)
    assert step == pytest.approx(0.630978, abs=1e-5)
    assert t.scope_seconds[("grad_sync/reduce", "wire")] \
        == pytest.approx(0.04478, abs=1e-5)
    assert t.scope_seconds[("grad_sync/pack", "inherited")] \
        == pytest.approx(0.13694, abs=1e-4)
    assert t.scope_seconds[("grad_sync/pack", "named")] \
        == pytest.approx(0.00697, abs=1e-4)
    assert t.scope_seconds[("grad_sync/unpack", "named")] \
        == pytest.approx(0.09828, abs=1e-4)
    q = pt.QUANTITIES
    got = {n: getattr(t, q[n][1])(**q[n][2]) for n in q
           if "train-dp4" in q[n][0]}
    assert got["sync_device_pct"] == pytest.approx(45.479, abs=0.01)
    assert got["sync_staging_ms"] == pytest.approx(242.185, abs=0.01)
    assert got["head_loss_device_pct"] == pytest.approx(19.688, abs=0.01)
    wire = t.scope_device_ms_per_step("grad_sync", "only")
    assert wire == pytest.approx(44.78, abs=0.01)
    assert got["sync_staging_ms"] + wire == pytest.approx(
        got["sync_device_pct"] / 100 * step * 1e3, rel=1e-6)
    # the loops by name: nothing of them is left unscoped
    assert not any("dynamic-update-slice" in k for k in t.unscoped_ops)
    # every device second is under a scope or listed by op as unscoped
    total = sum(t.scope_seconds.values())
    assert total == pytest.approx(t.busy_s, rel=1e-6)
    assert sum(t.unscoped_ops.values()) == pytest.approx(sum(
        v for (sc, _k), v in t.scope_seconds.items()
        if sc == pt.UNSCOPED), rel=1e-9)


def test_the_accepted_reduction_reads_the_cut_files_alike():
    """``trace_reduce`` on the same recordings: the annotations the
    program adds are not ``bench.*`` spans and move nothing it reads."""
    import gzip
    from jax.profiler import ProfileData
    for name, idle in (("serve_flood", 0.0087069),
                       ("train_1chip_scoped", 0.0031732),
                       ("train_dp4_scoped", 0.0060051)):
        with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz")) as f:
            planes = tr.planes_of(
                ProfileData.from_serialized_xspace(f.read()))
        r = tr.reduce_planes(planes)
        t = pt.ProgramTrace(planes, {}, SPANS)
        assert r.window_s == t.window_s and r.busy_s == t.busy_s
        assert r.modules == t.modules
        assert sum(r.gap_seconds.values()) == pytest.approx(idle, abs=1e-6)
        assert all(k.startswith("bench.") or k == tr.NO_SPAN
                   for k in r.gap_seconds)
