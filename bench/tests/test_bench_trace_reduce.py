"""The reduction from a trace to busy time, kernel time and labelled
idle gaps: on intervals worked by hand, and on a small trace recorded on
a TPU (``data/small.xplane.pb``, made by ``record_trace_fixture.py``)."""
import pathlib

import pytest

from bench import trace_reduce

FIXTURE = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"
MS = 1_000_000


def test_union_clipping_and_gaps_by_hand():
    host = [("bench:window", 10 * MS, 110 * MS),
            ("bench:step", 10 * MS, 60 * MS),
            ("bench:decode_tick", 20 * MS, 40 * MS),
            ("bench:submit", 70 * MS, 80 * MS)]
    ops = [("a", 0, 20 * MS),            # half outside the window
           ("b", 15 * MS, 30 * MS),      # overlaps a
           ("_paged_kernel", 35 * MS, 45 * MS),
           ("c", 100 * MS, 200 * MS)]    # runs past the window
    tr = trace_reduce.Trace(host, {"/device:TPU:0": ops})
    assert tr.window_s == pytest.approx(0.100)
    # busy: [10,30] + [35,45] + [100,110] = 40 ms
    assert tr.busy_s == pytest.approx(0.040)
    assert tr.seconds_matching("paged_kernel") == pytest.approx(0.010)
    assert tr.op_seconds()["c"] == pytest.approx(0.010)
    gaps = tr.idle_gaps()
    # [30,35]: inside decode_tick; [45,100]: midpoint 72.5 in submit
    assert gaps == {"bench:decode_tick": pytest.approx(0.005),
                    "bench:submit": pytest.approx(0.055)}
    assert sum(gaps.values()) == pytest.approx(tr.window_s - tr.busy_s)
    # device time of the ops inside the step span [10, 60]: a 10, b 15,
    # the kernel 10 (summed per op: a TPU core runs one op at a time)
    assert tr.seconds_within("bench:step") == pytest.approx(0.035)
    bd = tr.breakdown(top=2)
    assert [k for k, _ in bd["device_ops"]] == ["b", "a"]
    assert bd["idle_gaps"][0] == ["bench:submit", pytest.approx(0.055)]


def test_nested_events_count_once():
    # a loop's event holds its body's events on the same line
    ops = [("%while.2", 0, 10 * MS), ("%fusion.1", 1 * MS, 4 * MS),
           ("%paged_decode_attention.6", 5 * MS, 9 * MS)]
    tr = trace_reduce.Trace([("bench:window", 0, 20 * MS)],
                            {"/device:TPU:0": ops})
    assert tr.busy_s == pytest.approx(0.010)
    assert tr.op_seconds() == {"%fusion.1": pytest.approx(0.003),
                               "%paged_decode_attention.6":
                               pytest.approx(0.004)}


def test_no_window_span_fails():
    with pytest.raises(ValueError):
        trace_reduce.Trace([("bench:step", 0, 1)], {})


def test_trace_recorded_here(tmp_path):
    """A profiler trace recorded in this process: the benchmark's host
    spans are found by name and the window is their clock's."""
    import time

    import jax
    import jax.numpy as jnp
    span = jax.profiler.TraceAnnotation
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with span("bench:window"):
        for _ in range(3):
            with span("bench:step"):
                f(x).block_until_ready()
        with span("bench:sleep"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    tr = trace_reduce.load(trace_reduce.find(str(tmp_path)))
    assert tr.count("bench:step") == 3 and tr.count("bench:sleep") == 1
    assert 0.02 <= tr.window_s < 5.0
    if not tr.device:       # no TPU here: the device was never busy
        assert tr.busy_s == 0.0
        assert tr.idle_gaps() == {"host:none": tr.window_s}


def test_recorded_tpu_trace():
    tr = trace_reduce.load(str(FIXTURE))
    assert list(tr.device) == ["/device:TPU:0"]
    assert tr.count("bench:step") == 3 and tr.count("bench:sleep") == 1
    assert 0.0 < tr.busy_s < tr.window_s
    idle = tr.idle_gaps()
    assert sum(idle.values()) == pytest.approx(tr.window_s - tr.busy_s)
    # the 5 ms sleep is idle and charged to its span
    assert idle.get("bench:sleep", 0.0) >= 0.004
    # the matmul program ran inside the steps (the device's clock is put on
    # the host's to within some microseconds, a share of these 12 us ops),
    # and ops are named by their HLO instruction
    ops = tr.op_seconds()
    assert all(name.startswith("%") and " " not in name for name in ops)
    assert tr.seconds_within("bench:step") >= \
        0.5 * tr.seconds_matching("tanh_fusion") > 0
    assert sum(ops.values()) <= tr.busy_s + 1e-9
