"""The program's own spans beside the device: the reduction of
``bench/program_spans.py`` on intervals worked by hand, on the recorded
TPU trace (whose ``trace_reduce`` outputs it leaves as they were), and
the traced decode-backlog run of ``bench/tick_split.py`` on the CPU."""
import pathlib

import pytest

from bench import program_spans, trace_reduce

FIXTURE = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"
MS = 1_000_000


def test_recorded_trace_outputs_unchanged():
    prog = program_spans.load(str(FIXTURE))
    tr = prog.trace
    assert tr.window_s == pytest.approx(0.015320879, rel=1e-12)
    assert tr.busy_s == pytest.approx(0.000173807, rel=1e-12)
    assert tr.op_seconds() == {
        "%copy-start": pytest.approx(1.46e-07, rel=1e-9),
        "%copy-done": pytest.approx(3.5103e-05, rel=1e-9),
        "%convolution_tanh_fusion": pytest.approx(0.000138558, rel=1e-9)}
    assert tr.seconds_within("bench:step") == \
        pytest.approx(0.000110464, rel=1e-9)
    assert (tr.count("bench:step"), tr.count("bench:sleep")) == (3, 1)
    want_gaps = {"bench:sleep": 0.00852201, "host:none": 0.005335477,
                 "bench:step": 0.001289585}
    assert tr.idle_gaps() == {k: pytest.approx(v, rel=1e-9)
                              for k, v in want_gaps.items()}
    bd = tr.breakdown()
    assert [k for k, _ in bd["device_ops"]] == [
        "%convolution_tanh_fusion", "%copy-done", "%copy-start"]
    assert [k for k, _ in bd["idle_gaps"]] == [
        "bench:sleep", "host:none", "bench:step"]
    # the same file through the reduction alone
    base = trace_reduce.load(str(FIXTURE))
    assert (base.busy_s, base.op_seconds(), base.idle_gaps(),
            base.breakdown()) == (tr.busy_s, tr.op_seconds(),
                                  tr.idle_gaps(), tr.breakdown())
    # recorded before the program had spans: there is nothing to split
    assert prog.spans == [] and program_spans.tick_split(prog) == {}


def _program(spans, ops):
    tr = trace_reduce.Trace([("bench:window", 0, 100 * MS)],
                            {"/device:TPU:0": ops} if ops else {})
    return program_spans.Program(tr, spans)


def test_idle_inside_and_outside_by_hand():
    ops = [("a", 0, 10 * MS), ("b", 30 * MS, 40 * MS),
           ("c", 60 * MS, 70 * MS), ("d", 95 * MS, 120 * MS)]
    spans = [("repro:tick", 5 * MS, 35 * MS, {}),
             ("repro:tick", 50 * MS, 80 * MS, {}),
             ("repro:tick", 150 * MS, 160 * MS, {})]    # after the window
    prog = _program(spans, ops)
    assert prog.program_count("repro:tick") == 2
    assert prog.program_seconds("repro:tick") == pytest.approx(0.060)
    # idle [10,30] [40,60] [70,95]: 20 + 10 + 10 ms inside the ticks
    assert prog.idle_inside("repro:tick") == pytest.approx(0.040)
    assert prog.idle_outside("repro:tick") == pytest.approx(0.025)
    tr = prog.trace
    assert prog.idle_inside("repro:tick") + prog.idle_outside(
        "repro:tick") == pytest.approx(tr.window_s - tr.busy_s, abs=1e-15)
    # a name with no events: every idle second lies outside it
    assert prog.idle_inside("repro:none") == 0.0
    assert prog.idle_outside("repro:none") == pytest.approx(0.065)


def _ticks():
    """Two ticks worked by hand (ms): the device runs the tick's program
    from its dispatch to late in its sample."""
    spans = []
    for t0, parts, args in [
            (0, [("admit", 0, 5), ("tick.prepare", 5, 8),
                 ("tick.dispatch", 8, 10), ("tick.sample", 10, 38),
                 ("retire", 39, 40)],
             ({"admitted": 1, "prompt_tokens": 20},
              {"active": 16, "live_tokens": 100})),
            (45, [("admit", 45, 46), ("tick.prepare", 46, 50),
                  ("tick.dispatch", 50, 53), ("tick.sample", 53, 83),
                  ("retire", 83, 85)],
             ({"admitted": 0, "prompt_tokens": 0},
              {"active": 16, "live_tokens": 116}))]:
        spans.append(("repro:tick", t0 * MS, (parts[-1][2]) * MS, {}))
        for name, a, b in parts:
            arg = {"admit": args[0], "tick.prepare": args[1]}.get(name, {})
            spans.append((f"repro:{name}", a * MS, b * MS, arg))
    ops = [("%tick", 9 * MS, 36 * MS), ("%tick", 52 * MS, 80 * MS)]
    return spans, ops


def test_tick_split_by_hand():
    spans, ops = _ticks()
    tr = trace_reduce.Trace([("bench:window", 0, 90 * MS)],
                            {"/device:TPU:0": ops})
    split = program_spans.tick_split(program_spans.Program(tr, spans))
    assert split["ticks"] == 2
    assert split["tick_prepare_ms.decode"] == pytest.approx(3.5)
    assert split["tick_dispatch_ms.decode"] == pytest.approx(2.5)
    assert split["tick_sample_ms"] == pytest.approx(29.0)
    assert split["tick_host_ms"] == pytest.approx(40.0)
    # idle [0,9] [36,52] [80,90]: 9 + 4 + 7 + 5 ms inside the ticks,
    # [40,45] and [85,90] outside
    assert split["tick_idle_ms.decode"] == pytest.approx(12.5)
    assert split["loop_idle_ms.decode"] == pytest.approx(5.0)
    assert 2 * (split["tick_idle_ms.decode"]
                + split["loop_idle_ms.decode"]) == \
        pytest.approx(1e3 * (tr.window_s - tr.busy_s))
    assert split["tick_idle_ms_by_part"] == {
        "admit": pytest.approx(3.0), "tick.prepare": pytest.approx(3.5),
        "tick.dispatch": pytest.approx(1.5),
        "tick.sample": pytest.approx(2.5), "retire": pytest.approx(1.5),
        "self": pytest.approx(0.5)}     # [38,39], between sample and retire
    assert split["lanes_per_tick"] == 16
    assert split["live_tokens_per_tick"] == 108
    assert (split["admitted"], split["prompt_tokens"]) == (1, 20)


def test_tick_split_without_a_device_leaves_idle_out():
    spans, _ = _ticks()
    split = program_spans.tick_split(_program(spans, []))
    assert split["tick_prepare_ms.decode"] == pytest.approx(3.5)
    assert split["tick_dispatch_ms.decode"] == pytest.approx(2.5)
    assert not any("idle" in k for k in split)


@pytest.mark.parametrize("attach", [True, False])
def test_traced_decode_backlog_smoke(attach):
    from bench import harness, tick_split
    from bench.tests.smoke import SEED, SMOKE_DECODE_MIX, SMOKE_LM
    from repro.serve import spec
    make, begin = spec.make_gateway, harness.Run.trace_begin
    row = tick_split.split_run("stablelm3b-decode-backlog", SEED, 1.5,
                               attach, on_chip=False,
                               config_override=SMOKE_LM,
                               mix_override=SMOKE_DECODE_MIX)
    assert (spec.make_gateway, harness.Run.trace_begin) == (make, begin)
    assert row["correct"] is True
    assert row["metrics"]["tick_ms.decode"] > 0
    split = row["split"]
    if not attach:
        assert split == {}              # nothing attached: no program spans
        return
    assert split["ticks"] > 0
    assert split["tick_prepare_ms.decode"] > 0
    assert split["tick_dispatch_ms.decode"] > 0
    assert 0 < split["lanes_per_tick"] <= SMOKE_LM["serve"]["n_slots"]
    # no TPU here: no device plane, so no idle time is read
    assert "tick_idle_ms.decode" not in split
    assert "loop_idle_ms.decode" not in split
