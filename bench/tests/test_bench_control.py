"""The control of each cell, at a size a test run holds: the plain
reference put in the program's place through ``run_cell`` and computed
one precision step below the configuration's must make ``correct``
false, while the program's own readings of the same run stay within
their limits."""
import pytest

from bench.reference import decoder, lenet_sc
from bench.tests.smoke import SMOKE_DECODE_MIX, SMOKE_LM, smoke_run

# the decoder's depth at a test's width: 32 layers of width 256
DEEP_LM = dict(SMOKE_LM, model=dict(
    SMOKE_LM["model"], n_layers=32, d_model=256, n_heads=4, n_kv_heads=4,
    d_head=64, d_ff=688, vocab=8192),
    serve=dict(SMOKE_LM["serve"], max_len=128))
# outputs long enough that the sample compares some hundreds of tokens
LONG_MIX = dict(SMOKE_DECODE_MIX, check_requests=8,
                output_len={"dist": "uniform_int", "lo": 56, "hi": 72})


def _control_fails(line):
    assert line["correct"] is False
    failing = [n for n, c in line["checks"].items()
               if c["value"] > c["limit"]]
    assert failing, line["checks"]
    for name, value in line["info"]["program"].items():
        assert value <= line["checks"][name]["limit"], (name, value)


@pytest.mark.parametrize("control", decoder.CONTROLS)
def test_lm_control_fails_the_limit(control):
    _control_fails(smoke_run("stablelm3b-decode-backlog", control=control,
                             config=DEEP_LM, mix=LONG_MIX, seconds=3.0))


@pytest.mark.parametrize("control", lenet_sc.CONTROLS)
def test_frames_control_fails_the_limit(add_cell, control):
    add_cell("lenet5-sc-frames-backlog")
    _control_fails(smoke_run("lenet5-sc-frames-backlog", control=control))
