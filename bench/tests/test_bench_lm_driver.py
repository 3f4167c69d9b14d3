"""The LM driver end to end on the CPU at smoke sizes, called as a
function: a sound run is correct and reports its metrics."""
from bench.tests.smoke import smoke_run


def test_decode_backlog_smoke():
    line = smoke_run("stablelm3b-decode-backlog")
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    m = line["metrics"]
    assert set(m) == {"tokens_per_s", "setup_s"}
    assert m["tokens_per_s"]["value"] > 0
    assert list(line)[-1] == "checks"
    assert line["checks"]["max_logit_gap"]["value"] <= \
        line["checks"]["max_logit_gap"]["limit"]


CHAT = "stablelm3b-chat-prefix"


def test_chat_prefix_smoke(add_cell):
    add_cell(CHAT)
    line = smoke_run(CHAT)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ttft_p90_ms", "tpot_p95_ms", "setup_s"}
    assert line["metrics"]["ttft_p90_ms"]["value"] > 0
    assert line["metrics"]["tpot_p95_ms"]["value"] > 0
    assert line["attempted"] > 0 and line["failed"] == 0


def test_chat_prefix_traced_smoke(add_cell):
    add_cell(CHAT)
    line = smoke_run(CHAT, trace=True)
    assert line["correct"] is True
    m = line["metrics"]
    # prompts share a 16-token system prompt: some prefill was skipped
    assert 0 < m["prefix_hit_share.chat"]["value"] < 100
    assert m["prefill_ms.chat"]["value"] > 0
    # no TPU here: no device metric is reported
    assert "device_idle_share.decode" not in m
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
