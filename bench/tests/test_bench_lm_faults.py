"""The LM cells' comparison catches a broken timed path: each fault is
planted under the driver at smoke size on the CPU, and ``correct`` must
come out false."""
import numpy as np
import pytest

from bench.tests.smoke import smoke_run


def _token_altered(monkeypatch):
    from repro.serve.kvcache.paged import PagedKVSlotAdapter
    orig = PagedKVSlotAdapter.decode

    def decode(self, tokens, active):
        return (orig(self, tokens, active) + 1) % self.cfg.vocab
    monkeypatch.setattr(PagedKVSlotAdapter, "decode", decode)


def _state_unchanged(monkeypatch):
    from repro.serve import engine
    orig = engine.decode_step_paged

    def step(cfg, params, cache, tokens, *, arena, **kw):
        _, new_cache, logits = orig(cfg, params, cache, tokens, arena=arena,
                                    **kw)
        return arena, new_cache, logits
    monkeypatch.setattr(engine, "decode_step_paged", step)


def _half_batch(monkeypatch):
    from repro.serve.kvcache.paged import PagedKVSlotAdapter
    orig = PagedKVSlotAdapter.decode

    def decode(self, tokens, active):
        half = np.asarray(active, bool).copy()
        half[self.n_slots // 2:] = False
        return orig(self, tokens, half)
    monkeypatch.setattr(PagedKVSlotAdapter, "decode", decode)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
def test_fault_makes_the_decode_cell_incorrect(monkeypatch, fault):
    fault(monkeypatch)
    line = smoke_run("stablelm3b-decode-backlog")
    gap = line["checks"]["max_logit_gap"]
    assert line["correct"] is False, gap
    assert gap["value"] > gap["limit"]
