"""Roofline cost attribution (serve/obs/costmodel.py): XLA cost analysis via
AOT lowering, the degradation ladder when the backend offers none, stage-key
to serving-span mapping, roofline verdicts on the real serving geometries
(in-place decode memory-bound, chunked prefill fold compute-bound), and the
bitwise per-stage energy re-fold against the telemetry ledger."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import configs
from repro.models import lm
from repro.serve import obs
from repro.serve.gateway import frontend as fe
from repro.serve.gateway.gateway import (GatewayConfig, MicroBatchGateway,
                                         PromptGateway)
from repro.serve.gateway.sensors import Arrival
from repro.serve.gateway.slots import ContinuousBatcher, make_adapter
from repro.serve.shard import ShardedPromptGateway, build_slices

_SETUP_CACHE: dict = {}


def _setup(arch="stablelm_3b"):
    if arch not in _SETUP_CACHE:
        cfg = dataclasses.replace(configs.smoke_config(arch),
                                  param_dtype="float32")
        params, _ = lm.init(jax.random.key(0), cfg, {})
        _SETUP_CACHE[arch] = (cfg, params)
    return _SETUP_CACHE[arch]


def _slice_mesh(i: int) -> Mesh:
    devs = jax.devices()
    return Mesh(np.asarray([devs[i % len(devs)]]), ("model",))


def _prompt_arrivals(cfg, n, plen=16, seed=0, dt=0.001):
    rng = np.random.default_rng(seed)
    return [Arrival(t=i * dt, uid=i, endpoint=0, kind="prompt",
                    payload=rng.integers(0, cfg.vocab, plen)
                    .astype(np.int32)) for i in range(n)]


def _fake_fn(result=None, exc=None):
    """A stand-in for a jitted fn whose ``.lower().compile()
    .cost_analysis()`` chain yields ``result`` (or raises ``exc``) — the
    shapes interpret mode / non-XLA backends actually produce."""
    class _Compiled:
        def cost_analysis(self):
            if exc is not None:
                raise exc
            return result

    class _Lowered:
        def compile(self):
            return _Compiled()

    class _Fn:
        def lower(self, *args):
            return _Lowered()

    return _Fn()


# ==========================================================================
# analyze(): real lowering + the per-version/per-backend shape drift.
# ==========================================================================

def test_analyze_counts_flops_and_bytes_of_real_jit():
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.zeros((64, 64), jnp.float32)
    cost = obs.analyze(f, (a, a))
    assert cost is not None
    # a 64^3 matmul is 2*n^3 FLOPs; byte traffic covers the 3 arrays
    assert cost["flops"] == pytest.approx(2 * 64 ** 3, rel=0.25)
    assert cost["bytes"] >= 3 * 64 * 64 * 4
    # abstract args lower identically (nothing executes)
    spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    assert obs.analyze(f, (spec, spec)) == cost


def test_analyze_degrades_to_none_when_backend_offers_nothing():
    assert obs.analyze(_fake_fn(exc=RuntimeError("no analysis")), ()) is None
    assert obs.analyze(_fake_fn(result=None), ()) is None
    assert obs.analyze(_fake_fn(result=[]), ()) is None        # not a dict
    assert obs.analyze(_fake_fn(result={}), ()) is None
    assert obs.analyze(_fake_fn(result={"other": 1.0}), ()) is None
    assert obs.analyze(
        _fake_fn(result={"flops": 0.0, "bytes accessed": 0.0}), ()) is None


def test_analyze_normalizes_old_jax_list_shape_and_partial_dicts():
    full = {"flops": 5.0, "bytes accessed": 10.0}
    assert obs.analyze(_fake_fn(result=full), ()) == \
        {"flops": 5.0, "bytes": 10.0}
    # the installed jax returns a dict; a list-shaped result is no
    # analysis the attributor can read, so the stage degrades
    assert obs.analyze(_fake_fn(result=[full]), ()) is None
    # bytes with no FLOP count is still useful (traffic-only verdict)
    assert obs.analyze(_fake_fn(result={"bytes accessed": 128.0}), ()) == \
        {"flops": 0.0, "bytes": 128.0}


# ==========================================================================
# Stage-key -> serving-span mapping.
# ==========================================================================

def test_span_for_strips_slice_prefixes_and_bucket_suffixes():
    assert obs.span_for("decode") == "tick"
    assert obs.span_for("slice0.decode") == "tick"
    assert obs.span_for("chunk_fold") == "prefill_chunk"
    assert obs.span_for("slice3.chunk_fold") == "prefill_chunk"
    assert obs.span_for("prefill") == "prefill"
    assert obs.span_for("copy") == "migrate"
    assert obs.span_for("sensor_b8") == "batch"
    assert obs.span_for("slice2.gateway_b4") == "batch"
    # static-only stages (no serving span measures them)
    assert obs.span_for("write_block") is None
    assert obs.span_for("scatter") is None


# ==========================================================================
# attribute(): degradation ladder, measured joins, verdicts.
# ==========================================================================

def test_attribute_degrades_per_stage_never_crashes():
    tr = obs.Tracer()
    tr.begin("tick", pid=obs.ENGINE_PID, tid=0, t=0.0)
    tr.end("tick", pid=obs.ENGINE_PID, tid=0, t=0.25)
    rep = obs.attribute(
        {"decode": (_fake_fn(exc=RuntimeError("interpret mode")), ()),
         "chunk_fold": (_fake_fn(result={"bytes accessed": 64.0}), ()),
         "prefill": (_fake_fn(result={"flops": 90.0,
                                      "bytes accessed": 100.0}), ())},
        tr)
    st = rep["stages"]
    # no analysis at all: measured timings still attributed
    assert st["decode"]["source"] == "measured-only"
    assert st["decode"]["verdict"] == "unknown"
    assert st["decode"]["flops"] is None
    assert st["decode"]["calls"] == 1
    assert st["decode"]["measured_s"] == pytest.approx(0.25)
    # bytes-only: pure traffic classifies memory-bound at intensity 0
    assert st["chunk_fold"]["source"] == "bytes-only"
    assert st["chunk_fold"]["verdict"] == "memory-bound"
    assert st["chunk_fold"]["intensity"] == 0.0
    # both terms: intensity vs the ridge
    assert st["prefill"]["source"] == "xla"
    assert st["prefill"]["intensity"] == pytest.approx(0.9)
    assert st["prefill"]["verdict"] == "compute-bound"
    assert rep["ridge_flops_per_byte"] == obs.DEFAULT_RIDGE


def test_attribute_without_tracer_is_static_only():
    rep = obs.attribute(
        {"decode": (_fake_fn(result={"flops": 1.0,
                                     "bytes accessed": 10.0}), ())})
    entry = rep["stages"]["decode"]
    assert entry["calls"] == 0 and entry["measured_s"] == 0.0
    assert entry["verdict"] == "memory-bound"
    assert "achieved_flops_per_s" not in entry    # no time base to rate over
    assert "energy" not in rep                    # no ledger attached


def test_attribute_respects_custom_ridge():
    stages = {"prefill": (_fake_fn(result={"flops": 90.0,
                                           "bytes accessed": 100.0}), ())}
    assert obs.attribute(stages, ridge=0.5)["stages"]["prefill"]["verdict"] \
        == "compute-bound"
    assert obs.attribute(stages, ridge=2.0)["stages"]["prefill"]["verdict"] \
        == "memory-bound"


# ==========================================================================
# The real serving geometries: decode streams the whole KV arena for one
# token of math (memory-bound); the chunked prefill fold amortizes weight
# traffic over a block of tokens (compute-bound).
# ==========================================================================

def test_roofline_classifies_decode_memory_bound_prefill_compute_bound():
    cfg, params = _setup()
    ad = make_adapter(cfg, params, n_slots=4, max_len=64, paged=True,
                      block_size=16)
    tr = obs.Tracer()
    mon = obs.SLOMonitor(obs.SLOPolicy.default(period_s=1.0, ttft_s=0.5))
    gw = PromptGateway(ContinuousBatcher(ad), max_new_tokens=8,
                       tracer=tr, slo=mon)
    gw.warmup((16,))
    tel = gw.run(_prompt_arrivals(cfg, 6, plen=16))
    assert len(tel.records) == 6

    rep = obs.attribute(gw.cost_args(), tr, telemetry=tel)
    st = rep["stages"]
    assert st["decode"]["source"] == "xla"
    assert st["decode"]["verdict"] == "memory-bound"
    assert st["decode"]["intensity"] < obs.DEFAULT_RIDGE
    assert st["chunk_fold"]["source"] == "xla"
    assert st["chunk_fold"]["verdict"] == "compute-bound"
    assert st["chunk_fold"]["intensity"] > obs.DEFAULT_RIDGE
    # measured spans joined: decode ticks ran and achieved rates follow
    assert st["decode"]["calls"] == len(tr.spans("tick"))
    assert st["decode"]["calls"] > 0
    assert st["decode"]["achieved_flops_per_s"] > 0
    assert st["chunk_fold"]["calls"] == len(tr.spans("prefill_chunk")) > 0

    # the energy cross-check rides along and re-folds bitwise
    en = rep["energy"]
    assert en["conserved"] is True
    assert en["n_requests"] == 6
    assert en["total_nj"] == tel.fleet_energy_nj
    assert set(en["stages_nj"]) == {"frontend_prefill_nj",
                                    "frontend_decode_nj", "link_nj"}
    assert all(v > 0 for v in en["stages_nj"].values())


def test_stage_energy_refolds_ledger_bitwise_with_migration():
    # reuse the sharded migration scenario: its ledger includes a
    # migration part, the hardest stage to keep conserved
    cfg, params = _setup()
    slices = build_slices(cfg, params, [_slice_mesh(0), _slice_mesh(1)],
                          n_slots=2, max_len=16, block_size=4)
    tr = obs.Tracer()
    gw = ShardedPromptGateway(slices, max_new_tokens=4, tracer=tr)
    gw.warmup((8,))
    tel = gw.run(_prompt_arrivals(cfg, 6, plen=8))
    en = obs.stage_energy(tr, tel)
    assert en["conserved"] is True
    assert en["fleet_energy_nj"] == tel.fleet_energy_nj
    assert en["n_requests"] == len(tel.records)
    # and the sharded registry exposes slice-prefixed stages that all map
    # to real serving spans or are static-only
    stages = gw.cost_args()
    assert any(k.startswith("slice0.") for k in stages)
    assert any(k.startswith("slice1.") for k in stages)
    rep = obs.attribute(stages, tr)
    assert rep["stages"]["slice0.decode"]["verdict"] in (
        "memory-bound", "unknown")


def test_frame_gateway_cost_args_lower_and_classify():
    spec = fe.FrontendSpec(mode="sc", bits=4)
    gw = MicroBatchGateway(GatewayConfig(bucket_sizes=(1, 2),
                                         service_model="fixed",
                                         fixed_service_s=0.001), spec)
    rep = obs.attribute(gw.cost_args())
    st = rep["stages"]
    assert set(st) == {"sensor_b1", "gateway_b1", "sensor_b2", "gateway_b2"}
    for entry in st.values():
        assert entry["source"] == "xla"
        assert entry["span"] == "batch"
        assert entry["flops"] > 0 and entry["bytes"] > 0


def test_costmodel_entry_points_charge_the_callback_counter():
    c0 = obs.callback_count()
    obs.attribute({})
    obs.stage_energy(obs.Tracer())
    assert obs.callback_count() > c0
