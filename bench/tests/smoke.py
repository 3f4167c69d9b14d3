"""Smoke sizes of every configuration and mix, for the benchmark's CPU
tests, and one run of a cell at those sizes."""

SMOKE_LM = {
    "model": {"family": "decoder", "n_layers": 2, "d_model": 64,
              "n_heads": 4, "n_kv_heads": 2, "d_head": 16, "d_ff": 128,
              "vocab": 512, "mlp_type": "swiglu", "rope_theta": 10000.0,
              "norm_type": "rmsnorm", "norm_eps": 1e-5,
              "tie_embeddings": False, "param_dtype": "bfloat16"},
    "serve": {"paged": True, "backend": "xla", "block_size": 8,
              "n_slots": 4, "max_len": 64, "num_blocks": None}}
SMOKE_DECODE_MIX = {
    "arrival": {"process": "backlog", "min_pending": 4},
    "user_len": {"dist": "uniform_int", "lo": 9, "hi": 12},
    "output_len": {"dist": "uniform_int", "lo": 6, "hi": 10},
    "ramp_s": 0.3, "trace_s": 0.5}
SMOKE_CHAT_MIX = dict(
    SMOKE_DECODE_MIX,
    arrival={"process": "poisson", "rate_hz": 10.0},
    system={"count": 2, "len": 16,
            "pick": {"dist": "zipf", "n": 2, "s": 1.1}},
    drain_cap_s=10.0)
SMOKE_LENET = {
    "lenet": {"image_size": 28, "channels": 1, "conv1_filters": 8,
              "conv2_filters": 8, "ksize": 5, "dense": 32, "classes": 10,
              "dropout": 0.5}}
SMOKE_FRAMES_MIX = {"pool": 16, "chunks": 4, "check_frames": 256,
                    "trace_s": 0.5}
SEED = 2**31 + 11


def smoke_run(workload, *, seconds=1.5, trace=False, control=None,
              config=None, mix=None):
    """One run of ``workload`` on the CPU at smoke sizes (or at the
    configuration groups and mix fields ``config`` and ``mix`` give)."""
    from bench import harness
    lm = workload.startswith("stablelm")
    mix = mix or ((SMOKE_CHAT_MIX if "chat" in workload
                   else SMOKE_DECODE_MIX) if lm else SMOKE_FRAMES_MIX)
    if config is None:
        config = SMOKE_LM if lm else SMOKE_LENET
    return harness.run_cell(workload, SEED, seconds, trace,
                            control=control, on_chip=False,
                            config_override=config, mix_override=mix)
