"""The one traffic generator: turns a mix file of ``bench/traffic/`` into
requests or frames, from ``--seed`` alone.

A mix file is data only (JSON).  Its ``kind`` says which front door it
feeds ("lm" or "frames"); the fields it may set are read here and nowhere
else, so a new mix is a new file and never new code.

Every random size is drawn stratified: in each block of ``STRATUM``
draws the quantiles (j + 0.5) / STRATUM, j = 0..STRATUM-1, each appear
once, in an order the seed shuffles.  So every seed offers the same
multiset of sizes and gaps, in another order, and a run's load does not
swing with the seed; the token contents are the seed's own.  A
distribution is thus served as its 16 quantiles: "uniform 16-64" is 16
fixed lengths.
"""
from __future__ import annotations

import json
import math
import pathlib
from statistics import NormalDist

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
STRATUM = 16


def load(name: str) -> dict:
    """The mix file ``bench/traffic/<name>.json``; an unknown name fails."""
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any seed up to 2**63."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def ppf(spec: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of a size distribution at quantiles ``u`` in (0, 1).

    uniform_int  {lo, hi}                 integers lo..hi, equally likely
    lognormal_int {median, sigma, lo, hi} round(median * e**(sigma z)),
                                          clipped to lo..hi
    zipf         {n, s}                   rank 0..n-1 with P(k) ~ (k+1)**-s
    exponential  {mean}                   seconds
    """
    u = np.asarray(u, np.float64)
    kind = spec["dist"]
    if kind == "uniform_int":
        lo, hi = int(spec["lo"]), int(spec["hi"])
        return np.minimum(lo + np.floor(u * (hi - lo + 1)), hi).astype(np.int64)
    if kind == "lognormal_int":
        z = np.asarray([NormalDist().inv_cdf(float(x)) for x in u])
        v = np.round(spec["median"] * np.exp(spec["sigma"] * z))
        return np.clip(v, spec["lo"], spec["hi"]).astype(np.int64)
    if kind == "zipf":
        p = np.arange(1, int(spec["n"]) + 1, dtype=np.float64) ** -spec["s"]
        cdf = np.cumsum(p / p.sum())
        return np.minimum(np.searchsorted(cdf, u, side="right"),
                          int(spec["n"]) - 1).astype(np.int64)
    if kind == "exponential":
        return -float(spec["mean"]) * np.log1p(-u)
    raise ValueError(f"unknown distribution {kind!r}")


def support(spec: dict) -> list[int]:
    """Every value an integer distribution can produce (for warm-up)."""
    if spec["dist"] in ("uniform_int", "lognormal_int"):
        return list(range(int(spec["lo"]), int(spec["hi"]) + 1))
    if spec["dist"] == "zipf":
        return list(range(int(spec["n"])))
    raise ValueError(f"{spec['dist']!r} has no finite support")


QUANTILES = (np.arange(STRATUM) + 0.5) / STRATUM


def _shuffled(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` quantiles, each block of ``STRATUM`` in the seed's order."""
    blocks = -(-n // STRATUM)
    return np.concatenate([np.empty(0)] + [rng.permutation(QUANTILES)
                                           for _ in range(blocks)])[:n]


def stratified(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws of ``spec``, stratified in blocks of ``STRATUM``."""
    return ppf(spec, _shuffled(n, rng))


def remaining(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws of the tokens a lane still has to make in a backlog in
    steady state whose outputs follow ``spec``: ``P(r) ~ P(L >= r)`` for
    ``r = 1..max L``, over the ``STRATUM`` lengths ``spec`` is served as."""
    lengths = ppf(spec, QUANTILES)
    r = np.arange(1, int(lengths.max()) + 1)
    weight = (lengths[None, :] >= r[:, None]).sum(1)
    cdf = np.cumsum(weight) / weight.sum()
    return r[np.searchsorted(cdf, _shuffled(n, rng))]


# --------------------------------------------------------------------------
# LM requests
# --------------------------------------------------------------------------

def system_prompts(mix: dict, seed: int, vocab: int) -> list[np.ndarray]:
    """The mix's shared system prompts (empty when it shares none)."""
    shared = mix.get("system")
    if not shared:
        return []
    rng = rng_for(seed, 1)
    return [rng.integers(0, vocab, int(shared["len"]), dtype=np.int32)
            for _ in range(int(shared["count"]))]


def lm_requests(mix: dict, seed: int, vocab: int, n: int) -> list[dict]:
    """The first ``n`` requests of the mix, in arrival order.

    Each is ``{"uid", "due", "prompt", "max_new", "system"}``: ``due`` is
    seconds after the window opens (negative during the ramp; None for a
    backlog, which submits as lanes free up), ``system`` the index of the
    shared system prompt or -1.  The first ``min_pending`` requests of a
    backlog, which it admits together, take ``remaining`` output lengths,
    so that their lanes finish spread out as in a backlog in steady state
    and the window does not open on lanes that all started at once.
    """
    arrival = mix["arrival"]
    sizes = rng_for(seed, 2)
    user = stratified(mix["user_len"], n, sizes)
    out_len = stratified(mix["output_len"], n, sizes)
    if arrival["process"] == "backlog":
        first = min(int(arrival["min_pending"]), n)
        out_len[:first] = remaining(mix["output_len"], first,
                                    rng_for(seed, 10))
    systems = system_prompts(mix, seed, vocab)
    sys_idx = (stratified(mix["system"]["pick"], n, sizes) if systems
               else np.full(n, -1))
    if arrival["process"] == "poisson":
        gaps = stratified({"dist": "exponential",
                           "mean": 1.0 / float(arrival["rate_hz"])},
                          n, rng_for(seed, 3))
        due = np.cumsum(gaps) - float(mix.get("ramp_s", 0.0))
    elif arrival["process"] == "backlog":
        due = [None] * n
    else:
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    tokens = rng_for(seed, 4)
    reqs = []
    for i in range(n):
        user_tokens = tokens.integers(0, vocab, int(user[i]), dtype=np.int32)
        s = int(sys_idx[i])
        prompt = (np.concatenate([systems[s], user_tokens]) if s >= 0
                  else user_tokens)
        reqs.append({"uid": i, "due": None if due[i] is None
                     else float(due[i]), "prompt": prompt,
                     "max_new": int(out_len[i]), "system": s})
    return reqs


def lm_prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length the mix can produce."""
    base = int(mix["system"]["len"]) if mix.get("system") else 0
    return [base + u for u in support(mix["user_len"])]


def lm_request_count(mix: dict, seconds: float, n_slots: int) -> int:
    """Requests to generate so that the run never runs out."""
    arrival = mix["arrival"]
    if arrival["process"] == "backlog":
        lo = int(mix["output_len"]["lo"])
        # every lane finishes at most one request per `lo` ticks, and a
        # tick takes at least a millisecond
        span = float(mix.get("ramp_s", 0.0)) + seconds
        return n_slots * (2 + math.ceil(span / (lo * 1e-3))) + \
            int(arrival["min_pending"])
    rate = float(arrival["rate_hz"])
    return math.ceil(rate * (float(mix.get("ramp_s", 0.0)) + seconds) * 1.5
                     ) + 64


# --------------------------------------------------------------------------
# Frames
# --------------------------------------------------------------------------

def frame_indices(mix: dict, seed: int, n: int) -> np.ndarray:
    """Which image of the pool each of ``n`` frames shows."""
    return rng_for(seed, 5).integers(0, int(mix["pool"]), n)
