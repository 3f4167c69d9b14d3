"""Family-generic continuous batching: slot adapters + one scheduler loop.

The scheduler (admit / decode / retire) is family-agnostic; what differs
between model families is only how a slot's context is stored:

  StateSlotAdapter — O(1)-state families (rwkv): a request's entire context
    is a state pytree, so admission is a single scatter into the batched
    slot arrays and there are no position-alignment concerns.

  KVSlotAdapter — attention-cache families (decoder/moe/hybrid/encdec): each
    slot owns a B=1 cache (k/v padded to ``max_len``) with its *own* length,
    stacked on a leading slot axis.  The batched decode is a vmapped
    ``engine.decode_step``, which threads the per-slot lengths through
    ``attend_decode`` automatically — slots at different positions decode
    together in one fixed-shape compiled call.

  PagedKVSlotAdapter (serve/kvcache/, ``make_adapter(..., paged=True)``) —
    same families, same batcher surface, but slots hold block tables into a
    shared refcounted block pool instead of dense ``max_len`` buffers:
    prefix sharing, copy-on-write, LRU eviction, and block-granular
    admission.  The dense KVSlotAdapter remains the reference oracle the
    paged path is parity-tested against (tests/test_kvcache.py).

The batcher discovers paging hooks by presence: ``can_admit`` (queue while
the pool cannot cover a request's worst-case block demand),
``validate_request`` (reject at submit what could never fit), and
``slot_stats`` (per-request block accounting stamped onto the Request).

Both adapters mask state writes with the active-slot mask inside the
batched decode, so a freed (or never-admitted) slot keeps exactly the
state ``clear`` left it instead of decoding stale context forward between
retirement and the next admission.  ``clear`` semantics differ by adapter:
StateSlotAdapter zeroes the slot's state arrays; KVSlotAdapter resets the
slot's length to 0 — its k/v contents are stale but inert (nothing reads
past ``len``) and are fully overwritten by the next admission's padded
prefill.  Code that reads raw cache contents must consult ``len``.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm import LMConfig
from repro.serve import engine
from repro.serve.kvcache.pool import PoolExhausted
from repro.serve.obs.tracer import NO_SPAN


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 16
    eos_id: int | None = None
    generated: list = dataclasses.field(default_factory=list)
    # paged-adapter accounting, stamped at retire (0 under dense slots)
    kv_blocks: int = 0
    prefix_hit_blocks: int = 0
    # prompt tokens whose prefill was skipped via a prefix-cache resume
    prefill_tokens_skipped: int = 0
    # cross-slice migration accounting (sharded gateway, serve/shard/)
    migrations: int = 0
    migration_bytes: int = 0
    # serving SLO timestamps (virtual clock; -1 = untracked): when the
    # request left the pending queue for a slot, and when its prefill
    # produced the first token — stamped by the batcher when it has a
    # clock, copied onto the RequestRecord at completion
    t_dequeue: float = -1.0
    t_admit: float = -1.0

    @property
    def done(self) -> bool:
        if self.eos_id is not None and self.generated and \
                self.generated[-1] == self.eos_id:
            return True
        return len(self.generated) >= self.max_new_tokens


# ==========================================================================
# Adapters.
# ==========================================================================

class StateSlotAdapter:
    """State-slot engine for the rwkv family (batched decode over slots)."""

    STATE_KEYS = ("wkv", "shift1", "shift2")

    def __init__(self, cfg: LMConfig, params, n_slots: int):
        assert cfg.family == "rwkv", cfg.family
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = None                      # O(1) state: no length cap
        self.state = engine.init_cache(cfg, n_slots, 1)
        self._prefill = jax.jit(lambda p, b: engine.prefill(cfg, p, b))

        def _step(p, state, tokens, mask):
            new_cache, logits = engine.decode_step(cfg, p, state, tokens)
            masked = {"len": state["len"]}
            for key in self.STATE_KEYS:
                m = mask.reshape((1, -1) + (1,) * (new_cache[key].ndim - 2))
                masked[key] = jnp.where(m, new_cache[key], state[key])
            return masked, logits
        self._decode = jax.jit(_step)

    def insert(self, slot: int, prompt: np.ndarray,
               max_new: int | None = None) -> int:
        cache1, logits = self._prefill(
            self.params, {"tokens": jnp.asarray(prompt[None])})
        for key in self.STATE_KEYS:
            self.state[key] = self.state[key].at[:, slot].set(
                cache1[key][:, 0])
        return int(jnp.argmax(logits[0]))

    def clear(self, slot: int) -> None:
        for key in self.STATE_KEYS:
            self.state[key] = self.state[key].at[:, slot].set(0)

    def decode(self, tokens: np.ndarray, active: np.ndarray) -> np.ndarray:
        self.state, logits = self._decode(
            self.params, self.state, jnp.asarray(tokens, jnp.int32)[:, None],
            jnp.asarray(active, bool))
        return np.asarray(jnp.argmax(logits, -1))

    def jit_fns(self) -> dict[str, object]:
        """Named jitted entry points, for obs.RecompileDetector.track."""
        return {"prefill": self._prefill, "decode": self._decode}

    def cost_args(self, prompt_len: int = 8) -> dict[str, tuple]:
        """``jit_fns`` paired with representative steady-state arguments,
        for obs.costmodel roofline attribution (``fn.lower(*args)`` —
        shapes only, nothing executes)."""
        batch = {"tokens": jnp.zeros((1, prompt_len), jnp.int32)}
        tokens = jnp.zeros((self.n_slots, 1), jnp.int32)
        mask = jnp.ones((self.n_slots,), bool)
        return {"prefill": (self._prefill, (self.params, batch)),
                "decode": (self._decode,
                           (self.params, self.state, tokens, mask))}


class KVSlotAdapter:
    """KV-slot engine for attention-cache families, per-slot lengths.

    The stacked cache holds one B=1 cache per slot (leading axis =
    ``n_slots``); ``cache["len"]`` is a (n_slots,) vector.  Decode is one
    jitted vmap of :func:`engine.decode_step` — fixed shapes, one
    compilation, any mix of slot positions.
    """

    # cache keys with a sequence axis (padded to max_len); cross-attention
    # keys (xk/xv) are fixed-length and never padded.
    SEQ_KEYS = engine.PAGED_SEQ_KEYS

    def __init__(self, cfg: LMConfig, params, n_slots: int, max_len: int,
                 extras: Callable[[], dict] | None = None):
        assert cfg.family != "rwkv", "use StateSlotAdapter for rwkv"
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.extras = extras
        cache0 = engine.init_cache(cfg, 1, max_len)
        self.cache = jax.tree.map(
            lambda a: jnp.zeros((n_slots,) + a.shape, a.dtype), cache0)
        self._prefill = jax.jit(lambda p, b: engine.prefill(cfg, p, b))

        def _step(p, cache, tokens, mask):
            new_cache, logits = jax.vmap(
                lambda c, t: engine.decode_step(cfg, p, c, t),
                in_axes=(0, 0))(cache, tokens)
            sel = lambda new, old: jnp.where(
                mask.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)
            return jax.tree.map(sel, new_cache, cache), logits
        self._decode = jax.jit(_step)

    def insert(self, slot: int, prompt: np.ndarray,
               max_new: int | None = None) -> int:
        if len(prompt) > self.max_len:
            raise ValueError(f"prompt length {len(prompt)} exceeds slot "
                             f"capacity {self.max_len}")
        batch = {"tokens": jnp.asarray(prompt[None])}
        if self.extras is not None:
            batch.update(self.extras())
        cache1, logits = self._prefill(self.params, batch)
        cache1 = dict(cache1)
        for key in self.SEQ_KEYS:
            if key in cache1:
                a, ax = cache1[key], engine.seq_axis(key)
                pad = [(0, 0)] * a.ndim
                pad[ax] = (0, self.max_len - a.shape[ax])
                cache1[key] = jnp.pad(a, pad)
        self.cache = jax.tree.map(lambda sl, c1: sl.at[slot].set(c1),
                                  self.cache, cache1)
        return int(jnp.argmax(logits[0]))

    def clear(self, slot: int) -> None:
        # length 0 masks the slot: its (garbage) decodes write at pos 0 and
        # never walk the cache forward; admission overwrites everything.
        self.cache["len"] = self.cache["len"].at[slot].set(0)

    def decode(self, tokens: np.ndarray, active: np.ndarray) -> np.ndarray:
        t = jnp.asarray(tokens, jnp.int32)[:, None, None]    # (slots, 1, 1)
        self.cache, logits = self._decode(self.params, self.cache, t,
                                          jnp.asarray(active, bool))
        self.last_logits = logits[:, 0]     # (n_slots, vocab) — parity tests
        return np.asarray(jnp.argmax(logits[:, 0], -1))

    def jit_fns(self) -> dict[str, object]:
        """Named jitted entry points, for obs.RecompileDetector.track."""
        return {"prefill": self._prefill, "decode": self._decode}

    def cost_args(self, prompt_len: int = 8) -> dict[str, tuple]:
        """``jit_fns`` paired with representative steady-state arguments,
        for obs.costmodel roofline attribution (``fn.lower(*args)`` —
        shapes only, nothing executes)."""
        batch = {"tokens": jnp.zeros((1, prompt_len), jnp.int32)}
        if self.extras is not None:
            batch.update(self.extras())
        tokens = jnp.zeros((self.n_slots, 1, 1), jnp.int32)
        mask = jnp.ones((self.n_slots,), bool)
        return {"prefill": (self._prefill, (self.params, batch)),
                "decode": (self._decode,
                           (self.params, self.cache, tokens, mask))}


def make_adapter(cfg: LMConfig, params, n_slots: int, max_len: int = 128,
                 extras: Callable[[], dict] | None = None, *,
                 paged: bool = False, block_size: int = 16,
                 num_blocks: int | None = None, chunked: bool = True,
                 inplace: bool | None = None, kernel: bool | None = None,
                 mesh=None, backend: str | None = None):
    """Family dispatch: state slots for rwkv, KV slots for everything else.

    ``paged=True`` swaps the dense per-slot KV buffers for the block-pool
    adapter (``serve/kvcache/``): same batcher surface, shared-prefix blocks,
    and admission priced in blocks instead of whole slots.  ``chunked``
    (paged only) prefills via the block-size chunk fold so prefix hits skip
    recomputing the shared prompt; ``chunked=False`` keeps the one-shot
    prefill with storage-only sharing.  ``backend`` (paged only) picks the
    decode tick's attention dataflow — ``"gather"`` (the PR 2
    gather->decode->scatter parity oracle), ``"xla"`` (in-place tick, XLA
    reference read), ``"pallas"`` (in-place tick, Pallas paged-attention
    kernel), ``"cascade"`` (in-place tick with shared-prefix cascade
    grouping); None probes the platform (``serve.backend.auto_backend``).
    The old ``inplace``/``kernel`` booleans are deprecated aliases mapped
    by ``serve.backend.resolve_backend`` (with a ``DeprecationWarning``).
    ``mesh`` (paged only) commits the adapter's arena/params to a
    serving-mesh slice with ``engine.arena_specs`` placement — the
    sharded-serving entry point (serve/shard/; a single-device slice stays
    bitwise-identical to the unsharded adapter).  rwkv has O(1) state, so
    ``paged`` is a no-op for it.
    """
    if mesh is not None and (not paged or cfg.family == "rwkv"):
        # silently returning an unplaced adapter would defeat the sharding
        # without any signal — only the paged attention families commit
        # their state to a mesh slice
        raise ValueError("mesh placement requires paged=True and a "
                         f"non-rwkv family (got paged={paged}, "
                         f"family={cfg.family})")
    if cfg.family == "rwkv":
        return StateSlotAdapter(cfg, params, n_slots)
    if paged:
        from repro.serve.kvcache import PagedKVSlotAdapter
        return PagedKVSlotAdapter(cfg, params, n_slots, max_len,
                                  block_size=block_size,
                                  num_blocks=num_blocks, extras=extras,
                                  chunked=chunked, inplace=inplace,
                                  kernel=kernel, mesh=mesh, backend=backend)
    return KVSlotAdapter(cfg, params, n_slots, max_len, extras)


# ==========================================================================
# The scheduler loop (family-agnostic).
# ==========================================================================

class ContinuousBatcher:
    """vLLM-style continuous batching over any slot adapter.

    Flow per step():
      1. admit: for each free slot, pop a pending request, prefill (B=1) and
         scatter its context into the slot; a request whose prefill token
         already finishes it (EOS or a 1-token budget) retires immediately
         without occupying the slot;
      2. decode: one batched decode over all slots;
      3. retire: finished requests free their slot and the adapter zeroes
         the slot's state so it cannot keep evolving between admissions.
    """

    def __init__(self, adapter):
        self.adapter = adapter
        self.n_slots = adapter.n_slots
        self.pending: deque[Request] = deque()
        self.active: list[Request | None] = [None] * self.n_slots
        self.last_token = np.zeros((self.n_slots,), np.int32)
        self.peak_active = 0            # max concurrent slots ever decoded
        self.last_active = 0            # slots decoding in the latest step
        # observability hooks (serve/obs/), wired by the prompt gateways
        # for the duration of a run; all None by default and every use is
        # guarded, so a bare batcher makes zero obs calls
        self.clock = None               # SimClock for t_dequeue/t_admit
        self.tracer = None              # span recorder
        self.trace_pid = 1              # engine track (1 + slice_idx)

    def submit(self, req: Request):
        if self.adapter.max_len is not None and \
                len(req.prompt) + req.max_new_tokens > self.adapter.max_len:
            raise ValueError(
                f"request {req.uid}: prompt {len(req.prompt)} + "
                f"{req.max_new_tokens} new tokens exceeds slot capacity "
                f"{self.adapter.max_len}")
        validate = getattr(self.adapter, "validate_request", None)
        if validate is not None:        # paged: whole-pool capacity bound
            validate(len(req.prompt), req.max_new_tokens)
        self.pending.append(req)
        tr = self.tracer
        if tr is not None and tr.innermost(tid=req.uid) is None:
            # driven directly (no gateway loop opened the lifecycle at
            # arrival): the wait runs from submit
            tr.begin("request", tid=req.uid)
            tr.begin("queue_wait", tid=req.uid)

    def _admissible(self, req: Request) -> bool:
        can = getattr(self.adapter, "can_admit", None)
        return can is None or can(req.prompt, req.max_new_tokens)

    def _stamp_stats(self, slot: int, req: Request) -> None:
        stats = getattr(self.adapter, "slot_stats", None)
        if stats is not None:
            st = stats(slot)
            req.kv_blocks = st.get("kv_blocks", 0)
            req.prefix_hit_blocks = st.get("prefix_hit_blocks", 0)
            req.prefill_tokens_skipped = st.get("prefill_tokens_skipped", 0)

    @property
    def busy(self) -> bool:
        return bool(self.pending) or any(r is not None for r in self.active)

    def debug_state(self) -> dict:
        """Occupancy snapshot for incident bundles (serve/obs/incident.py):
        queue depth and per-slot uids only — never prompt or token payloads,
        so a bundle can leave the machine."""
        return {
            "n_slots": self.n_slots,
            "pending": len(self.pending),
            "pending_uids": [r.uid for r in list(self.pending)[:16]],
            "active_uids": [None if r is None else r.uid
                            for r in self.active],
            "last_active": self.last_active,
            "peak_active": self.peak_active,
        }

    def _now(self) -> float:
        """Virtual time for SLO stamps: the tracer's (possibly
        wall-interpolated) clock when tracing, the bare clock when only SLO
        stamping is on, -1 (untracked) for a bare batcher."""
        if self.tracer is not None:
            return self.tracer.now()
        if self.clock is not None:
            return self.clock.t
        return -1.0

    def _retire_trace(self, req: Request, reason: str) -> None:
        # the guard heals requests that went active before the tracer was
        # wired (no decode span to close)
        if self.tracer is not None and \
                self.tracer.innermost(tid=req.uid) == "decode":
            self.tracer.end("decode", tid=req.uid,
                            args={"tokens": len(req.generated),
                                  "retire": reason})

    def step(self, decode: bool = True) -> list[Request]:
        """Admit + one decode tick.  Returns requests completed this tick.

        ``decode=False`` is the prefill-role mode of the disaggregated
        gateway (serve/shard/): admit pending requests (chunked prefill)
        and retire at-capacity / EOS-at-prefill lanes, but skip the
        batched decode — admitted lanes keep their prefill token staged in
        ``last_token`` and wait for the router to hand them off to a
        decode slice.  The default path is untouched.

        With a tracer attached the step is a ``tick`` span on the engine
        track, holding ``admit``, the adapter's ``tick.prepare`` /
        ``tick.dispatch`` / ``tick.sample`` and ``retire``."""
        tr = self.tracer
        if tr is None:
            return self._step(decode, None)
        with tr.span("tick", pid=self.trace_pid, tid=0) as sp:
            return self._step(decode, sp)

    def _step(self, decode: bool, sp) -> list[Request]:
        tr = self.tracer
        finished: list[Request] = []
        with (tr.span("admit", pid=self.trace_pid, tid=0) if tr is not None
              else NO_SPAN) as admit:
            admitted, prompt_tokens = self._admit(finished)
            if admit is not None:
                admit.set(admitted=admitted, prompt_tokens=prompt_tokens)
        # a slot whose context filled every KV block cannot take another
        # token — surface it as finished instead of letting its next write
        # be silently clamped onto the final (possibly shared) block
        cap = getattr(self.adapter, "at_capacity", None)
        if cap is not None:
            for slot, req in enumerate(self.active):
                if req is not None and cap(slot):
                    self._stamp_stats(slot, req)
                    self._retire_trace(req, "at_capacity")
                    finished.append(req)
                    self.active[slot] = None
                    self.adapter.clear(slot)
                    self.last_token[slot] = 0
        active = np.asarray([r is not None for r in self.active])
        self.last_active = int(active.sum())
        self.peak_active = max(self.peak_active, self.last_active)
        if not active.any():
            if sp is not None:
                sp.set(active=0, finished=len(finished))
            return finished
        if not decode:
            if sp is not None:
                sp.set(active=self.last_active, finished=len(finished),
                       decode=False)
            return finished
        if tr is not None:
            # the adapter's tick.* spans land on this engine track; the
            # request tracks are the default lane again after it
            tr.set_ctx(0, pid=self.trace_pid)
        toks = self.adapter.decode(self.last_token, active)
        if tr is not None:
            tr.set_ctx(0)
        with (tr.span("retire", pid=self.trace_pid, tid=0) if tr is not None
              else NO_SPAN):
            for slot, req in enumerate(self.active):
                if req is None:
                    continue
                tok = int(toks[slot])
                req.generated.append(tok)
                self.last_token[slot] = tok
                if req.done:
                    self._stamp_stats(slot, req)
                    self._retire_trace(req, "done")
                    finished.append(req)
                    self.active[slot] = None
                    self.adapter.clear(slot)
                    self.last_token[slot] = 0
        if sp is not None:
            sp.set(active=self.last_active, finished=len(finished),
                   **getattr(self.adapter, "last_work", {}))
        return finished

    def _admit(self, finished: list[Request]) -> tuple[int, int]:
        """Fill free slots from the queue (FIFO; the head that cannot be
        admitted stops admission).  Requests whose prefill token already
        finishes them join ``finished``.  Returns the requests prefilled
        and their prompt tokens."""
        tr = self.tracer
        admitted = prompt_tokens = 0
        stalled = False                 # FIFO: head can't admit -> stop
        for slot in range(self.n_slots):
            while self.active[slot] is None and self.pending and not stalled:
                if not self._admissible(self.pending[0]):
                    stalled = True      # blocks free up as requests retire
                    break
                req = self.pending.popleft()
                req.t_dequeue = self._now()
                if tr is not None:
                    if tr.innermost(tid=req.uid) != "queue_wait":
                        # submitted before the tracer was wired (pre-run
                        # queueing): open the lifecycle late so the rest
                        # of it is traced
                        tr.begin("request", tid=req.uid,
                                 args={"late_open": True})
                        tr.begin("queue_wait", tid=req.uid)
                    tr.end("queue_wait", tid=req.uid)
                    # chunk spans from the paged adapter's fold land on
                    # this request's track without threading uids through
                    tr.set_ctx(req.uid)
                with (tr.span("prefill", tid=req.uid,
                              prompt_len=len(req.prompt))
                      if tr is not None else NO_SPAN) as pre:
                    try:
                        tok = self.adapter.insert(
                            slot, np.asarray(req.prompt, np.int32),
                            max_new=req.max_new_tokens)
                    except PoolExhausted:
                        # insert rolled its allocations back; requeue at
                        # the head and let retirements free blocks
                        # (can_admit makes this unreachable, but admission
                        # must degrade to queueing, never to a crashed
                        # serving loop)
                        tok = None
                        if pre is not None:
                            pre.set(admitted=False)
                    else:
                        req.t_admit = self._now()
                        if pre is not None:
                            pre.set(slot=slot)
                if tok is None:
                    self.pending.appendleft(req)
                    stalled = True
                    if tr is not None:
                        tr.begin("queue_wait", tid=req.uid)
                    break
                admitted += 1
                prompt_tokens += len(req.prompt)
                req.generated.append(tok)
                if req.done:            # EOS fired on the prefill token
                    self._stamp_stats(slot, req)
                    self.adapter.clear(slot)
                    finished.append(req)
                    continue
                if tr is not None:
                    tr.begin("decode", tid=req.uid)
                self.active[slot] = req
                self.last_token[slot] = tok
        return admitted, prompt_tokens

    def run(self) -> list[Request]:
        """Drain the queue; returns all completed requests."""
        done: list[Request] = []
        while self.busy:
            done.extend(self.step())
        return done
