"""ServingEngine: continuous-batching request scheduler over a PUDSession.

The calibrated array only pays off when it is kept saturated: PUDTune's
extra error-free columns buy 1.81x more parallel MACs per wave, but a
serve loop that decodes one request at a time leaves them idle between
requests.  This engine turns the single-vector decode path into a
multi-request system:

  * **Requests** enter a queue (``submit``); each is one prompt plus a
    token budget and an optional latency deadline.
  * **Slots** — the engine owns a fixed-size padded batch of ``batch_size``
    decode slots and one KV-cache pytree sized ``[L, batch_size, max_len,
    ...]``; every slot holds at most one in-flight request.
  * **Continuous batching** — admission and eviction happen at *step*
    granularity: before every decode step, free slots are filled from the
    queue; after it, finished requests are evicted and their slots freed
    immediately — no waiting for the whole batch to drain.
  * **Bucketed prefill** — prompt lengths are padded to the next power of
    two before the jitted prefill (zero-pad at the tail; the causal mask
    keeps pads invisible to real positions and the logits row is read at
    the true last token), so 20 ragged prompts compile O(log max_len)
    prefill variants instead of 20.
  * **Chunked prefill** (``chunk_prefill=N``) — prefill is split into
    fixed-size chunks that interleave with decode waves: a slot spends
    several steps in the *prefilling* phase (one chunk per step, resumed
    into a private KV cache via ``model.prefill_chunk``) before its first
    token, so one long prompt no longer stalls every decode slot in the
    batch.  Chunked slots finish bit-identically to whole-request prefill
    (chunk rows see exactly the same kv rows/mask-tail as the whole pass).
  * **Prefix cache** (``prefix_cache=True``) — completed prefills are
    stored in an LRU keyed on (params version, prompt-token hash); a
    repeated prompt skips prefill entirely (full hit) and a repeated
    system prompt resumes chunked prefill after the shared prefix
    (partial hit).  The cache is invalidated on every ``stage_params``
    hot swap, so a stale prefix after drift recalibration is impossible.
  * **SLO-aware admission** (``slo=``) — admission is priced by the
    placement perf model (``FleetPerfModel.step_seconds``): requests
    admit in earliest-deadline-first order, hopeless ones shed at
    admission, and expired in-flight ones shed mid-decode; completions
    carry ``slo_met``.
  * **Per-slot positions** — one jitted decode step serves all slots at
    once with a [B] vector of cache lengths (models/attention.py's
    per-slot decode path), so requests admitted at different times decode
    correctly side by side with no host-side Python loop over slots.

Bit-exactness: every per-slot computation (per-row activation quantization,
the integer bit-plane kernel, per-row attention masks, rmsnorm) is
independent of the other batch lanes, so the tokens a request gets from a
batched engine are bit-identical to running it alone — enforced across
backends, layouts and scheduling modes by tests/test_engine.py and
tests/test_chunked_prefill.py.  MoE models are the exception (router
capacity is sequence-global): they keep the legacy exact-length
whole-prompt prefill (``model.supports_chunked_prefill``).

Batch-size selection: with a calibrated + placed ``PUDSession``, the
default ``batch_size`` comes from the placement-derived ``FleetPerfModel``
(``optimal_batch_size`` — weight replicas x operand residency), the point
up to which the DRAM-side aggregate tokens/s grows monotonically.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .prefix_cache import PrefixCache
from .tracing import span
from .watchdog import StepWatchdog

DEFAULT_MAX_BATCH = 32

#: Fallback modeled decode-step wall time when no perf model is available
#: (SLO virtual clock only; never used for measurement).
DEFAULT_STEP_MS = 5.0

#: run() raises after this many consecutive steps with queued/active work
#: but zero progress (a prefill_budget smaller than the chunk size is the
#: one configuration that can starve forever).
_STALL_LIMIT = 8


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


@dataclasses.dataclass
class Request:
    """One generation request: a prompt, a token budget, and optionally a
    latency deadline (milliseconds from submit, on the engine's modeled
    clock) for SLO-aware admission."""

    request_id: int
    tokens: Any                   # [S] int prompt tokens (array-like)
    max_new_tokens: int
    deadline_ms: float | None = None

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.tokens).shape[-1])


@dataclasses.dataclass
class Completion:
    """A finished request: generated tokens plus scheduling metadata.

    The ``*_t`` fields are ``time.perf_counter()`` readings: ``submit_t`` at
    ``submit``, ``prefill_t`` when its own prefill began (its admission, on
    a full prefix-cache hit; None when shed from the queue), ``token_t[k]``
    when token ``k`` was read back to the host.
    """

    request_id: int
    tokens: list[int]             # generated tokens (length = max_new_tokens)
    slot: int
    admitted_step: int            # engine step index at admission
    finished_step: int            # engine step index after the last token
    logits: np.ndarray | None = None   # [gen, V] when collect_logits
    slo_met: bool | None = None   # None when the request had no deadline
    shed: bool = False            # dropped by the SLO policy / shed_request
    submit_t: float | None = None
    prefill_t: float | None = None
    token_t: list[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class SLOConfig:
    """Admission policy knobs for deadline-carrying requests.

    ``step_time_ms`` overrides the modeled per-step wall time (the virtual
    clock the policy prices admission with); by default it comes from the
    session's placement perf model (``step_seconds`` at the engine batch
    size) and falls back to ``DEFAULT_STEP_MS``.  A deterministic modeled
    clock keeps the policy reproducible in tests and independent of host
    jitter.
    """

    default_deadline_ms: float | None = None  # applied when a request has none
    step_time_ms: float | None = None         # virtual-clock override
    shed_on_admit: bool = True                # shed hopeless requests at admit
    shed_admitted: bool = True                # evict expired in-flight requests


@dataclasses.dataclass
class _PrefillState:
    """Per-slot chunked-prefill progress (phase == "prefill")."""

    tokens: np.ndarray            # [bucket] prompt zero-padded to its bucket
    prompt_len: int
    bucket: int                   # pow2 prefill length (kv rows, static)
    chunk: int                    # chunk length (divides bucket)
    pos: int                      # positions < pos are already in the cache
    cache: Any                    # private batch-1 KV pytree [L,1,max_len,..]


@dataclasses.dataclass
class _Slot:
    request: Request
    admitted_step: int
    generated: list[int]
    logits: list[np.ndarray]
    phase: str = "decode"         # "prefill" | "decode"
    pf: _PrefillState | None = None
    deadline_vms: float | None = None   # virtual-clock deadline
    prefill_t: float | None = None      # perf_counter at its prefill's start
    token_t: list[float] = dataclasses.field(default_factory=list)


def _replicated_like(params) -> NamedSharding | None:
    """Replicated over the mesh ``params`` are placed on, or None for
    params on one device."""
    leaves = jax.tree.leaves(params)
    sh = getattr(leaves[0], "sharding", None) if leaves else None
    if isinstance(sh, NamedSharding):
        return NamedSharding(sh.mesh, PartitionSpec())
    return None


class ServingEngine:
    """Continuous-batching decode engine for one model + packed params.

    ``params`` is the serving tree (``PackedModel.params`` for the PUD path
    or a raw bf16 tree); ``session`` is the ``PUDSession`` whose packed
    model is being served — it contributes the default batch size (from
    placement occupancy) and the DRAM-side rate model for ``perf_report``
    and SLO pricing.  The engine itself is execution-agnostic: the
    PUD-vs-bf16 choice already happened at pack time.

    The model must expose ``prefill(params, tokens, max_len=)`` and a
    ``decode_step(params, cache, tokens, cur_len)`` that accepts a [B]
    vector ``cur_len`` (transformer-family models; see models/attention).
    Bucketed and chunked prefill additionally require
    ``supports_chunked_prefill`` / ``prefill_chunk`` / ``cache_defs``
    (TransformerLM); models without them keep the legacy exact-length
    whole-prompt prefill.

    Scheduler extensions (all off by default — the default configuration
    behaves exactly like the step-granular FIFO engine):

    ``chunk_prefill``     chunk length in tokens (rounded up to a power of
                          two); prompts prefill one chunk per step,
                          interleaved with decode waves.
    ``prefill_budget``    max prefill tokens per step across slots (None =
                          one chunk per prefilling slot per step).
    ``prefix_cache``      True (build a default ``PrefixCache``) or a
                          configured instance; reuses completed prefills.
    ``slo``               ``SLOConfig``, or a float shorthand for
                          ``SLOConfig(default_deadline_ms=...)``; enables
                          EDF admission + shedding.

    Params placed over a device mesh (a fleet lane's) get KV caches made
    replicated over the same mesh, so every program of the lane compiles
    as one SPMD program.

    Tracing: the engine opens ``repro.engine.*`` spans (``runtime/tracing``)
    around each step, prefill, insert, decode dispatch and decode readback;
    under ``jax.profiler`` they land in the trace, and their seconds and
    counts are always summed into ``scheduler_report()["span_s"]`` /
    ``["span_n"]``.  ``lane`` (set by ``FleetServingEngine``) tags them.
    """

    def __init__(self, model, params, *, max_len: int,
                 session=None, batch_size: int | None = None,
                 max_batch: int = DEFAULT_MAX_BATCH,
                 collect_logits: bool = False,
                 watchdog: StepWatchdog | None = None,
                 heartbeat=None,
                 chunk_prefill: int | None = None,
                 prefill_budget: int | None = None,
                 prefix_cache: bool | PrefixCache = False,
                 slo: SLOConfig | float | None = None):
        if batch_size is None:
            batch_size = self._default_batch_size(session, max_batch)
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.model = model
        self.params = params
        self.session = session
        self.batch_size = int(batch_size)
        self.max_len = int(max_len)
        self.collect_logits = collect_logits

        # Bucketed/chunked prefill require chunk-resumable models; MoE
        # configs (sequence-global router capacity) and models without the
        # protocol stay on the legacy exact-length path.
        self._bucketed = bool(getattr(model, "supports_chunked_prefill",
                                      False))
        if chunk_prefill is not None:
            if not self._bucketed:
                raise ValueError(
                    "chunk_prefill requires a model with bit-exact chunked "
                    "prefill (supports_chunked_prefill); MoE routing is "
                    "sequence-global")
            if chunk_prefill < 1:
                raise ValueError(
                    f"chunk_prefill must be >= 1, got {chunk_prefill}")
            chunk_prefill = min(_next_pow2(int(chunk_prefill)), self.max_len)
        self.chunk_prefill = chunk_prefill
        self.prefill_budget = prefill_budget
        if prefix_cache is True:
            prefix_cache = PrefixCache()
        elif prefix_cache is False:
            prefix_cache = None
        self._prefix_cache = prefix_cache
        if isinstance(slo, (int, float)):
            slo = SLOConfig(default_deadline_ms=float(slo))
        self._slo = slo

        self._queue: collections.deque[Request] = collections.deque()
        self._slots: list[_Slot | None] = [None] * self.batch_size
        self._cache = None                       # allocated on first admit
        self._cache_sharding = _replicated_like(params)
        # host-side slot state, shipped to the device once per step
        self._tokens = np.zeros((self.batch_size, 1), np.int32)
        self._lens = np.zeros((self.batch_size,), np.int32)
        self._completions: list[Completion] = []
        self._step_idx = 0
        self._active_slot_steps = 0              # sum of live slots per step
        self._submit_t: dict[int, float] = {}    # request id -> perf_counter
        self._span_totals: dict[str, list] = {}  # span name -> [seconds, n]
        self.lane: int | None = None             # fleet lane, tags spans

        # SLO virtual clock: deterministic modeled milliseconds, advanced by
        # one modeled step time per scheduling step.
        self._vtime_ms = 0.0
        self._step_ms = self._resolve_step_ms()
        self._deadlines: dict[int, float | None] = {}
        self._slo_stats = {"shed_on_admit": 0, "shed_admitted": 0,
                           "met": 0, "missed": 0}
        self._last_step_worked = False

        # Params identity for prefix-cache keys: bumped on every hot swap,
        # so entries computed under a pre-recalibration pack can never be
        # served afterwards (the swap also drops them outright).
        self._params_version = 0
        self._prefix_invalidated_entries = 0

        # jit trace counters (incremented inside the traced bodies, so they
        # tick once per compiled variant, not once per call)
        self._prefill_traces = 0
        self._chunk_traces = 0
        self._prefill_chunks = 0                 # chunk calls executed
        self._prefilled_tokens = 0               # kv rows actually computed
        self._prompt_tokens = 0                  # real prompt tokens among them
        self._prefill_calls = 0                  # whole-prefill + chunk calls

        # Step telemetry: every decode step is bracketed by a StepWatchdog
        # (EMA step time, straggler flags, optional hang callback) and
        # optionally announced through a Heartbeat for fleet-level liveness.
        # The default watchdog has no on_hang, so no monitor thread spawns.
        self.watchdog = watchdog if watchdog is not None else StepWatchdog()
        self.heartbeat = heartbeat
        self._hangs = 0
        user_hang = self.watchdog.on_hang
        if user_hang is not None:
            def _counted_hang(waited_s, _cb=user_hang):
                self._hangs += 1
                _cb(waited_s)
            self.watchdog.on_hang = _counted_hang

        # Double-buffered serving tree: ``stage_params`` parks a freshly
        # packed tree here and the NEXT ``step()`` swaps it in at the step
        # boundary, so decode never observes a half-replaced pack and no
        # request stalls (the params argument of the jitted step is not
        # donated — only the KV cache is — so the old tree stays valid
        # through the step that builds its replacement).
        self._staged_params = None
        self._swap_steps: list[int] = []

        # The cache argument is donated: the engine owns the single
        # [L, B, max_len, ...] KV pytree and rebinds it after every call,
        # so XLA updates it in place instead of copying it per token.  The
        # chunk step likewise donates the slot's private prefill cache.
        self._prefill = jax.jit(self._prefill_fn, static_argnames=("s",))
        self._prefill_bucketed = jax.jit(self._prefill_bucketed_fn,
                                         static_argnames=("sb",))
        self._chunk = jax.jit(self._chunk_fn,
                              static_argnames=("c", "kv_len"),
                              donate_argnums=(1,))
        self._insert = jax.jit(self._insert_fn, donate_argnums=(0,))
        self._step = jax.jit(self._step_fn, donate_argnums=(1,))

    # -- construction helpers ------------------------------------------------

    @staticmethod
    def _default_batch_size(session, max_batch: int) -> int:
        """Occupancy-derived slots: the placement perf model's optimum."""
        if session is not None:
            pm = session.placement_perf_model() or session.tuned_perf_model()
            if hasattr(pm, "optimal_batch_size"):
                return max(1, pm.optimal_batch_size(max_batch))
        return max(1, min(4, max_batch))

    def _resolve_step_ms(self) -> float:
        """Modeled decode-step milliseconds for the SLO virtual clock."""
        if self._slo is not None and self._slo.step_time_ms is not None:
            return float(self._slo.step_time_ms)
        if self.session is not None:
            pm = (self.session.placement_perf_model()
                  or self.session.tuned_perf_model())
            if pm is not None and hasattr(pm, "step_seconds"):
                try:
                    fpt = self.session.flops_per_token()
                except Exception:
                    fpt = None
                if fpt:
                    return pm.step_seconds(fpt, self.batch_size) * 1e3
        return DEFAULT_STEP_MS

    def _span(self, name: str, **attrs) -> span:
        if self.lane is not None:
            attrs["lane"] = self.lane
        return span(name, self._span_totals, **attrs)

    def _bucket(self, s: int) -> int:
        """pow2 prompt-length bucket, clamped to the cache length."""
        return min(self.max_len, _next_pow2(max(1, s)))

    # -- jitted inner functions ---------------------------------------------

    def _prefill_fn(self, params, tokens, s):
        del s  # static: distinct prompt lengths trace separately
        self._prefill_traces += 1      # python side effect: trace-time only
        logits, cache = self.model.prefill(params, tokens,
                                           max_len=self.max_len)
        return logits, cache

    def _prefill_bucketed_fn(self, params, tokens, last, sb):
        """Whole prefill over a pow2-padded prompt; logits read at the
        traced true-last-token row, so every length in a bucket shares one
        compiled variant."""
        del sb  # static: one trace per bucket (shape already implies it)
        self._prefill_traces += 1      # python side effect: trace-time only
        logits, cache = self.model.prefill(params, tokens,
                                           max_len=self.max_len,
                                           last_idx=last)
        return logits, cache

    def _chunk_fn(self, params, cache, tokens, start, last, c, kv_len):
        del c  # static chunk length (tokens carries the shape)
        self._chunk_traces += 1        # python side effect: trace-time only
        logits, cache = self.model.prefill_chunk(
            params, tokens, cache, start, kv_len=kv_len, last_idx=last)
        return logits, cache

    def _insert_fn(self, cache, new_cache, slot):
        """Scatter a batch-1 prefill cache into batch lane ``slot``.

        Cache leaves are [L, B, max_len, ...] (batch axis 1).
        """
        return jax.tree.map(
            lambda c, n: jax.lax.dynamic_update_slice_in_dim(
                c, n.astype(c.dtype), slot, axis=1),
            cache, new_cache)

    def _step_fn(self, params, cache, tokens, lens):
        logits, cache = self.model.decode_step(params, cache, tokens, lens)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt[:, None], logits, cache

    # -- queue / scheduler ---------------------------------------------------

    def submit(self, request: Request) -> None:
        if request.prompt_len + request.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {request.request_id}: prompt_len "
                f"{request.prompt_len} + max_new_tokens "
                f"{request.max_new_tokens} exceeds max_len {self.max_len}")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        deadline = request.deadline_ms
        if deadline is None and self._slo is not None:
            deadline = self._slo.default_deadline_ms
        self._deadlines[request.request_id] = (
            None if deadline is None else self._vtime_ms + float(deadline))
        self._submit_t[request.request_id] = time.perf_counter()
        self._queue.append(request)

    def submit_all(self, requests) -> None:
        for r in requests:
            self.submit(r)

    @property
    def n_pending(self) -> int:
        return len(self._queue)

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    @property
    def prefill_trace_count(self) -> int:
        """Compiled prefill variants (whole buckets + chunk shapes)."""
        return self._prefill_traces + self._chunk_traces

    def _zero_cache_like(self, cache1):
        """Full-batch cache pytree from a batch-1 prefill cache."""
        b = self.batch_size
        return jax.tree.map(
            lambda c: jnp.zeros(c.shape[:1] + (b,) + c.shape[2:], c.dtype,
                                device=self._cache_sharding), cache1)

    def _zero_cache1(self):
        """Fresh batch-1 KV pytree for a chunked-prefill slot."""
        defs = self.model.cache_defs(1, self.max_len)
        return jax.tree.map(lambda d: jnp.zeros(
            d.shape, d.dtype, device=self._cache_sharding), defs)

    @staticmethod
    def _trim_cache1(cache1, length: int):
        """First ``length`` seq rows of a batch-1 cache (leaf axis 2)."""
        return jax.tree.map(lambda c: c[:, :, :length], cache1)

    def _pad_cache1(self, cache1):
        """Zero-pad a trimmed batch-1 cache back to ``max_len`` seq rows."""
        def pad(c):
            w = [(0, 0)] * c.ndim
            w[2] = (0, self.max_len - c.shape[2])
            return jnp.pad(c, w)
        return jax.tree.map(pad, cache1)

    # -- prefix cache --------------------------------------------------------

    def _candidate_lengths(self, s: int) -> list[int]:
        """Reusable prefix lengths for a prompt of ``s`` tokens: the whole
        prompt, then chunk-aligned proper prefixes, longest first (partial
        reuse requires the chunk path to resume the suffix)."""
        lengths = [s]
        if self.chunk_prefill is not None:
            c = self.chunk_prefill
            lengths += [k for k in range((s - 1) // c * c, 0, -c)]
        return lengths

    def prefix_probe(self, tokens) -> int:
        """Longest cached prefix covering ``tokens`` (0 without a cache).

        Non-mutating — ``FleetServingEngine`` uses it to pick a lane by
        cache affinity before falling back to round-robin.
        """
        if self._prefix_cache is None:
            return 0
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        return self._prefix_cache.probe(
            self._params_version, tokens,
            self._candidate_lengths(int(tokens.shape[0])))

    def _prefix_insert(self, tokens_np: np.ndarray, cache1, logits_row):
        """Store the completed prefill: the full prompt (with its logits
        row) plus every chunk-aligned proper prefix, all trimmed to exact
        token counts so bucket-padding garbage can never be reused."""
        if self._prefix_cache is None:
            return
        s = int(tokens_np.shape[0])
        v = self._params_version
        self._prefix_cache.insert(v, tokens_np, self._trim_cache1(cache1, s),
                                  logits_row)
        if self.chunk_prefill is not None:
            for length in range((s - 1) // self.chunk_prefill
                                * self.chunk_prefill, 0,
                                -self.chunk_prefill):
                self._prefix_cache.insert(
                    v, tokens_np[:length],
                    self._trim_cache1(cache1, length), None)

    # -- admission -----------------------------------------------------------

    def _estimate_steps(self, req: Request, resume_from: int = 0) -> int:
        """Modeled scheduling steps to finish ``req`` from admission."""
        if self.chunk_prefill is not None:
            remaining = max(0, req.prompt_len - resume_from)
            prefill_steps = -(-remaining // self.chunk_prefill)
        else:
            prefill_steps = 1
        return prefill_steps + req.max_new_tokens - 1

    def _shed_completion(self, req: Request, slot: int = -1,
                         generated=None, logits=None) -> None:
        self._completions.append(Completion(
            request_id=req.request_id,
            tokens=list(generated or []),
            slot=slot,
            admitted_step=self._step_idx,
            finished_step=self._step_idx,
            logits=logits,
            slo_met=False,
            shed=True,
            submit_t=self._submit_t.pop(req.request_id, None)))
        self._slo_stats["missed"] += 1

    def _admission_order(self) -> list[Request]:
        """Queue in admission order: FIFO, or earliest-deadline-first with
        a stable FIFO tie-break when the SLO policy is on (no-deadline
        requests sort last — they are the ones being *held* while tighter
        deadlines jump ahead)."""
        q = list(self._queue)
        if self._slo is None:
            return q
        def key(pair):
            i, r = pair
            d = self._deadlines.get(r.request_id)
            return (d if d is not None else float("inf"), i)
        return [r for _, r in sorted(enumerate(q), key=key)]

    def _admit(self) -> int:
        """Fill free slots from the queue. Returns #admitted.

        Per candidate (in admission order): shed if its deadline is
        already unreachable under the modeled step time (``SLOConfig.
        shed_on_admit``), reuse a cached prefix when one covers the
        prompt, otherwise prefill — whole-bucket immediately, or chunked
        across the following steps when ``chunk_prefill`` is set.
        """
        free = self.free_slots
        if not free or not self._queue:
            return 0
        candidates = self._admission_order()
        taken: list[Request] = []      # leaving the queue: admitted or shed
        admitted = 0
        ci = 0
        for slot in free:
            while ci < len(candidates):
                req = candidates[ci]
                ci += 1
                taken.append(req)
                if self._slo is not None and self._slo.shed_on_admit:
                    deadline = self._deadlines.get(req.request_id)
                    resume = self._probe_resume_point(req)
                    eta = (self._vtime_ms
                           + self._estimate_steps(req, resume) * self._step_ms)
                    if deadline is not None and eta > deadline:
                        self._slo_stats["shed_on_admit"] += 1
                        self._shed_completion(req)
                        continue
                self._admit_slot(slot, req)
                admitted += 1
                break
        if taken:
            # identity-based removal: Request holds array prompts, so the
            # dataclass __eq__ deque.remove would use is unsafe
            taken_ids = {id(r) for r in taken}
            self._queue = collections.deque(
                r for r in self._queue if id(r) not in taken_ids)
        return admitted

    def _probe_resume_point(self, req: Request) -> int:
        if self._prefix_cache is None:
            return 0
        return self.prefix_probe(np.asarray(req.tokens, np.int32))

    def _admit_slot(self, slot: int, req: Request) -> None:
        tokens_np = np.ascontiguousarray(
            np.asarray(req.tokens, np.int32).reshape(-1))
        s = req.prompt_len
        entry = None
        if self._prefix_cache is not None:
            entry = self._prefix_cache.lookup(
                self._params_version, tokens_np, self._candidate_lengths(s))

        if entry is not None and entry.n_tokens == s and \
                entry.logits is not None:
            # full hit: the stored cache + logits replace prefill outright
            t = time.perf_counter()
            cache1 = self._pad_cache1(entry.cache)
            self._start_decode(slot, req, cache1,
                               np.asarray(entry.logits).reshape(-1), t, t)
            return

        if self.chunk_prefill is not None:
            # chunked path: enter the prefilling phase; a partial hit seeds
            # the private cache and resumes after the shared prefix
            sb = self._bucket(s)
            chunk = min(self.chunk_prefill, sb)
            padded = np.zeros((sb,), np.int32)
            padded[:s] = tokens_np
            resume = 0
            cache1 = self._zero_cache1()
            if entry is not None and entry.n_tokens < s:
                resume = entry.n_tokens
                cache1 = self._pad_cache1(entry.cache)
            st = _Slot(request=req, admitted_step=self._step_idx,
                       generated=[], logits=[], phase="prefill",
                       pf=_PrefillState(tokens=padded, prompt_len=s,
                                        bucket=sb, chunk=chunk, pos=resume,
                                        cache=cache1),
                       deadline_vms=self._deadlines.get(req.request_id))
            self._slots[slot] = st
            return

        # whole prefill: pow2-bucketed for chunk-capable models, legacy
        # exact-length otherwise (MoE / non-transformer protocols)
        sb = self._bucket(s) if self._bucketed else s
        with self._span("engine.prefill", request=req.request_id, prompt=s,
                        bucket=sb) as sp:
            if self._bucketed:
                padded = np.zeros((1, sb), np.int32)
                padded[0, :s] = tokens_np
                logits, cache1 = self._prefill_bucketed(
                    self.params, jnp.asarray(padded),
                    jnp.asarray(s - 1, jnp.int32), sb)
            else:
                tokens = jnp.asarray(tokens_np)[None, :]
                logits, cache1 = self._prefill(self.params, tokens,
                                               tokens.shape[1])
            logits_row = np.asarray(logits[0])
        self._prefilled_tokens += sb
        self._prompt_tokens += s
        self._prefill_calls += 1
        self._prefix_insert(tokens_np, cache1, logits_row)
        self._start_decode(slot, req, cache1, logits_row, sp.start, sp.end)

    def _start_decode(self, slot: int, req: Request, cache1,
                      logits_row: np.ndarray, prefill_t: float,
                      token_t: float) -> None:
        """Install a completed prefill into a batch lane and begin decode.

        ``prefill_t``: when this request's prefill began (kept if its slot
        already has one, from its first chunk); ``token_t``: when the
        logits row of its first token reached the host."""
        if self._cache is None:
            self._cache = self._zero_cache_like(cache1)
        with self._span("engine.insert", request=req.request_id, slot=slot):
            self._cache = self._insert(self._cache, cache1, slot)
        first = int(np.argmax(logits_row))
        st = self._slots[slot]
        if st is None:                 # whole-prefill / full-hit admission
            st = _Slot(request=req, admitted_step=self._step_idx,
                       generated=[], logits=[],
                       deadline_vms=self._deadlines.get(req.request_id))
            self._slots[slot] = st
        st.phase = "decode"
        st.pf = None
        if st.prefill_t is None:
            st.prefill_t = prefill_t
        st.token_t.append(token_t)
        st.generated.append(first)
        if self.collect_logits:
            st.logits.append(np.asarray(logits_row))
        self._tokens[slot, 0] = first
        self._lens[slot] = req.prompt_len
        if len(st.generated) >= req.max_new_tokens:
            # degenerate budget: the prefill token already finishes it
            self._evict(slot)

    # -- chunked prefill -----------------------------------------------------

    def _advance_chunks(self) -> int:
        """Run at most one prefill chunk per prefilling slot, bounded by
        ``prefill_budget`` tokens per step. Returns tokens prefilled."""
        if self.chunk_prefill is None:
            return 0
        budget = self.prefill_budget
        progressed = 0
        for slot, st in enumerate(self._slots):
            if st is None or st.phase != "prefill":
                continue
            pf = st.pf
            c = pf.chunk
            if budget is not None and budget - progressed < c:
                continue               # zero-budget chunk: hold, no progress
            start = pf.pos
            is_last = start + c >= pf.prompt_len
            last_local = (pf.prompt_len - 1 - start) if is_last else (c - 1)
            real = min(c, pf.prompt_len - start)
            with self._span("engine.prefill", request=st.request.request_id,
                            prompt=real, chunk=c) as sp:
                chunk_tokens = jnp.asarray(
                    pf.tokens[start:start + c][None, :])
                logits, pf.cache = self._chunk(
                    self.params, pf.cache, chunk_tokens,
                    jnp.asarray(start, jnp.int32),
                    jnp.asarray(last_local, jnp.int32), c, pf.bucket)
                if is_last:
                    logits_row = np.asarray(logits[0])
            if st.prefill_t is None:
                st.prefill_t = sp.start
            pf.pos = start + c
            progressed += c
            self._prefill_chunks += 1
            self._prefill_calls += 1
            self._prefilled_tokens += c
            self._prompt_tokens += real
            if is_last:
                tokens_np = pf.tokens[:pf.prompt_len]
                self._prefix_insert(tokens_np, pf.cache, logits_row)
                self._start_decode(slot, st.request, pf.cache, logits_row,
                                   sp.start, sp.end)
        return progressed

    # -- eviction / shedding -------------------------------------------------

    def _evict(self, slot: int, shed: bool = False) -> None:
        st = self._slots[slot]
        deadline = st.deadline_vms
        slo_met: bool | None = None
        if shed:
            slo_met = False
            self._slo_stats["missed"] += 1
        elif deadline is not None:
            slo_met = self._vtime_ms <= deadline
            self._slo_stats["met" if slo_met else "missed"] += 1
        self._completions.append(Completion(
            request_id=st.request.request_id,
            tokens=list(st.generated),
            slot=slot,
            admitted_step=st.admitted_step,
            finished_step=self._step_idx,
            logits=(np.stack(st.logits) if st.logits else None),
            slo_met=slo_met,
            shed=shed,
            submit_t=self._submit_t.pop(st.request.request_id, None),
            prefill_t=st.prefill_t,
            token_t=st.token_t))
        self._slots[slot] = None
        self._lens[slot] = 0

    def _shed_expired(self) -> int:
        """Evict in-flight requests whose deadline has already passed on
        the virtual clock (``SLOConfig.shed_admitted``); a mid-prefill
        shed simply discards the slot's private chunk cache."""
        if self._slo is None or not self._slo.shed_admitted:
            return 0
        shed = 0
        for slot, st in enumerate(self._slots):
            if st is None or st.deadline_vms is None:
                continue
            if self._vtime_ms > st.deadline_vms:
                self._slo_stats["shed_admitted"] += 1
                self._evict(slot, shed=True)
                shed += 1
        return shed

    def shed_request(self, request_id: int) -> bool:
        """Drop a request wherever it is — queued, prefilling, or decoding.

        Returns True when found.  An in-flight request completes with its
        partial tokens and ``shed=True``; a prefilling slot's private
        cache is discarded (nothing was inserted into the batch yet).
        """
        for req in self._queue:
            if req.request_id == request_id:
                self._queue = collections.deque(
                    r for r in self._queue if r is not req)
                self._shed_completion(req)
                return True
        for slot, st in enumerate(self._slots):
            if st is not None and st.request.request_id == request_id:
                self._evict(slot, shed=True)
                return True
        return False

    # -- params hot swap -----------------------------------------------------

    def stage_params(self, params) -> None:
        """Stage a replacement serving tree for a between-steps hot swap.

        The engine keeps decoding on the current tree; the swap happens at
        the top of the next ``step()``, before admission, so every request
        (in-flight and newly admitted) sees a consistent pack and no step
        is ever skipped.  Staging again before the swap replaces the
        previously staged tree (last writer wins).  The swap bumps the
        params version and drops every prefix-cache entry — a KV prefix
        computed under the old pack is stale the moment the new one lands.
        """
        self._staged_params = params

    @property
    def swap_pending(self) -> bool:
        return self._staged_params is not None

    # -- step loop -----------------------------------------------------------

    def step(self) -> list[Completion]:
        """One scheduling step: swap staged params, shed expired requests,
        admit, advance prefill chunks, run one batched decode wave over
        decoding slots, evict finished requests.

        Returns the requests that finished on this step.
        """
        with self._span("engine.step", step=self._step_idx):
            done_before = len(self._completions)
            if self._staged_params is not None:
                self.params = self._staged_params
                self._staged_params = None
                self._swap_steps.append(self._step_idx)
                self._params_version += 1
                if self._prefix_cache is not None:
                    self._prefix_invalidated_entries += \
                        self._prefix_cache.invalidate()
            self._shed_expired()
            self._admit()
            chunked = self._advance_chunks()
            live = [i for i, s in enumerate(self._slots)
                    if s is not None and s.phase == "decode"]
            if live:
                self._active_slot_steps += len(live)
                with self._span("engine.decode.dispatch", live=len(live)) as d:
                    self.watchdog.start_step(self._step_idx, now=d.start)
                    nxt, logits, self._cache = self._step(
                        self.params, self._cache, jnp.asarray(self._tokens),
                        jnp.asarray(self._lens))
                with self._span("engine.decode.readback") as rb:
                    nxt = np.asarray(nxt)
                self.watchdog.end_step(now=rb.end)
                self._step_idx += 1
                logits_np = np.asarray(logits) if self.collect_logits else None
                for i in live:
                    st = self._slots[i]
                    st.generated.append(int(nxt[i, 0]))
                    st.token_t.append(rb.end)
                    if self.collect_logits:
                        st.logits.append(logits_np[i])
                    self._tokens[i, 0] = nxt[i, 0]
                    self._lens[i] += 1
            worked = bool(live) or chunked > 0 or \
                len(self._completions) > done_before
            if worked:
                self._vtime_ms += self._step_ms
            if live:
                for i in live:
                    st = self._slots[i]
                    if st is not None and \
                            len(st.generated) >= st.request.max_new_tokens:
                        self._evict(i)
            self._last_step_worked = worked
            # beat after evictions so a supervisor reads end-of-step state
            if worked and self.heartbeat is not None:
                self.heartbeat.beat(self._step_idx, active=self.n_active,
                                    completed=len(self._completions))
            return self._completions[done_before:]

    def run(self, requests=None) -> list[Completion]:
        """Drain the queue (plus ``requests``, if given) to completion.

        Returns all completions sorted by request_id.  Raises when the
        scheduler stalls (queued/active work but no progress for
        ``_STALL_LIMIT`` consecutive steps — e.g. a ``prefill_budget``
        smaller than the chunk size).
        """
        if requests is not None:
            self.submit_all(requests)
        stalls = 0
        while self._queue or self.n_active:
            self.step()
            stalls = 0 if self._last_step_worked else stalls + 1
            if stalls >= _STALL_LIMIT:
                raise RuntimeError(
                    f"scheduler stalled: {self.n_pending} pending / "
                    f"{self.n_active} active but no progress for "
                    f"{stalls} steps (prefill_budget "
                    f"{self.prefill_budget} < chunk {self.chunk_prefill}?)")
        return sorted(self._completions, key=lambda c: c.request_id)

    # -- reporting -----------------------------------------------------------

    def scheduler_report(self) -> dict:
        """Scheduler counters: slot occupancy, steps, host seconds per span
        (``span_s``, with counts in ``span_n``; ``decode_wall_s`` is the
        decode dispatch plus its readback), prefill counters (``prompt_tokens``
        real prompt tokens among the ``prefilled_tokens`` rows computed, in
        ``prefill_calls`` calls), prefix-cache and SLO telemetry."""
        steps = self._step_idx
        gen_tokens = sum(len(c.tokens) for c in self._completions)
        occ = (self._active_slot_steps / (steps * self.batch_size)
               if steps else 0.0)
        span_s = {k: v[0] for k, v in self._span_totals.items()}
        decode_wall_s = (span_s.get("engine.decode.dispatch", 0.0)
                         + span_s.get("engine.decode.readback", 0.0))
        rep = {
            "batch_size": self.batch_size,
            "steps": steps,
            "completed": len(self._completions),
            "pending": self.n_pending,
            "active": self.n_active,
            "generated_tokens": gen_tokens,
            "slot_occupancy": occ,
            "decode_wall_s": decode_wall_s,
            "stragglers": len(self.watchdog.stragglers),
            "step_ema_s": self.watchdog.ema_s,
            "hangs": self._hangs,
            "swaps": len(self._swap_steps),
            "swap_steps": list(self._swap_steps),
            "prefill_traces": self._prefill_traces,
            "chunk_traces": self._chunk_traces,
            "prefill_chunks": self._prefill_chunks,
            "prefilled_tokens": self._prefilled_tokens,
            "prompt_tokens": self._prompt_tokens,
            "prefill_calls": self._prefill_calls,
            "span_s": span_s,
            "span_n": {k: v[1] for k, v in self._span_totals.items()},
        }
        if self._prefix_cache is not None:
            pc = self._prefix_cache.stats()
            pc["invalidated_entries"] = self._prefix_invalidated_entries
            rep["prefix_cache"] = pc
        if self._slo is not None:
            rep["slo"] = dict(self._slo_stats, step_ms=self._step_ms)
        return rep

    def perf_report(self, flops_per_token: float | None = None) -> dict:
        """Scheduler counters + the session's batch-aware DRAM-side rates."""
        rep = self.scheduler_report()
        if self.session is not None:
            rep.update(self.session.perf_report(
                flops_per_token, batch_size=self.batch_size))
        return rep


def _merge(dicts) -> dict:
    """Per-key sums of numeric dicts."""
    out: dict = {}
    for d in dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0) + v
    return out


class FleetServingEngine:
    """Data-parallel fleet of ``ServingEngine``s over per-lane sharded packs.

    One inner engine per "data"-axis lane of a ``PUDFleetSession``;
    requests partition by prefix-cache affinity (the lane whose LRU holds
    the longest matching prefix wins — repeated system prompts keep
    landing where their KV already lives) with round-robin as the
    fallback, and every lane keeps the single-engine semantics —
    continuous batching, per-request bit-exact decode — so a request's
    tokens (and logits) are identical to running it through a
    single-device ``ServingEngine``.  Scheduler extensions
    (``chunk_prefill`` / ``prefix_cache`` / ``slo``) pass through to every
    lane; ``prefix_cache=True`` builds one *per-lane* cache (entries hold
    lane-sharded KV pytrees, so they must not cross lanes).  The
    model-parallel dimension lives *inside* each lane's params: every
    packed projection is a ``ShardedPackedTensor`` executing via
    ``shard_map`` over the mesh's "model" axis
    (``kernels.ops.pud_matmul_sharded``), so a lane's decode step is one
    jitted program spanning its model shards.
    """

    def __init__(self, model, lane_params, *, max_len: int,
                 fleet=None, sessions=None, batch_size: int | None = None,
                 **kw):
        if not lane_params:
            raise ValueError("need at least one data lane")
        if sessions is None and fleet is not None:
            # lane d's default batch size derives from its shard-0 session
            sessions = [row[0] for row in fleet.sessions]
        if sessions is None:
            sessions = [None] * len(lane_params)
        if isinstance(kw.get("prefix_cache"), PrefixCache) and \
                len(lane_params) > 1:
            raise ValueError(
                "a shared PrefixCache cannot span lanes (entries hold "
                "lane-local KV); pass prefix_cache=True for per-lane caches")
        self.fleet = fleet
        self.lanes = [
            ServingEngine(model, p, session=s, max_len=max_len,
                          batch_size=batch_size, **kw)
            for p, s in zip(lane_params, sessions)]
        for i, lane in enumerate(self.lanes):
            lane.lane = i
        self._next_lane = 0

    @property
    def n_lanes(self) -> int:
        return len(self.lanes)

    @property
    def batch_size(self) -> int:
        return self.lanes[0].batch_size

    @property
    def n_pending(self) -> int:
        return sum(lane.n_pending for lane in self.lanes)

    @property
    def n_active(self) -> int:
        return sum(lane.n_active for lane in self.lanes)

    def submit(self, request: Request) -> int:
        """Place the request on the lane with the longest cached prefix of
        its prompt (cache affinity), else round-robin; returns the lane
        index."""
        best, best_len = None, 0
        for i, lane in enumerate(self.lanes):
            n = lane.prefix_probe(np.asarray(request.tokens, np.int32))
            if n > best_len:
                best, best_len = i, n
        if best is None:
            best = self._next_lane
            self._next_lane = (best + 1) % len(self.lanes)
        self.lanes[best].submit(request)
        return best

    def submit_all(self, requests) -> None:
        for r in requests:
            self.submit(r)

    def stage_lane_params(self, lane: int, params) -> None:
        """Per-lane hot-swap hook (drift recovery repacks one lane only)."""
        self.lanes[lane].stage_params(params)

    def step(self) -> list[Completion]:
        """Step every lane that has work; returns this step's completions."""
        done: list[Completion] = []
        for lane in self.lanes:
            if lane._queue or lane.n_active or lane.swap_pending:
                done.extend(lane.step())
        return done

    def run(self, requests=None) -> list[Completion]:
        """Drain every lane; all completions sorted by request_id."""
        if requests is not None:
            self.submit_all(requests)
        while any(lane._queue or lane.n_active for lane in self.lanes):
            self.step()
        comps = [c for lane in self.lanes for c in lane._completions]
        return sorted(comps, key=lambda c: c.request_id)

    # -- reporting -----------------------------------------------------------

    def scheduler_report(self) -> dict:
        """Fleet-merged counters plus the per-lane reports; span seconds
        and counts are summed over lanes, which step one after another."""
        reps = [lane.scheduler_report() for lane in self.lanes]

        def summed(key):
            return sum(r[key] for r in reps)

        rep = {
            "n_lanes": len(self.lanes),
            "batch_size": self.batch_size,
            "steps": max(r["steps"] for r in reps),
            "completed": summed("completed"),
            "pending": summed("pending"),
            "active": summed("active"),
            "generated_tokens": summed("generated_tokens"),
            "slot_occupancy": summed("slot_occupancy") / len(reps),
            "prefilled_tokens": summed("prefilled_tokens"),
            "prompt_tokens": summed("prompt_tokens"),
            "prefill_calls": summed("prefill_calls"),
            "decode_wall_s": summed("decode_wall_s"),
            "span_s": _merge(r["span_s"] for r in reps),
            "span_n": _merge(r["span_n"] for r in reps),
            "lanes": reps,
        }
        pcs = [r["prefix_cache"] for r in reps if "prefix_cache" in r]
        if pcs:
            hits = sum(p["hits"] for p in pcs)
            misses = sum(p["misses"] for p in pcs)
            rep["prefix_cache"] = {
                "entries": sum(p["entries"] for p in pcs),
                "bytes": sum(p["bytes"] for p in pcs),
                "hits": hits,
                "misses": misses,
                "hit_rate": (hits / (hits + misses)
                             if hits + misses else 0.0),
                "inserts": sum(p["inserts"] for p in pcs),
                "evictions": sum(p["evictions"] for p in pcs),
                "invalidations": sum(p["invalidations"] for p in pcs),
            }
        return rep

    def perf_report(self, flops_per_token: float | None = None) -> dict:
        """Merged scheduler counters + the fleet's aggregate rate model."""
        rep = self.scheduler_report()
        if self.fleet is not None:
            rep.update(self.fleet.perf_report(
                flops_per_token, batch_size=self.batch_size))
        return rep
