"""ContinuousBatcher: iteration-level scheduling over the paged KV pool.

The lockstep path (`GenerativeSession.generate`) has three structural
costs for multi-request traffic: the whole batch decodes until the SLOWEST
request finishes, partial batches burn compute on tiled padding rows, and
a new request waits for the entire previous batch. This module removes all
three with the Orca insight — schedule at ITERATION granularity:

 - every decode dispatch steps ALL active slots at their OWN positions
   (the vector-``decode_pos`` path in ops/attention.py writes slot i's K/V
   at ``pos[i]`` and masks attention to its own length);
 - a request that emits EOS or hits ``max_new_tokens`` releases its slot
   and pool pages THAT iteration;
 - a queued request prefills into the freed slot on the next iteration
   (one batch-1 prefill dispatch scattered into its slot's cache rows)
   while every other sequence keeps decoding — nobody restarts, nobody
   waits for a batch boundary.

Requests move through a small state machine::

    QUEUED --admit+slot--> PREFILL --first token--> DECODE --eos/max--> FINISHED
        \\                                             \\
         +------------------ FAILED <------------------+

PREFILL is a RESUMABLE state: by default prompts are prefilled in
fixed-size CHUNKS (one KV page per scheduler iteration, via the
chunk-offset entry in ops/attention.py), interleaved with decode
iterations — a 4k-token prompt no longer freezes every in-flight decode
for its whole prefill, it costs each decoder one chunk of extra latency
per iteration instead. Chunking also removes the prompt <= window
admission cap: the model's declared input length bounds the CHUNK, not
the prompt. ``prefill_chunk_tokens=0`` restores the legacy one-shot
prefill.

Multi-tenant prefix reuse (kvpool.PrefixCache): when a scheduled prompt's
page-aligned prefix is already cached, the cached K/V rows are INSTALLED
into the sequence's slot by a device-side copy and only the suffix is
prefilled — at millions-of-users scale most traffic shares a system
prompt, so the hit path turns TTFT from O(prompt) into O(suffix). Cold
prefills insert their full prefix pages into the cache as they finish.
Admission credits the expected sharing against its backlog page budget
(admission.py), so shared-prefix floods admit deeper than worst-case
sizing says.

Per-request token streams: `submit()` returns a `GenRequest` whose
`.stream()` yields tokens as the scheduler emits them (server.py wires
this through `/generate` with ``"stream": true``) and whose `.result()`
blocks for the full array.

Determinism: greedy decode (temperature<=0) is token-identical to the
lockstep path for the same prompt — per-row attention is independent of
batch composition. Sampled decode draws per-REQUEST keys
(fold_in(PRNGKey(request.seed), position)), so a request's tokens are a
function of its own (seed, prompt) and never of co-scheduled traffic.
"""
from __future__ import annotations

import enum
import itertools
import queue
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ...ffconst import CompMode
from ...obs.startup import watch_compiles
from ...obs.tracing import first_call, get_tracer, phased
from ...runtime.executor import NO_ROW
from ..batcher import BatcherStopped
from .admission import AdmissionController
from .kvpool import (PagedKVPool, derive_num_slots, install_slot,
                     kv_bytes_per_token, kv_cache_spec, op_states,
                     refuse_ring, refuse_sequence_state, ring_bytes_per_slot,
                     state_bytes_per_slot, write_slot_span, zero_kv_caches)


class RequestCancelled(RuntimeError):
    """The caller cancelled a still-queued request (ContinuousBatcher
    .cancel) before it reached a slot."""


class ResizeTicket:
    """Handle for one requested mesh resize (ContinuousBatcher
    .request_resize). The scheduler applies the resize between
    iterations — once live sequences fit the target — and resolves the
    ticket with the migration stats; `wait()` blocks until then."""

    def __init__(self, target_slots: int):
        self.target_slots = int(target_slots)
        self.result: Optional[Dict] = None
        self.error: Optional[BaseException] = None
        self._done = threading.Event()

    def wait(self, timeout: Optional[float] = None) -> Dict:
        if not self._done.wait(timeout=timeout):
            raise TimeoutError(
                f"resize to {self.target_slots} slots not applied within"
                f" {timeout}s")
        if self.error is not None:
            raise self.error
        return self.result

    def done(self) -> bool:
        return self._done.is_set()

    def _finish(self, result: Dict) -> None:
        self.result = result
        self._done.set()

    def _fail(self, err: BaseException) -> None:
        self.error = err
        self._done.set()


class HandoffTicket:
    """Handle for one scheduler-thread KV-handoff step: an EXPORT of a
    parked sequence's cache rows to host memory, or an IMPORT of shipped
    rows into this batcher's caches as a decode-entry request. Like
    ResizeTicket, the work runs between scheduler iterations — the cache
    arrays are jit-donated, so only the scheduler thread may touch them —
    and `wait()` blocks until it resolves. Failures are typed: admission
    errors and `KVGeometryMismatch` land here, never in the loop."""

    def __init__(self):
        self.result = None
        self.error: Optional[BaseException] = None
        self._done = threading.Event()

    def wait(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout=timeout):
            raise TimeoutError(
                f"KV handoff step not applied within {timeout}s")
        if self.error is not None:
            raise self.error
        return self.result

    def done(self) -> bool:
        return self._done.is_set()

    def _finish(self, result) -> None:
        self.result = result
        self._done.set()

    def _fail(self, err: BaseException) -> None:
        self.error = err
        self._done.set()


class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILL = "prefill"
    DECODE = "decode"
    # disaggregated serving (docs/serving.md "Disaggregated serving"): a
    # prefill-only request holds this state after its first token — KV
    # complete and resident, slot + pages held, NOT decoding — until the
    # fleet handoff plane exports its pages to a decode replica
    # (release_parked) or the handoff fails and it degrades to local
    # decode (resume_parked)
    PARKED = "parked"
    FINISHED = "finished"
    FAILED = "failed"


_DONE = object()


class GenRequest:
    """Handle for one submitted generation request."""

    def __init__(self, rid: int, prompt: np.ndarray, max_new_tokens: int,
                 eos_id: Optional[int], seed: int):
        self.id = rid
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.seed = int(seed)
        self.state = RequestState.QUEUED
        self.tokens: List[int] = []
        self.error: Optional[BaseException] = None
        self._stream: "queue.Queue" = queue.Queue()
        self._done = threading.Event()
        self.t_submit = time.monotonic()
        self.t_first_token: Optional[float] = None
        self.t_done: Optional[float] = None
        self.queue_wait_s: Optional[float] = None
        # emission timestamp per token — what serve-bench computes
        # inter-token latencies from (the chunked-prefill acceptance bound)
        self.token_times: List[float] = []
        # prefix-cache outcome, set when the scheduler takes the request:
        # cache_hit = >=1 page of the prompt was installed from the cache
        self.cache_hit = False
        self.prefix_tokens = 0
        # expert-affine admission (sched/affinity.py): the probe's expert
        # signature, and how many picks jumped over this request (the
        # anti-starvation bound)
        self.expert_sig = frozenset()
        self.affinity_skips = 0
        # disaggregated serving: a prefill-only request parks after its
        # first token instead of entering DECODE — the fleet handoff
        # plane ships its finished KV to a decode replica
        self.prefill_only = False
        # distributed-tracing handoff (obs/tracing.py): submit() stamps
        # the caller's TraceContext here as a Handoff token; the
        # scheduler thread resumes it around this request's spans, so
        # server handler -> scheduler crossings stitch under one
        # trace_id (None when tracing is off or no context is active)
        self.trace = None
        # failover fence (serving/fleet/router.py): once fenced, the
        # emitted-token snapshot is frozen — a possibly-still-live
        # scheduler thread (hung, then resumed) can no longer append
        # tokens the fleet-level replay would duplicate
        self._emit_lock = threading.Lock()
        self._fenced = False

    # -- consumer API ------------------------------------------------------
    def stream(self, timeout: Optional[float] = None):
        """Yield token ids in emission order; raises the request's error if
        it failed. Each next() waits at most `timeout` seconds."""
        while True:
            try:
                item = self._stream.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError(
                    f"request {self.id}: no token within {timeout}s")
            if item is _DONE:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until finished; returns the (n,) int32 generated tokens."""
        if not self._done.wait(timeout=timeout):
            raise TimeoutError(
                f"request {self.id} not finished within {timeout}s")
        if self.error is not None:
            raise self.error
        return np.asarray(self.tokens, np.int32)

    def done(self) -> bool:
        return self._done.is_set()

    @property
    def ttft_s(self) -> Optional[float]:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_submit

    @property
    def trace_id(self) -> Optional[str]:
        return self.trace.trace_id if self.trace is not None else None

    # -- scheduler side ----------------------------------------------------
    def _emit(self, tok: int) -> None:
        with self._emit_lock:
            if self._fenced:
                return
            self.tokens.append(int(tok))
            self.token_times.append(time.monotonic())
            self._stream.put(int(tok))

    def _finish(self) -> None:
        with self._emit_lock:
            if self._fenced or self._done.is_set():
                return
            self.state = RequestState.FINISHED
            self.t_done = time.monotonic()
            self._stream.put(_DONE)
            self._done.set()

    def _fail(self, err: BaseException) -> None:
        with self._emit_lock:
            if self._fenced or self._done.is_set():
                return
            self.state = RequestState.FAILED
            self.error = err
            self.t_done = time.monotonic()
            self._stream.put(err)
            self._done.set()

    def _fence(self, err: BaseException):
        """Atomically freeze the request for fleet failover: no token
        emitted after the fence is visible anywhere, so the returned
        (tokens, token_times) snapshot is EXACTLY what the caller's
        stream has seen or will see before the error sentinel (the
        stream queue is FIFO — tokens precede the error). Returns None
        when the request already FINISHED cleanly (nothing to replay);
        otherwise fails the handle with `err` (unless some failure is
        already recorded — the consumer must see exactly one error) and
        returns the snapshot, even for already-FAILED requests, since a
        scheduler crash fails its slots before the router's failover
        runs."""
        with self._emit_lock:
            already = self._fenced
            self._fenced = True
            if self.state is RequestState.FINISHED:
                return None
            snap = (list(self.tokens), list(self.token_times))
            if not already and self.error is None:
                self.state = RequestState.FAILED
                self.error = err
                self.t_done = time.monotonic()
                self._stream.put(err)
                self._done.set()
            return snap


class _Slot:
    """One active sequence bound to a pool slot."""

    __slots__ = ("req", "slot", "pos", "emitted", "last_tok", "key",
                 "t_last_emit", "plen", "filled", "shared", "small",
                 "draft_small", "draft_filled")

    def __init__(self, req: GenRequest, slot: int, key: np.ndarray):
        self.req = req
        self.slot = slot
        self.pos = 0          # cache position the NEXT decode writes at
        self.emitted = 0
        self.last_tok = 0
        self.key = key        # (2,) uint32 per-request PRNG key
        self.t_last_emit = time.monotonic()
        self.plen = 0         # prompt length
        self.filled = 0       # prompt tokens already in the cache (chunked
        #                       prefill resumes here each iteration)
        self.shared = 0       # leading tokens installed from the prefix
        #                       cache (pinned shared pages; CoW boundary)
        self.small = None     # per-prefill batch-1 caches, dropped at the
        #                       finish scatter
        self.draft_small = None  # the DRAFT model's batch-1 prefill
        #                       caches (speculative decoding only)
        self.draft_filled = 0    # prompt tokens in the draft's cache —
        #                       always from 0, even on a prefix-cache hit
        #                       (the band holds TARGET-geometry pages)


class ContinuousBatcher:
    """Continuous-batching scheduler over a compiled causal-transformer
    FFModel (same model contract as GenerativeSession: final tensor is a
    vocab distribution, the declared input seq length is the prefill
    window).

    temperature/top_k are BATCHER-level policy (each combination jits a
    decode step — client-chosen values would be a compile-DoS surface,
    the same rule register_generative applies); per-request `seed` is an
    operand and free.

    prefill_chunk_tokens (default: one KV page) splits every prefill into
    fixed-size chunks interleaved with decode iterations; 0 restores the
    legacy one-shot prefill (and with it the prompt <= window cap).
    prefix_cache_pages budgets the hash-addressed prefix cache's device
    band (default: two slots' worth when chunking; 0 disables reuse —
    see kvpool.PrefixCache for the sharing/CoW contract).

    Speculative decoding (docs/serving.md): pass a compiled causal
    `draft_model` (same vocab) and `spec_tokens=k`. Every decode
    iteration then runs ONE fused dispatch — k unrolled greedy draft
    steps over the draft's own slot-dense caches, then the target
    scoring the pending token plus all k proposals through the
    multi-query decode entry — and emits each slot's longest accepted
    prefix (capped at k tokens/iteration; the classic k+1 bonus is
    traded for fixed dispatch shapes). Greedy output is token-identical
    to non-speculative greedy regardless of the draft. Greedy-only and
    chunked-prefill-only; the draft prefills the full prompt through
    its own chunk stream (prefix-cache hits install target-geometry
    pages only).

    Metrics default to the PROCESS-WIDE obs registry (like ff_checkpoint_*
    and ff_watchdog_*), which every server's /metrics already concatenates
    — passing a per-server registry here would render duplicate families.
    Pass an explicit `registry` only for isolated tests.
    """

    @phased("serve.build")
    def __init__(self, model, max_len: int, num_slots: Optional[int] = None,
                 page_size: int = 16, machine=None, max_queue: int = 64,
                 queue_pages_budget: Optional[int] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 registry=None,
                 prefill_chunk_tokens: Optional[int] = None,
                 prefix_cache_pages: Optional[int] = None,
                 draft_model=None, spec_tokens: int = 3,
                 expert_affinity: bool = False,
                 affinity_window: int = 4,
                 trace_label: Optional[str] = None,
                 role: str = "unified"):
        if getattr(model.executor, "mesh", None) is not None:
            # a mesh is fine as long as nothing is actually partitioned
            # (the common replicated case — e.g. a dp axis the batch does
            # not divide): sharding CONSTRAINTS assume the compiled batch,
            # which the batch-polymorphic prefill/decode dispatches break
            for op in model.graph.ops.values():
                for t in list(op.outputs) + list(op.weights):
                    ps = getattr(t, "parallel_shape", None)
                    if ps is not None and any(
                            p is not None for p in ps.partition_spec()):
                        raise ValueError(
                            "ContinuousBatcher serves unsharded models;"
                            f" tensor {t.name!r} is partitioned"
                            f" ({ps.partition_spec()}) and its sharding"
                            " constraint assumes the compiled batch")
        self.model = model
        self.max_len = int(max_len)
        self.window = model.input_ops[0].outputs[0].dims[1]
        # chunked prefill (default ON, one page per chunk): PREFILL becomes
        # a resumable state interleaved with decode iterations, and the
        # prompt is no longer bounded by the model's declared input length.
        # 0 = legacy one-shot prefill (pads to the window, cache-cold).
        if prefill_chunk_tokens is None:
            chunk = int(page_size)
        else:
            chunk = int(prefill_chunk_tokens)
            if chunk < 0:
                raise ValueError(
                    f"prefill_chunk_tokens={prefill_chunk_tokens}:"
                    " need >= 0 (0 = one-shot prefill)")
        # the chunk is fed through the model input, so it must fit the
        # declared window
        self.prefill_chunk_tokens = min(chunk, self.window) if chunk else 0
        if self.prefill_chunk_tokens == 0 and self.max_len < self.window:
            # one-shot prefill scatters a full (1, window) pass into the
            # slot's cache rows; chunked prefill has no such floor
            raise ValueError(
                f"max_len={max_len} smaller than the prefill window"
                f" ({self.window})")
        if top_k is not None and int(top_k) < 1:
            raise ValueError(f"top_k={top_k}: must be >= 1")
        if float(temperature) < 0.0:
            raise ValueError(f"temperature={temperature}: must be >= 0")
        self.temperature = float(temperature)
        self.top_k = top_k
        # disaggregated serving role (docs/serving.md "Disaggregated
        # serving"): "prefill" parks every request after its first token
        # for the fleet KV-handoff plane (and charges no decode leg in
        # predicted_ttft_s — nothing decodes here); "decode" serves
        # imported sequences beside normal traffic; "unified" is the
        # classic both-phases replica.
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role={role!r}: must be 'prefill', 'decode' or"
                " 'unified'")
        if role == "prefill" and draft_model is not None:
            raise ValueError(
                "role='prefill' cannot speculate: a parked request never"
                " decodes here, and the draft's caches do not ship in"
                " the KV handoff")
        self.role = role
        # the ops that keep a serving cache of either kind, found by
        # capability (Op.kv_cache_arrays per token, Op.sequence_state_arrays
        # per sequence), not by one op type
        self.attn_ops = [op for op in model.graph.ops.values()
                         if op.kv_cache_arrays()
                         or op.sequence_state_arrays()]
        if not self.attn_ops:
            raise ValueError(
                "generation needs an op that keeps a serving cache"
                " (multihead_attention, latent_attention, ssm_mixer)")
        # a prefill dispatch runs what follows the last of them for the one
        # position it samples, or not at all: a graph whose tail reads
        # across positions is refused here (RowCutError), not served wrong
        model.executor.row_cut()
        # per-sequence state (kvpool.py's second kind) is not addressable
        # by token position: what would need it AT a position is refused
        # here, typed, by the capability and never by an option
        self._seq_parts = {
            op.name: frozenset(op.sequence_state_arrays() or ())
            for op in self.attn_ops}
        self._stateful = any(self._seq_parts.values())
        # so is a window op's ring (kvpool.py's third kind): no page names
        # its rows
        self._rings = {c.op: c.token_rows(self.max_len)
                       for c in kv_cache_spec(model) if c.ring is not None}
        self._unpaged = self._stateful or bool(self._rings)
        if role != "unified":
            self._refuse_unpaged(
                f"role={role!r} (KV export/import between replicas)")
        if draft_model is not None:
            self._refuse_unpaged("speculative decoding")
            refuse_sequence_state(draft_model, "speculative decoding")
            refuse_ring(draft_model, "speculative decoding")

        # speculative decoding (docs/serving.md): a draft model proposes
        # `spec_tokens` greedy candidates per slot per iteration, the
        # target scores all of them plus the pending token in ONE fused
        # multi-query dispatch (ops/attention.py vector C>1 decode
        # entry), and the longest matching prefix is emitted — greedy
        # output stays token-identical to non-speculative greedy,
        # rejected suffixes just roll the write-back pointer back.
        self.draft_model = draft_model
        self.spec_tokens = int(spec_tokens) if draft_model is not None else 0
        self.draft_attn_ops = []
        if draft_model is not None:
            if self.spec_tokens < 2:
                # the emission cap (m = min(n_acc+1, k), which keeps the
                # draft exactly one token behind) means k=1 can emit at
                # most one token per iteration — a guaranteed regression
                # vs plain decode, so it is rejected rather than allowed
                # to silently serve slower
                raise ValueError(
                    f"spec_tokens={spec_tokens}: need >= 2 — emission is"
                    " capped at spec_tokens tokens/iteration, so k=1 can"
                    " never beat plain decode")
            if self.temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only (temperature 0):"
                    " sampled acceptance needs rejection sampling, which"
                    " this batcher does not implement")
            if self.prefill_chunk_tokens == 0:
                raise ValueError(
                    "speculative decoding requires chunked prefill"
                    " (prefill_chunk_tokens > 0): the draft model"
                    " prefills its own cache through the chunk entry")
            if self.spec_tokens + 1 > self.window:
                raise ValueError(
                    f"spec_tokens={self.spec_tokens}: the verify dispatch"
                    f" feeds {self.spec_tokens + 1} query tokens, more"
                    f" than the target's declared window ({self.window})")
            draft_window = draft_model.input_ops[0].outputs[0].dims[1]
            if draft_window < self.prefill_chunk_tokens:
                raise ValueError(
                    f"draft window ({draft_window}) smaller than the"
                    f" prefill chunk ({self.prefill_chunk_tokens}): the"
                    " draft prefills through the same chunk entry")
            self.draft_attn_ops = [
                op for op in draft_model.graph.ops.values()
                if op.kv_cache_arrays()]
            if not self.draft_attn_ops:
                raise ValueError(
                    "draft model needs an attention op that keeps a"
                    " serving cache")
            draft_model.executor.row_cut()
            tvocab = model.final_tensor.dims[-1]
            dvocab = draft_model.final_tensor.dims[-1]
            if tvocab != dvocab:
                raise ValueError(
                    f"draft vocab ({dvocab}) != target vocab ({tvocab}):"
                    " proposals must be scoreable by the target")
        # expert-affine admission (docs/moe.md "Serving"): a host-side
        # router probe signs every request at submit; _admit_new then
        # prefers queued requests whose expert set overlaps the running
        # batch's, within a bounded fairness window. Purely an admission
        # ORDER policy — tokens are unchanged.
        self._affinity_probe = None
        self.affinity_window = max(1, int(affinity_window))
        if expert_affinity:
            from .affinity import ExpertAffinityProbe

            self._affinity_probe = ExpertAffinityProbe(model)
        # prefix cache sizing: default two slots' worth of band pages when
        # chunked prefill is on (the hit path needs the chunk-offset entry
        # to prefill just the suffix); 0 disables reuse
        import math as _math

        pages_per_slot = _math.ceil(self.max_len / int(page_size))
        full_pages_per_slot = self.max_len // int(page_size)
        if prefix_cache_pages is None:
            prefix_pages = 2 * pages_per_slot if (
                self.prefill_chunk_tokens and not self._unpaged) else 0
        else:
            prefix_pages = int(prefix_cache_pages)
            if prefix_pages:
                self._refuse_unpaged("the prefix cache")
        if prefix_pages and not self.prefill_chunk_tokens:
            raise ValueError(
                "prefix caching requires chunked prefill"
                " (prefill_chunk_tokens > 0): installing a cached prefix"
                " leaves only the suffix to prefill, which needs the"
                " chunk-offset entry")
        if full_pages_per_slot == 0:
            prefix_pages = 0  # no full page fits a slot: nothing cacheable
        band_slots = (_math.ceil(prefix_pages / full_pages_per_slot)
                      if prefix_pages else 0)
        if num_slots is None:
            # the band lives in HBM next to the decode slots: carve it out
            # of the derived capacity so the memory model stays honest
            derived = derive_num_slots(model, self.max_len, machine=machine)
            if draft_model is not None:
                # the draft's slot-dense caches live beside the target's:
                # scale the derived capacity by the combined per-token
                # cache cost so the HBM estimate stays honest
                tb = kv_bytes_per_token(model)
                db = kv_bytes_per_token(draft_model)
                derived = max(1, int(derived * tb / max(1, tb + db)))
            num_slots = max(1, derived - band_slots)
        self.num_slots = int(num_slots)

        if registry is None:
            from ...obs.registry import REGISTRY as registry  # noqa: N813
        self.registry = registry
        self.pool = PagedKVPool(self.num_slots, self.max_len,
                                page_size=page_size, registry=registry,
                                prefix_cache_pages=prefix_pages)
        # the scheduler thread's track name in trace exports (a Replica
        # passes its own name so the merged timeline shows one track per
        # replica); metric labels keep using pool.label, unchanged
        self.trace_label = str(trace_label) if trace_label else self.pool.label
        self.admission = AdmissionController(
            self.pool,
            self.window if self.prefill_chunk_tokens == 0 else None,
            max_queue=max_queue,
            queue_pages_budget=queue_pages_budget, registry=registry)
        self._g_active = registry.gauge(
            "ff_serving_slots_active", "Decode slots holding a live request",
            labels=("pool",))
        self._g_active.set(0, pool=self.pool.label)
        self._h_ttft = registry.histogram(
            "ff_serving_ttft_ms",
            "Submit-to-first-token latency, split by prefix-cache outcome",
            labels=("cache",))
        self._h_itl = registry.histogram(
            "ff_serving_itl_ms", "Inter-token latency during decode")
        self._c_requests = registry.counter(
            "ff_serving_requests_total",
            "Continuous-batching requests by outcome", labels=("outcome",))
        self._c_tokens = registry.counter(
            "ff_serving_tokens_total", "Tokens generated")
        self._c_head_rows = registry.counter(
            "ff_serving_prefill_head_rows_total",
            "Prompt positions a prefill dispatch ran past the last caching"
            " op, through the vocabulary head (one a request)",
            labels=("pool",))
        self._ewma_affinity_overlap: Optional[float] = None
        if self._affinity_probe is not None:
            self._c_affinity = registry.counter(
                "ff_serving_affinity_picks_total",
                "Expert-affine admission picks by outcome",
                labels=("outcome",))
            self._g_affinity_overlap = registry.gauge(
                "ff_serving_affinity_overlap",
                "EWMA expert-signature overlap of admitted requests with"
                " the running batch", labels=("pool",))

        # state of the ops that count (Op.serving_counters: an expert
        # layer's assignments and hits, an attention's rows filled and
        # read), threaded through decode_all from one iteration to the
        # next; read by `op_counters()`
        self._op_counters: Dict[str, Dict[str, object]] = {
            op.name: {v: model.state[op.name][v]
                      for v in op.serving_counters}
            for op in model.graph.ops.values()
            if op.serving_counters and op.name in (model.state or {})}
        # a latent attention op's prefill chunks, by the prefix of the
        # batch-1 holder each is attended over (`_count_mla_prefill`)
        self._mla_ops = [op for op in self.attn_ops
                         if hasattr(op, "prefill_prefix_rows")]
        if self._mla_ops:
            from ...obs.attention_rows import mla_prefill_chunks_counter

            self._c_mla_prefill = mla_prefill_chunks_counter(registry)
        registry.gauge(
            "ff_kvpool_bytes_per_token",
            "Bytes of serving cache one token position costs across all"
            " caching ops", labels=("pool",)).set(
                kv_bytes_per_token(model), pool=self.pool.label)
        registry.gauge(
            "ff_kvpool_state_bytes_per_slot",
            "Bytes of per-sequence state one slot costs across all caching"
            " ops, whatever its sequence's length", labels=("pool",)).set(
                state_bytes_per_slot(model), pool=self.pool.label)
        registry.gauge(
            "ff_kvpool_ring_bytes_per_slot",
            "Bytes of window rings one slot costs across all caching ops,"
            " whatever its sequence's length", labels=("pool",)).set(
                ring_bytes_per_slot(model, self.max_len),
                pool=self.pool.label)
        g_ring = registry.gauge(
            "ff_kvpool_ring_rows",
            "Token rows of a window op's ring", labels=("pool", "op"))
        for name, rows in self._rings.items():
            g_ring.set(rows, pool=self.pool.label, op=name)
        # admissions of a model that keeps per-sequence state: each starts
        # its sequence from a zeroed batch-1 state that later overwrites
        # the slot's (`_admit_new`); `op_counters` reports it per op
        self._state_resets = 0
        watch_compiles()
        tracer = get_tracer()
        with tracer.phase("serve.build.programs"):
            self._build_fns()
        with tracer.phase("serve.build.kv_alloc"):
            self._caches = self._zero_caches()
            self._band = self._zero_band()
            self._draft_caches = self._zero_draft_caches()
        self._rid = itertools.count()
        self._queue: List[GenRequest] = []
        self._slots: List[Optional[_Slot]] = [None] * self.num_slots
        self._cv = threading.Condition()
        self._running = False
        self._thread: Optional[threading.Thread] = None
        self._completed = 0
        self._retired = 0   # by this loop alone (scheduler thread only)
        self._failed = 0
        # fleet health signals (serving/fleet/health.py): the scheduler
        # stamps a heartbeat at the top of EVERY loop iteration (the idle
        # wait wakes at least every 0.1 s, so a stale heartbeat means a
        # stuck dispatch, not an empty queue) and keeps a busy-gap EWMA
        # of the wall between consecutive iterations that had work —
        # unlike _observe_decode_iter this includes any stall between
        # dispatches, which is exactly what a straggling replica shows.
        self._t_heartbeat: Optional[float] = None
        self._t_iter_prev: Optional[float] = None
        self._iter_had_work = False
        self._ewma_step_s: Optional[float] = None
        self._step_warmup = 0
        # chaos hook (serving/fleet/chaos.py): called once per scheduler
        # iteration with the batcher. Raising kills the loop like any
        # scheduler bug (_fail_all); sleeping stalls it (hang/straggle).
        self.fault_hook = None
        # lifetime generated-token count — the chaos plan's
        # crash-at-token-N trigger reads this, monotonic and cheap
        self.tokens_emitted = 0
        # disaggregated serving (docs/serving.md): parked prefill-only
        # requests awaiting KV handoff, the hook the fleet coordinator
        # registers to hear about them, and the scheduler-thread work
        # queue for export/import steps (the cache arrays are
        # jit-donated — only the loop may touch them, same rule as
        # _maybe_resize)
        self._parked: Dict[int, _Slot] = {}
        self.on_parked = None
        self._pending_handoffs: List[tuple] = []
        # mesh resize (docs/resharding.md): one pending ticket at a time,
        # applied by the scheduler thread between iterations
        self._pending_resize: Optional[ResizeTicket] = None
        self._resizes: List[Dict] = []
        self._c_resizes = registry.counter(
            "ff_serving_resizes_total",
            "Applied serving mesh resizes", labels=("direction",))
        # measured serving-rate model (docs/serving.md "Fleet"): EWMAs of
        # per-token prefill cost (sampled at SYNCED prefill dispatches —
        # one-shot and fused-final-chunk, which block on the picked token)
        # and decode-iteration wall. `predicted_ttft_s` composes them into
        # the SLO-admission estimate the fleet router sheds by.
        self._ewma_prefill_s_per_tok: Optional[float] = None
        self._ewma_decode_iter_s: Optional[float] = None
        # speculative serving doubles prefill work: the DRAFT prefills
        # the whole prompt through its own chunk stream beside the
        # target's. Its per-token cost is measured separately (sampled
        # at the draft's final synced chunk) and credited in
        # `predicted_ttft_s`'s prefill leg — without it a speculative
        # fleet under-predicts TTFT and over-admits.
        self._ewma_draft_prefill_s_per_tok: Optional[float] = None
        self._g_prefill_rate = registry.gauge(
            "ff_serving_prefill_tokens_per_s",
            "Measured prefill rate, EWMA over synced prefill dispatches",
            labels=("pool",))
        self._g_decode_iter = registry.gauge(
            "ff_serving_decode_iter_ms",
            "Measured decode-iteration wall, EWMA", labels=("pool",))
        # speculative decoding instrumentation (docs/observability.md)
        self._ewma_spec_accept: Optional[float] = None
        self._spec_proposed = 0
        self._spec_accepted = 0
        if self.draft_model is not None:
            self._c_spec_proposed = registry.counter(
                "ff_spec_decode_proposed_total",
                "Draft tokens proposed for verification")
            self._c_spec_accepted = registry.counter(
                "ff_spec_decode_accepted_total",
                "Draft tokens accepted by the target's greedy verify")
            self._g_spec_accept = registry.gauge(
                "ff_spec_decode_acceptance",
                "EWMA draft-token acceptance rate (accepted/proposed)",
                labels=("pool",))

    def _refuse_unpaged(self, feature: str) -> None:
        """Typed refusal of what addresses cache rows by token position,
        for a model one of whose ops keeps per-sequence state or a ring."""
        refuse_sequence_state(self.model, feature)
        refuse_ring(self.model, feature)

    # -- jitted device functions ------------------------------------------
    def _zero_caches(self):
        # zero_kv_caches allocates the SAME geometry (kv_cache_spec)
        # derive_num_slots sized the pool with — allocation can never
        # drift from the HBM estimate
        return zero_kv_caches(self.model, self.num_slots, self.max_len)

    def _zero_band(self):
        """The prefix cache's device-side page store: slot-shaped rows
        SEPARATE from the decode caches, so decode dispatches never carry
        (or attend over) the band. None when prefix reuse is off."""
        band_slots = self.pool.band_slots
        if band_slots == 0:
            return None
        return zero_kv_caches(self.model, band_slots, self.max_len)

    def _zero_draft_caches(self):
        """The draft model's slot-dense KV caches, mirroring the target's
        geometry slot-for-slot (row p of slot i holds the draft's K/V of
        sequence i's token at position p). None without speculation."""
        if self.draft_model is None:
            return None
        return zero_kv_caches(self.draft_model, self.num_slots, self.max_len)

    def _zero_small(self, model=None):
        """Fresh batch-1 caches for one chunked prefill (of `model`,
        default the target): chunks attend and write here (positions
        [0, filled)), and the finish step scatters the first max_len rows
        into the sequence's pool slot in one update. The extra chunk-1
        SLACK rows absorb the final chunk's fixed-width padded write: the
        last chunk always dispatches at full chunk width starting as late
        as position plen-1 <= max_len-1, and without the slack
        `dynamic_update_slice` would CLAMP that write at the array edge,
        silently shifting real prompt K/V rows (pinned by
        tests/test_prefix_cache.py::test_chunked_prefill_last_chunk_never_clamps).
        A window op's holder is its ring, as the pool's: its op places the
        chunk's real rows alone, so it takes no slack."""
        return zero_kv_caches(
            model if model is not None else self.model, 1, self.max_len,
            slack=max(0, self.prefill_chunk_tokens - 1))

    def _build_fns(self):
        import jax
        import jax.numpy as jnp

        model = self.model
        executor = model.executor
        final_guid = model.final_tensor.guid
        input_name = model.input_ops[0].name
        max_len = self.max_len
        attn_names = [op.name for op in self.attn_ops]
        seq_parts = self._seq_parts
        counter_names = sorted(self._op_counters)
        counter_vars = {name: tuple(self._op_counters[name])
                        for name in counter_names}
        temperature, top_k = self.temperature, self.top_k

        from ..generate import sampling_logits

        def pick_row(probs_row, pos, key):
            """Next token from one row's (V,) distribution — the per-row
            mirror of GenerativeSession._pick (same sampling_logits policy
            core): greedy at temperature<=0, else categorical at
            fold_in(key, pos), so a request's tokens depend only on its
            own (seed, position), never on which slots it shares the
            iteration with."""
            if temperature <= 0.0:
                return jnp.argmax(probs_row, axis=-1).astype(jnp.int32)
            logits = sampling_logits(probs_row, temperature, top_k)
            return jax.random.categorical(
                jax.random.fold_in(key, pos), logits).astype(jnp.int32)

        def small_caches(big):
            return {
                name: {part: jnp.zeros((1,) + arr.shape[1:], arr.dtype)
                       for part, arr in big[name].items()}
                for name in attn_names
            }

        def prefill_one(params, state, caches, tokens, slot, plen, key):
            """Prefill ONE request (tokens: (1, window), prompt in the
            first plen positions) into pool slot `slot`: run the batch-1
            forward with fresh batch-1 caches — past the last caching op
            for row plen-1 alone — then scatter the filled rows into the
            slot-dense pool caches and pick the first token (the same
            _scatter_and_pick the fused chunked finish uses)."""
            st = {**state, **small_caches(caches)}
            values, new_state, _ = executor.forward_values(
                params, st, {input_name: tokens}, None,
                CompMode.COMP_MODE_INFERENCE, fill_kv_cache=True,
                valid_len=plen, final_row=plen - 1)
            small = op_states(new_state, attn_names)
            return _scatter_and_pick(caches, small, slot,
                                     values[final_guid][0, 0], plen - 1, key)

        def decode_all(params, state, caches, toks, pos, keys):
            """One decode iteration over EVERY slot: toks (S,) last tokens,
            pos (S,) per-slot write positions, keys (S, 2) per-request PRNG
            keys. Inactive slots carry dummy operands; their outputs are
            discarded host-side. Also handed back: the state of the ops
            that count (`Op.serving_counters`), which the caller threads
            into the next iteration's `state`. A caching op that also
            counts keeps its counters beside its cache arrays."""
            st = {**state, **{name: {**state.get(name, {}), **caches[name]}
                              for name in attn_names}}
            values, new_state, _ = executor.forward_values(
                params, st, {input_name: toks[:, None]}, None,
                CompMode.COMP_MODE_INFERENCE, decode_pos=pos)
            probs = values[final_guid][:, 0, :]  # (S, V)
            with jax.named_scope("sample:pick"):
                next_tok = jax.vmap(pick_row)(probs, pos, keys)
            return (next_tok,
                    {name: {part: new_state[name][part]
                            for part in caches[name]}
                     for name in attn_names},
                    {name: {var: new_state[name][var]
                            for var in counter_vars[name]}
                     for name in counter_names})

        def chunk_forward(executor_, input_name_, attn_names_, params,
                          state, small, tokens, off, row, valid=None):
            """The chunk-offset forward shared by TARGET and DRAFT
            prefill: run C tokens at prompt offset `off` through the
            chunk-offset decode entry (ops/attention.py _decode_step,
            scalar pos, C queries) against batch-1 caches, and what
            follows the last caching op for the chunk's position `row`
            alone (NO_ROW: not at all — a chunk that is not the prompt's
            last has no position anybody reads); returns (tensor values —
            the final one (1, 1, V) where a row was asked for, absent
            otherwise —, updated caches). Padded tail positions of the
            last chunk write garbage rows at positions >= plen —
            harmless to a per-token cache, because decode overwrites row p
            before any query can attend it; per-sequence state is kept
            clear of them by `valid`, the chunk's count of real tokens
            (None: all of them)."""
            st = {**state, **small}
            values, new_state, _ = executor_.forward_values(
                params, st, {input_name_: tokens}, None,
                CompMode.COMP_MODE_INFERENCE, decode_pos=off,
                valid_len=valid, final_row=row)
            return values, op_states(new_state, attn_names_)

        def scatter_span(pool_caches, small, slot, attn_names_):
            """Batch-1 -> pool-slot install, shared by the target's
            fused finish AND the draft's: each per-token array's first
            max_len rows (the batch-1 caches carry chunk-1 slack rows, see
            _zero_small, that must not spill into the pool slot), a ring
            whole, and each per-sequence array whole — which is what
            resets a reused slot's state."""
            out = {}
            with jax.named_scope("kv:scatter_span"):
                for name in attn_names_:
                    out[name] = install_slot(
                        pool_caches[name], small[name], slot,
                        seq_parts.get(name, ()))
            return out

        def prefill_chunk(params, state, small, tokens, off):
            """One chunked-prefill step for ONE request that is not its
            last: returns the updated batch-1 caches, and no
            distribution."""
            _, new_small = chunk_forward(
                executor, input_name, attn_names, params, state, small,
                tokens, off, NO_ROW)
            return new_small

        def _scatter_and_pick(caches, small, slot, probs_row, pos, key):
            new_caches = scatter_span(caches, small, slot, attn_names)
            with jax.named_scope("sample:pick"):
                tok = pick_row(probs_row, pos, key)   # from (V,)
            return tok, new_caches

        def prefill_last_chunk(params, state, caches, small, tokens, off,
                               slot, idx, pos, key):
            """The FUSED final prefill step: run the last chunk, scatter
            the request's whole batch-1 cache span into its pool slot,
            and pick the first output token — one dispatch, so a prompt
            that fits a single chunk prefills as cheaply as the one-shot
            path did."""
            values, new_small = chunk_forward(
                executor, input_name, attn_names, params, state, small,
                tokens, off, idx, valid=idx + 1)
            return _scatter_and_pick(caches, new_small, slot,
                                     values[final_guid][0, 0], pos, key)

        def install_prefix(small, band, src_slot, src_row, n_rows):
            """Prefix-cache HIT: gather the matched band pages' K/V rows
            (src_slot/src_row: (max_len,) per-destination-row coordinates,
            real for rows < n_rows) into the leading rows of a fresh
            batch-1 prefill cache — the device-side copy that replaces
            recomputing the prefix."""
            keep = (jnp.arange(max_len) < n_rows)[:, None]
            out = {}
            with jax.named_scope("kv:prefix"):
                for name in attn_names:
                    # band rows gathered: (M, e); sm: (1, max_len + slack,
                    # e). Update the first max_len rows; the slack tail
                    # (see _zero_small) passes through untouched
                    out[name] = {
                        part: write_slot_span(sm, jnp.where(
                            keep, band[name][part][src_slot, src_row],
                            sm[0, :max_len])[None], 0)
                        for part, sm in small[name].items()
                    }
            return out

        def insert_pages(band, caches, slot, src_rows, dst_slots, dst_rows):
            """Prefix-cache INSERT: copy every new page of a finished
            prefill from its pool slot into band pages in ONE dispatch.
            The coordinate arrays have a FIXED shape (full_pages_per_slot
            * page_size rows — the caller pads by repeating the last real
            page, an idempotent scatter) so the function compiles exactly
            once. Band pages are written exactly once, before their
            entries become matchable — the immutability half of CoW."""
            new_band = {}
            with jax.named_scope("kv:prefix"):
                for name in attn_names:
                    new_band[name] = {
                        part: arr.at[dst_slots, dst_rows].set(
                            caches[name][part][slot, src_rows])
                        for part, arr in band[name].items()
                    }
            return new_band

        # donate the pool caches: the scheduler always threads the newest
        # ones through, so XLA updates them in place. Each program's first
        # call is timed (`first_call`) and leaves the bare jitted function
        # on its attribute.
        def program(attr, fn, donate):
            setattr(self, attr, first_call(
                jax.jit(fn, donate_argnums=donate), fn.__name__, self,
                attr))

        program("_prefill_fn", prefill_one, (2,))
        program("_decode_fn", decode_all, (2,))
        program("_chunk_fn", prefill_chunk, (2,))
        # (donating `small` here too would warn: the fused output has no
        # batch-1 cache to reuse the buffers for — they just die)
        program("_last_chunk_fn", prefill_last_chunk, (2,))
        program("_install_fn", install_prefix, (0,))
        program("_insert_fn", insert_pages, (0,))

        def import_span(caches, small, slot):
            """KV-handoff import (disagg): scatter a shipped sequence's
            padded (1, max_len) row span into pool slot `slot` — the same
            donated one-dispatch install the fused prefill finish uses,
            so an import stalls the decode loop no longer than a chunk
            scatter does (per-array eager updates would serialize the
            dispatch queue once per cache array)."""
            return scatter_span(caches, small, slot, attn_names)

        program("_import_fn", import_span, (0,))

        if self.draft_model is None:
            return
        # -- speculative decoding (draft + fused multi-query verify) ----
        draft = self.draft_model
        dexecutor = draft.executor
        dfinal_guid = draft.final_tensor.guid
        dinput_name = draft.input_ops[0].name
        dattn_names = [op.name for op in self.draft_attn_ops]
        k_spec = self.spec_tokens

        def draft_chunk(dparams, dstate, small, tokens, off):
            """One draft prefill chunk — `prefill_chunk` for the draft
            model (only the K/V matter)."""
            _, new_small = chunk_forward(
                dexecutor, dinput_name, dattn_names, dparams, dstate,
                small, tokens, off, NO_ROW)
            return new_small

        def draft_last_chunk(dparams, dstate, dcaches, small, tokens,
                             off, slot):
            """The draft's FINAL prefill chunk fused with the scatter of
            its whole batch-1 cache span into its pool slot — the
            pick-free sibling of `prefill_last_chunk`."""
            _, new_small = chunk_forward(
                dexecutor, dinput_name, dattn_names, dparams, dstate,
                small, tokens, off, NO_ROW)
            return scatter_span(dcaches, new_small, slot, dattn_names)

        def spec_decode_all(params, state, caches, dparams, dstate,
                            dcaches, toks, pos):
            """One SPECULATIVE decode iteration over every slot, ONE
            dispatch: the draft proposes `k_spec` greedy tokens per slot
            (unrolled autoregressive steps over its own caches), the
            target scores the pending token plus all proposals in one
            fused multi-query decode (C = k_spec+1), and the longest
            matching prefix is accepted.

            Emission is CAPPED at k_spec tokens (the classic k+1 bonus
            on full acceptance is traded away) so the draft's cache
            stays exactly one token behind the target's: the next
            iteration's first draft step consumes exactly `last_tok`,
            keeping every dispatch shape fixed. Rejected proposals'
            cache rows (target rows pos+m..pos+k, draft rows
            pos+m..pos+k-1) are never cleaned: the write-back pointer
            just does not advance over them, the causal mask hides them,
            and the next iteration's writes land on top of them before
            any query can attend that far.

            Returns (emitted (S, k_spec) target tokens — first counts[i]
            valid per slot, counts (S,), n_acc (S,) raw verify matches
            BEFORE the emission cap — the acceptance-rate numerator,
            new target caches, new draft caches)."""
            cur = toks
            dc = dcaches
            props = []
            for j in range(k_spec):
                st = {**dstate,
                      **{name: dict(dc[name]) for name in dattn_names}}
                values, new_state, _ = dexecutor.forward_values(
                    dparams, st, {dinput_name: cur[:, None]}, None,
                    CompMode.COMP_MODE_INFERENCE, decode_pos=pos + j)
                cur = jnp.argmax(values[dfinal_guid][:, 0, :],
                                 axis=-1).astype(jnp.int32)
                props.append(cur)
                dc = op_states(new_state, dattn_names)
            props = jnp.stack(props, axis=1)                  # (S, k)
            qtoks = jnp.concatenate([toks[:, None], props], axis=1)
            st = {**state,
                  **{name: dict(caches[name]) for name in attn_names}}
            values, new_state, _ = executor.forward_values(
                params, st, {input_name: qtoks}, None,
                CompMode.COMP_MODE_INFERENCE, decode_pos=pos)
            probs = values[final_guid]                        # (S, k+1, V)
            tgt = jnp.argmax(probs, axis=-1).astype(jnp.int32)
            # greedy accept: proposal j survives while every proposal
            # before it matched the target's own argmax at that position
            match = (props == tgt[:, :k_spec]).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            counts = jnp.minimum(n_acc + 1, k_spec)
            new_caches = op_states(new_state, attn_names)
            return tgt[:, :k_spec], counts, n_acc, new_caches, dc

        program("_draft_chunk_fn", draft_chunk, (2,))
        program("_draft_last_fn", draft_last_chunk, (2,))
        program("_spec_fn", spec_decode_all, (2, 5))

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        with self._cv:
            if self._running:
                return
            if self._thread is not None and self._thread.is_alive():
                # a previous stop() timed out with actives still draining:
                # a second loop thread would race on the donated caches
                raise RuntimeError(
                    "previous scheduler thread is still draining; cannot"
                    " restart until it exits")
            self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        """Stop accepting work. ACTIVE requests decode to completion (their
        pages are reserved, so they are bounded); QUEUED requests fail with
        BatcherStopped — the same typed-shutdown contract DynamicBatcher
        has."""
        with self._cv:
            self._running = False
            self._cv.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=60.0)
            if not t.is_alive():
                self._thread = None
            # else: keep the handle — start() must refuse to spawn a
            # second loop over the same (donated) cache arrays
        self._drain_queue(BatcherStopped("batcher stopped"))
        self._fail_pending_resize(BatcherStopped("batcher stopped"))
        self._fail_pending_handoffs(BatcherStopped("batcher stopped"))
        self._fail_parked(BatcherStopped("batcher stopped"))

    def abort(self, err: BaseException) -> None:
        """Non-blocking kill for a replica declared DEAD: fence every
        slotted request (freezing its emitted-token snapshot for the
        fleet's replay — see GenRequest._fence), fail queued work and
        any pending resize with `err`, and release the pool/admission
        state. Unlike stop() this never joins the scheduler thread — it
        may be hung inside a dispatch — so the thread is left to notice
        `_running=False` and exit on its own; its late emissions are
        fenced no-ops, and a late pool touch at worst kills the already
        condemned loop. start() still refuses to spawn a second loop
        while the old thread drains."""
        with self._cv:
            self._running = False
            slots, self._slots = list(self._slots), [None] * self.num_slots
            self._parked.clear()
            self._cv.notify_all()
        for s in slots:
            if s is None:
                continue
            self.pool.free(s.req.id)
            self.admission.release(s.req.id)
            self._failed += 1
            self._c_requests.inc(outcome="failed")
            s.req._fence(err)
        self._drain_queue(err)
        self._fail_pending_resize(err)
        self._fail_pending_handoffs(err)
        self._g_active.set(0, pool=self.pool.label)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- client API --------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int,
               eos_id: Optional[int] = None, seed: int = 0,
               prefill_only: bool = False) -> GenRequest:
        """Admit one request (prompt_ids: (L,) or (1, L) int tokens).
        Raises an AdmissionError subclass on rejection; otherwise returns
        a GenRequest whose stream()/result() deliver the tokens.

        prefill_only (implied by role='prefill'): the request runs its
        prefill and emits its FIRST token, then PARKS — KV resident,
        slot held, no decoding — for the fleet KV-handoff plane
        (`request_export` / `release_parked` / `resume_parked`)."""
        prefill_only = bool(prefill_only) or self.role == "prefill"
        if prefill_only and self.draft_model is not None:
            raise ValueError(
                "prefill_only cannot speculate: the draft's caches do"
                " not ship in the KV handoff")
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1:
            raise ValueError(
                "continuous batching takes ONE prompt per request —"
                f" expected shape (L,) or (1, L), got {prompt.shape}")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens}: need >= 1")
        rid = next(self._rid)
        # expected prefix sharing, credited against the admission backlog
        # budget (a probe, not a pin — the real match happens at schedule
        # time; the budget is a throttle, so a stale probe is harmless)
        shared_pages = 0
        if self.pool.prefix is not None:
            matched, _ = self.pool.prefix.match(prompt)
            shared_pages = min(matched, prompt.size - 1) // self.pool.page_size
        # expert signature outside the lock: one small host matmul
        sig = (self._affinity_probe.signature(prompt)
               if self._affinity_probe is not None else frozenset())
        with self._cv:
            if not self._running:
                raise BatcherStopped("batcher is not running")
            with get_tracer().span("serve.admit", request=rid):
                self.admission.admit(rid, prompt.size, max_new_tokens,
                                     shared_pages=shared_pages)
            req = GenRequest(rid, prompt, max_new_tokens, eos_id, seed)
            req.prefill_only = prefill_only
            # capture the caller's TraceContext as an explicit handoff:
            # the scheduler thread resumes it (None when tracing is off)
            req.trace = get_tracer().handoff("serve.submit")
            req.expert_sig = sig
            self._queue.append(req)
            self._cv.notify_all()
        return req

    def cancel(self, req: GenRequest) -> bool:
        """Best-effort cancel of a STILL-QUEUED request: removes it from
        the wait queue, releases its admission reservation, and fails it
        with RequestCancelled. Returns False when the request already
        reached a slot (or finished) — scheduled work runs to completion
        (its pages are owned; there is no mid-decode preemption path)."""
        with self._cv:
            try:
                self._queue.remove(req)
            except ValueError:
                return False
        self.admission.release(req.id)
        self._failed += 1
        self._c_requests.inc(outcome="cancelled")
        req._fail(RequestCancelled(f"request {req.id} cancelled"))
        return True

    def request_resize(self, num_slots: Optional[int] = None,
                       machine=None) -> ResizeTicket:
        """Resize the serving mesh capacity under load: give an explicit
        slot target OR a machine spec (the grown/shrunk mesh's chip),
        from which the target is derived through the same HBM model that
        sized the pool (`derive_num_slots`). The scheduler applies the
        resize between iterations — a shrink waits until live sequences
        fit the target (new admissions are held, nothing is dropped) —
        migrating every live sequence's OWNED cache rows into the new
        arrays, so in-flight requests keep decoding token-identically.
        Returns a ResizeTicket; `.wait()` blocks until applied."""
        self._refuse_unpaged("a live resize")
        if num_slots is None and machine is None:
            raise ValueError("give num_slots or a machine spec")
        if num_slots is None:
            num_slots = max(1, derive_num_slots(self.model, self.max_len,
                                                machine=machine)
                            - self.pool.band_slots)
        target = int(num_slots)
        if target < 1:
            raise ValueError(f"num_slots={target}: need >= 1")
        ticket = ResizeTicket(target)
        with self._cv:
            if not self._running:
                raise BatcherStopped("batcher is not running")
            if (self._pending_resize is not None
                    and not self._pending_resize.done()):
                raise RuntimeError("a resize is already pending")
            self._pending_resize = ticket
            self._cv.notify_all()
        return ticket

    # -- disaggregated KV handoff (serving/fleet/disagg.py) ----------------
    # The prefill side parks finished requests (`_first_token`); the
    # coordinator then drives: request_export -> ship rows -> the decode
    # replica's request_import -> release_parked (or resume_parked on any
    # failure). Export/import run on the scheduler thread between
    # iterations — the cache arrays are jit-donated, so no other thread
    # may read or write them (the _maybe_resize rule).

    def parked_requests(self) -> List[GenRequest]:
        with self._cv:
            return [s.req for s in self._parked.values()]

    def request_export(self, req: GenRequest) -> HandoffTicket:
        """Schedule a host-side export of a PARKED request's finished KV
        rows. Resolves with {"desc", "rows", "plen", "last_tok",
        "bytes"}: `desc` is the pool's geometry-checked page descriptor
        (`PagedKVPool.export_sequence`), `rows` maps "op/part" to the
        (plen, heads*dim) host array of exactly the rows the page table
        owns. The request STAYS parked — a failed ship can still
        resume_parked with nothing lost."""
        self._refuse_unpaged("KV export")
        ticket = HandoffTicket()
        with self._cv:
            if not self._running:
                raise BatcherStopped("batcher is not running")
            self._pending_handoffs.append(("export", ticket, req))
            self._cv.notify_all()
        return ticket

    def request_import(self, desc: Dict, rows: Dict, prompt,
                       last_tok: int, max_new_tokens: int,
                       eos_id: Optional[int] = None, seed: int = 0,
                       trace=None) -> HandoffTicket:
        """Schedule the decode-entry IMPORT of a shipped sequence: the
        scheduler installs `rows` into a freshly allocated slot and the
        request enters DECODE with ZERO recompute — `max_new_tokens` is
        the REMAINING budget (the prefill side already emitted the first
        token), `last_tok` seeds the first decode step, and greedy/
        per-request-keyed sampling make the continuation token-identical
        to unified serving (decode is a pure function of cache rows,
        absolute positions and the request's own seed). Resolves with
        the new GenRequest; fails typed — AdmissionError subclasses when
        this replica sheds, `KVGeometryMismatch` when the exporter's
        page regime differs (kvpool.py)."""
        self._refuse_unpaged("KV import")
        ticket = HandoffTicket()
        payload = {"desc": desc, "rows": rows,
                   "prompt": np.asarray(prompt, np.int32),
                   "last_tok": int(last_tok),
                   "max_new_tokens": int(max_new_tokens),
                   "eos_id": eos_id, "seed": int(seed), "trace": trace}
        with self._cv:
            if not self._running:
                raise BatcherStopped("batcher is not running")
            self._pending_handoffs.append(("import", ticket, payload))
            self._cv.notify_all()
        return ticket

    def resume_parked(self, req: GenRequest) -> bool:
        """Fallback: convert a PARKED request back to local decoding
        (the replica degrades to unified for this request). Zero-drop
        safety net for every handoff failure mode — no decode replica,
        shed on import, geometry mismatch, coordinator crash. Returns
        False when the request is no longer parked (already released,
        failed over, or resumed)."""
        with self._cv:
            s = self._parked.pop(req.id, None)
            if s is None or req.state is not RequestState.PARKED:
                return False
            req.state = RequestState.DECODE
            self._cv.notify_all()
        return True

    def release_parked(self, req: GenRequest) -> bool:
        """The handoff COMMITTED on the decode side: free the parked
        request's slot, pages and admission reservation here, and close
        the local handle with `RequestCancelled` — NOT a clean finish.
        The caller's FleetRequest has already rebound to the decode
        continuation, and it treats RequestCancelled as
        "await the rebind": a consumer blocked on THIS handle wakes,
        sees the typed error, and retries on the new incarnation. A
        clean _finish() would instead read as a complete 1-token answer
        to any consumer that snapshotted before the rebind. Returns
        False when the request is no longer parked."""
        with self._cv:
            s = self._parked.pop(req.id, None)
            if s is None:
                return False
            self._slots[s.slot] = None
        self.pool.free(req.id)
        self.admission.release(req.id)
        self._completed += 1
        self._c_requests.inc(outcome="handed_off")
        self._sync_active_gauge()
        req._fail(RequestCancelled(
            f"request {req.id} handed off to a decode replica"))
        with self._cv:
            self._cv.notify_all()
        return True

    def _runnable_locked(self) -> bool:
        """Any slot that still schedules work (caller holds _cv): PARKED
        slots hold pages but neither prefill nor decode."""
        return any(s is not None
                   and s.req.state is not RequestState.PARKED
                   for s in self._slots)

    def _process_handoffs(self, tracer) -> None:
        """Run queued export/import steps (scheduler thread only). A
        failing step fails ITS ticket — typed admission/geometry errors
        are the coordinator's routing signals, never loop kills."""
        with self._cv:
            work, self._pending_handoffs = self._pending_handoffs, []
        for kind, ticket, payload in work:
            try:
                if kind == "export":
                    ticket._finish(self._export_parked(payload, tracer))
                else:
                    ticket._finish(self._import_one(tracer, **payload))
            except Exception as e:
                ticket._fail(e)

    def _export_parked(self, req: GenRequest, tracer) -> Dict:
        """Gather a parked request's owned cache rows to host numpy
        (scheduler thread only — see _process_handoffs)."""
        with self._cv:
            s = self._parked.get(req.id)
        if s is None or req.state is not RequestState.PARKED:
            raise KeyError(f"request {req.id} is not parked")
        desc = self.pool.export_sequence(req.id)
        plen = int(s.plen)
        with tracer.resume(req.trace), \
                tracer.span("serve.kv_export", request=req.id,
                            tokens=plen):
            rows = {
                f"{name}/{part}": np.asarray(arr[s.slot, :plen])
                for name, pair in self._caches.items()
                for part, arr in pair.items()
            }
        return {"desc": desc, "rows": rows, "plen": plen,
                "last_tok": int(s.last_tok),
                "bytes": int(sum(r.nbytes for r in rows.values()))}

    def _import_one(self, tracer, desc, rows, prompt, last_tok,
                    max_new_tokens, eos_id, seed, trace) -> GenRequest:
        """Install a shipped sequence as a decode-entry request
        (scheduler thread only — see request_import for the contract)."""
        import jax
        import jax.numpy as jnp

        from .kvpool import KVGeometryMismatch

        plen = int(desc["n_tokens"])
        for name, pair in self._caches.items():
            for part, arr in pair.items():
                src = rows.get(f"{name}/{part}")
                want = (plen,) + tuple(int(d) for d in arr.shape[2:])
                if src is None or tuple(src.shape) != want:
                    raise KVGeometryMismatch(
                        f"kv_rows[{name}/{part}]",
                        None if src is None else tuple(src.shape), want)
        rid = next(self._rid)
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens={max_new_tokens}: an import with no"
                " remaining budget has nothing to decode")
        self.admission.admit(rid, plen, max_new_tokens)
        try:
            slot_idx = self.pool.import_sequence(desc, seq_id=rid)
        except BaseException:
            self.admission.release(rid)
            raise
        req = GenRequest(rid, np.asarray(prompt, np.int32),
                         max_new_tokens, eos_id, seed)
        req.trace = trace
        # the KV arrived fully materialized: admission must never charge
        # this request a prefill leg (predicted_ttft_s, own == 0)
        req.cache_hit = True
        req.prefix_tokens = plen
        req.queue_wait_s = self.admission.on_scheduled(rid)
        key = np.asarray(jax.random.PRNGKey(seed), np.uint32)
        s = _Slot(req, slot_idx, key)
        s.plen = s.filled = s.pos = plen
        s.last_tok = int(last_tok)
        with tracer.resume(trace), \
                tracer.span("serve.kv_import", request=rid, tokens=plen):
            # pad each shipped span to (1, max_len) rows and scatter the
            # whole slot in ONE jitted donated dispatch (rows past plen
            # are zeros — stale by definition, decode overwrites row plen
            # before any query can attend it)
            small = {}
            for name, pair in self._caches.items():
                sm = {}
                for part, arr in pair.items():
                    pad = np.zeros(
                        (1, self.max_len)
                        + tuple(int(d) for d in arr.shape[2:]),
                        dtype=arr.dtype)
                    pad[0, :plen] = rows[f"{name}/{part}"]
                    sm[part] = jnp.asarray(pad)
                small[name] = sm
            self._caches = self._import_fn(self._caches, small, slot_idx)
        req.state = RequestState.DECODE
        req.t_first_token = time.monotonic()
        with self._cv:
            self._slots[slot_idx] = s
            self._cv.notify_all()
        self._sync_active_gauge()
        return req

    def _fail_pending_handoffs(self, err: BaseException) -> None:
        with self._cv:
            work, self._pending_handoffs = self._pending_handoffs, []
        for _, ticket, _ in work:
            if not ticket.done():
                ticket._fail(err)

    def _fail_parked(self, err: BaseException) -> None:
        """Fail every still-parked request (stop/crash paths): fence so
        the fleet replay sees the frozen first-token snapshot, release
        the pool and admission state."""
        with self._cv:
            parked, self._parked = dict(self._parked), {}
            for s in parked.values():
                if self._slots[s.slot] is s:
                    self._slots[s.slot] = None
        for s in parked.values():
            self.pool.free(s.req.id)
            self.admission.release(s.req.id)
            self._failed += 1
            self._c_requests.inc(outcome="failed")
            s.req._fence(err)
        if parked:
            self._sync_active_gauge()

    # -- fleet probes ------------------------------------------------------
    # The router tier (serving/fleet/) routes and sheds on these three
    # read-only probes; they take no scheduler locks beyond the condition
    # variable and never touch device state.
    _EWMA_ALPHA = 0.25

    def _observe_prefill(self, n_tokens: int, dt: float) -> None:
        """One synced prefill dispatch covered `n_tokens` in `dt` seconds
        (scheduler thread only)."""
        if n_tokens <= 0 or dt <= 0:
            return
        sample = dt / n_tokens
        old = self._ewma_prefill_s_per_tok
        self._ewma_prefill_s_per_tok = sample if old is None else \
            (1 - self._EWMA_ALPHA) * old + self._EWMA_ALPHA * sample
        self._g_prefill_rate.set(
            1.0 / self._ewma_prefill_s_per_tok, pool=self.pool.label)

    def _observe_draft_prefill(self, n_tokens: int, dt: float) -> None:
        """One synced DRAFT prefill dispatch covered `n_tokens` in `dt`
        seconds (scheduler thread only) — the measured cost of the
        doubled prefill work speculation adds per prompt token."""
        if n_tokens <= 0 or dt <= 0:
            return
        sample = dt / n_tokens
        old = self._ewma_draft_prefill_s_per_tok
        self._ewma_draft_prefill_s_per_tok = sample if old is None else \
            (1 - self._EWMA_ALPHA) * old + self._EWMA_ALPHA * sample

    def _observe_decode_iter(self, dt: float) -> None:
        """One decode iteration took `dt` seconds of wall (scheduler
        thread only). The EWMA stays the RAW per-iteration wall — a
        prefill chunk interleaved between decode iterations waits one
        FULL iteration, so the `predicted_ttft_s` interference leg needs
        walls; the speculative accepted-token accounting enters that
        model as a cap on HOW MANY walls a prefill can collide with
        (`_decode_drain_iterations`), never by shrinking the wall."""
        if dt <= 0:
            return
        old = self._ewma_decode_iter_s
        self._ewma_decode_iter_s = dt if old is None else \
            (1 - self._EWMA_ALPHA) * old + self._EWMA_ALPHA * dt
        self._g_decode_iter.set(self._ewma_decode_iter_s * 1e3,
                                pool=self.pool.label)

    # health probes (serving/fleet/health.py): liveness, heartbeat age,
    # and the busy-gap step-latency EWMA the straggler score reads.
    _STEP_EWMA_ALPHA = 0.3   # mirrors elastic/detector.py
    _STEP_WARMUP = 2

    def scheduler_alive(self) -> bool:
        """True while the scheduler thread exists and runs — False after
        a crash (_fail_all leaves a dead thread) or a clean stop."""
        t = self._thread
        return t is not None and t.is_alive()

    def heartbeat_age_s(self) -> Optional[float]:
        """Seconds since the scheduler last passed the top of its loop
        (None before the first iteration). The idle wait wakes at least
        every 0.1 s, so an age of seconds means a hung dispatch or a
        stalled host thread, never merely an empty queue."""
        t = self._t_heartbeat
        return None if t is None else max(0.0, time.monotonic() - t)

    def step_latency_s(self) -> Optional[float]:
        """EWMA wall between consecutive busy scheduler iterations
        (None until warmed up) — the fleet HealthMonitor's straggler
        signal, scored against the fleet median."""
        return self._ewma_step_s

    def reset_latency(self) -> None:
        """Forget the step-latency baseline and re-enter warmup — the
        FailureDetector.reset_latency contract: after a respawn/resize
        the first iterations recompile and would otherwise flag the
        recovered replica as a straggler."""
        self._ewma_step_s = None
        self._step_warmup = 0
        self._t_iter_prev = None

    def _observe_step_gap(self, dt: float) -> None:
        if dt <= 0:
            return
        if self._step_warmup < self._STEP_WARMUP:
            self._step_warmup += 1
            return
        old = self._ewma_step_s
        self._ewma_step_s = dt if old is None else \
            (1 - self._STEP_EWMA_ALPHA) * old + self._STEP_EWMA_ALPHA * dt

    def prefix_probe(self, prompt_ids) -> int:
        """Tokens of `prompt_ids` THIS batcher's prefix cache would
        install from already-resident pages (probe only — no pin, no
        hit/miss accounting; 0 when prefix reuse is off). The fleet
        router's affinity signal: the replica with the deepest probe
        already owns the prompt's shared prefix."""
        if self.pool.prefix is None:
            return 0
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        matched, _ = self.pool.prefix.match(prompt)
        return int(min(matched, max(prompt.size - 1, 0)))

    def prefix_probe_chain(self, chain, prompt_len: int) -> int:
        """`prefix_probe` against a PRECOMPUTED `prefix_route_chain`: the
        fleet router hashes each prompt once and probes every replica
        with the same chain (PrefixCache.match_chain), so an N-replica
        probe costs N dict walks, not N full-prompt re-hashings."""
        if self.pool.prefix is None or not chain:
            return 0
        matched = self.pool.prefix.match_chain(chain) * self.pool.page_size
        return int(min(matched, max(int(prompt_len) - 1, 0)))

    def prefill_backlog_s(self) -> float:
        """Queued prefill work in seconds at the MEASURED prefill rate
        (0.0 until the EWMA calibrates) — the prefill pool's saturation
        currency for the role-scoped autoscaler: a prefill replica's
        overload shows up as backlog-seconds growth long before its
        pages fill (parked requests hold pages briefly; the queue is
        where pressure accumulates)."""
        per_tok = self._ewma_prefill_s_per_tok
        if per_tok is None:
            return 0.0
        return self.queued_prefill_tokens() * per_tok

    def queued_prefill_tokens(self) -> int:
        """Prompt tokens admitted but not yet prefilled: the whole wait
        queue plus the unfilled remainder of every slot still in the
        PREFILL state — the backlog term of `predicted_ttft_s`."""
        with self._cv:
            backlog = sum(int(r.prompt.size) for r in self._queue)
            for s in self._slots:
                if s is not None and s.req.state is RequestState.PREFILL:
                    backlog += max(0, s.plen - s.filled)
        return backlog

    def predicted_ttft_s(self, prompt_len: int,
                         shared_tokens: int = 0) -> float:
        """Predicted time-to-first-token for a NEW request of
        `prompt_len` tokens, from the measured rate model:

            (backlog + own) tokens x EWMA per-token prefill cost
          + ceil((backlog + own) / chunk) x EWMA decode-iteration wall

        The first term is the queue-depth x measured-prefill-rate leg
        (own = prompt minus `shared_tokens` the prefix cache would
        install); the second is the chunk-interleave model — chunked
        prefill runs one decode iteration between chunks whenever
        anything is decoding, so every pending chunk costs one decode
        wall on top of its own compute. With a draft model the prefill
        leg additionally credits the draft's doubled prefill dispatches
        at the draft's own measured per-token cost. A cold batcher (no
        samples yet) predicts 0 and admits — the estimate only starts
        shedding once it is backed by measurements.

        own = 0 — a request whose KV is ALREADY materialized (a
        prefix-band hit covering the whole prompt, or a disaggregated
        KV import) — is admitted on the decode legs only: charging it
        the prefill-EWMA leg would shed servable traffic. A replica
        with role='prefill' conversely charges NO decode leg: nothing
        decodes there (parked requests hold pages, not iterations), so
        the chunk-interleave term is structurally zero."""
        own = max(0, int(prompt_len) - max(0, int(shared_tokens)))
        backlog = self.queued_prefill_tokens()
        total = own + backlog
        per_tok = self._ewma_prefill_s_per_tok
        t = total * per_tok if per_tok is not None else 0.0
        if self.draft_model is not None:
            # draft-aware admission (docs/serving.md): speculation
            # prefills every prompt token TWICE — the draft's chunk
            # stream runs beside the target's — so the prefill leg
            # credits the second dispatch at the draft's measured
            # per-token cost (falling back to the target's until the
            # first draft sample lands; prefix-cache credit does not
            # apply — the draft re-prefills even on a band hit)
            draft_per_tok = self._ewma_draft_prefill_s_per_tok
            if draft_per_tok is None:
                draft_per_tok = per_tok
            if draft_per_tok is not None:
                t += (int(prompt_len) + backlog) * draft_per_tok
        chunk = self.prefill_chunk_tokens
        iter_s = self._ewma_decode_iter_s
        if own == 0 and iter_s is not None:
            # fully materialized KV: its first emission rides the next
            # decode wall — the only latency it is honestly owed
            t += iter_s
        if chunk and iter_s is not None and self.role != "prefill":
            with self._cv:
                interleaved = len(self._queue) > 0 or any(
                    s is not None and s.req.state is RequestState.DECODE
                    for s in self._slots)
            if interleaved:
                import math as _math

                iters = _math.ceil(total / chunk)
                if self.spec_tokens:
                    # speculative accounting: count ACCEPTED TOKENS per
                    # iteration, not iterations. Each decode wall
                    # retires ~k_eff tokens per slot, so decoders drain
                    # up to spec_tokens x sooner and chunks past the
                    # drain horizon pay no decode wall — without this
                    # cap the fatter speculative iteration wall
                    # over-predicts TTFT and sheds servable traffic
                    iters = min(iters, self._decode_drain_iterations())
                t += iters * iter_s
        return t

    def _decode_drain_iterations(self) -> int:
        """Decode iterations left before every live request's token
        budget drains at the MEASURED accepted-token rate (k_eff =
        1 + acceptance x spec_tokens, capped at spec_tokens — the
        per-iteration emission ceiling). Queued and prefilling requests
        count at their full budget: they will be decoding inside the
        prediction window — and because they SERIALIZE through the slot
        pool, the horizon is bounded below by the TOTAL remaining work
        over the pool's per-iteration throughput (slots x k_eff), not
        just the longest single budget. The cap for
        `predicted_ttft_s`'s chunk-interleave leg under speculation."""
        import math as _math

        k_eff = 1.0
        if self.spec_tokens:
            acc = self._ewma_spec_accept or 0.0
            k_eff = min(float(self.spec_tokens),
                        1.0 + acc * self.spec_tokens)
        k_eff = max(1.0, k_eff)
        with self._cv:
            budgets = [s.req.max_new_tokens - s.emitted
                       for s in self._slots if s is not None]
            budgets += [r.max_new_tokens for r in self._queue]
        budgets = [b for b in budgets if b > 0]
        if not budgets:
            return 0
        longest = _math.ceil(max(budgets) / k_eff)
        pooled = _math.ceil(sum(budgets)
                            / (max(1, self.num_slots) * k_eff))
        return max(longest, pooled)

    def stats(self) -> Dict[str, object]:
        with self._cv:
            active = sum(1 for s in self._slots if s is not None)
            queued = len(self._queue)
            parked = len(self._parked)
        out = {
            "queue_depth": queued,
            "slots_active": active,
            "role": self.role,
            "parked": parked,
            "completed": self._completed,
            "failed": self._failed,
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "num_slots": self.num_slots,
            "prefill_s_per_token": self._ewma_prefill_s_per_tok,
            "draft_prefill_s_per_token": self._ewma_draft_prefill_s_per_tok,
            "decode_iter_s": self._ewma_decode_iter_s,
            "step_latency_s": self._ewma_step_s,
            "tokens_emitted": self.tokens_emitted,
            "queued_prefill_tokens": self.queued_prefill_tokens(),
            "resizes": list(self._resizes),
            "pool": self.pool.stats(),
            "admission": self.admission.stats(),
        }
        if self.draft_model is not None:
            out["spec"] = {
                "tokens": self.spec_tokens,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "acceptance": (self._spec_accepted / self._spec_proposed
                               if self._spec_proposed else 0.0),
                "acceptance_ewma": self._ewma_spec_accept,
            }
        if self._affinity_probe is not None:
            out["affinity"] = {
                "window": self.affinity_window,
                "overlap_ewma": self._ewma_affinity_overlap,
                "picks": {outcome: int(v) for (outcome,), v
                          in self._c_affinity.items()},
            }
        return out

    def op_counters(self) -> Dict[str, Dict[str, object]]:
        """{op name: {counter: host value}} of the ops that count
        (`Op.serving_counters`) as the last finished decode iteration left
        them; fetches from the device, so it is for whoever asks (a
        dashboard's scrape, the benchmark), not for the loop."""
        import jax

        got = jax.device_get(self._op_counters)
        for name, parts in self._seq_parts.items():
            if parts:
                got.setdefault(name, {})["state_resets"] = self._state_resets
        return got

    def publish_op_counters(self) -> Dict:
        """Mirror the counting ops' state into the registry: the expert
        layers' into the `ff_moe_*` families (obs/moe.py), whose numbers
        are returned, the attentions' rows filled and read into
        `ff_mla_*` / `ff_attn_*` (obs/attention_rows.py) and the
        state-space mixers' state traffic into `ff_ssm_*` (obs/ssm.py)."""
        from ...obs.attention_rows import publish_attention_row_metrics
        from ...obs.moe import publish_moe_metrics
        from ...obs.ssm import publish_ssm_metrics

        state = self.op_counters()
        publish_attention_row_metrics(self.model, self.registry, state=state)
        publish_ssm_metrics(self.model, self.registry, state=state)
        return publish_moe_metrics(self.model, self.registry, state=state)

    # -- scheduler loop ----------------------------------------------------
    def _idle_locked(self) -> bool:
        """Nothing to schedule (caller holds _cv). PARKED slots hold KV
        for the fleet handoff plane but schedule nothing — they must not
        keep the loop spinning hot, nor block a clean stop (stop() fails
        them after the join)."""
        return (self._running and not self._queue
                and not self._runnable_locked()
                and self._pending_resize is None
                and not self._pending_handoffs)

    def _loop(self) -> None:
        tracer = get_tracer()
        tracer.set_thread_name(self.trace_label)
        params = self.model.params
        state = self.model.state
        n_iter = 0
        try:
            while True:
                with self._cv:
                    if self._idle_locked():
                        with tracer.span("serve.wait"):
                            while self._idle_locked():
                                # an idle loop is a HEALTHY loop: stamp
                                # the heartbeat on every 0.1 s wake so the
                                # monitor can tell "no work" from "hung
                                # dispatch"
                                self._t_heartbeat = time.monotonic()
                                self._cv.wait(timeout=0.1)
                    if not self._running and not self._runnable_locked():
                        break
                    running = self._running
                n_iter += 1
                # one pass = one parent span; every phase inside is a
                # child on this thread, so the parent's self time is the
                # loop's own overhead (and a fault hook's stall)
                with tracer.span("serve.iter", iter=n_iter) as it:
                    self._iterate(params, state, tracer, it, running)
        except BaseException as e:  # scheduler died: fail everything
            self._fail_all(e)
        finally:
            self._g_active.set(0, pool=self.pool.label)

    def _iterate(self, params, state, tracer, it, running: bool) -> None:
        """One pass of the scheduler loop under its `serve.iter` span
        `it`, which leaves with the pass's counters as args."""
        # health signals + chaos: stamp the heartbeat, sample the
        # busy-gap step latency (gaps after an iteration that HAD work —
        # so hook stalls and slow dispatches count, idle 0.1 s waits do
        # not), then run the fault hook: a raise kills the loop like any
        # scheduler bug, a sleep registers as a hang/straggle.
        now = time.monotonic()
        self._t_heartbeat = now
        if self._iter_had_work and self._t_iter_prev is not None:
            self._observe_step_gap(now - self._t_iter_prev)
        self._t_iter_prev = now
        self._iter_had_work = bool(self._queue) or any(self._slots)
        hook = self.fault_hook
        if hook is not None:
            hook(self)
        queue_depth = len(self._queue)
        emitted0, retired0 = self.tokens_emitted, self._retired

        # 0) apply a pending mesh resize (a shrink defers until live
        #    sequences fit; admissions are held meanwhile)
        if self._pending_resize is not None:
            self._maybe_resize(tracer)

        # 0b) disaggregated KV handoff steps (export parked rows / import
        #     shipped ones) — scheduler thread only, same donated-cache
        #     rule as the resize
        if self._pending_handoffs:
            self._process_handoffs(tracer)

        # 1) move queued requests into free slots (skipped once stopping:
        #    queued requests fail fast in stop()). In one-shot mode this
        #    runs the whole prefill; in chunked mode it only installs any
        #    cached prefix and arms the resumable PREFILL state.
        admitted = 0
        if running:
            with tracer.span("serve.schedule") as sp:
                admitted = self._admit_new(params, state, tracer)
                sp.set(admitted=admitted)

        # 2) one prefill chunk per PREFILLING slot — interleaved with
        #    decode so a long prompt costs in-flight decodes one chunk of
        #    latency per iteration, not its whole prefill
        prefill_slots, prefill_chunks, prefill_tokens = \
            self._step_prefills(params, state, tracer)

        # 3) one decode iteration over all DECODING slots
        with tracer.span("serve.decode_stage") as sp:
            active = [s for s in self._slots if s is not None
                      and s.req.state is RequestState.DECODE]
            sp.set(slots=len(active))
            if active:
                toks, pos, keys = self._stage_decode(active)
        if active and self.spec_tokens:
            self._spec_iterate(params, state, tracer, active, toks, pos)
        elif active:
            with tracer.span("serve.decode", slots=len(active)) as sp:
                if tracer.enabled:
                    sp.set(requests=[s.req.id for s in active])
                t0 = time.monotonic()
                with tracer.span("serve.decode_dispatch"):
                    next_tok, self._caches, self._op_counters = \
                        self._decode_fn(
                            params, {**state, **self._op_counters},
                            self._caches, toks, pos, keys)
                with tracer.span("serve.decode_fetch"):
                    next_tok = np.asarray(next_tok)  # sync
                self._observe_decode_iter(time.monotonic() - t0)
            with tracer.span("serve.emit") as sp:
                e0, r0 = self.tokens_emitted, self._retired
                now = time.monotonic()
                for s in active:
                    self._h_itl.observe((now - s.t_last_emit) * 1e3)
                    s.t_last_emit = now
                    self.pool.extend(s.req.id, 1)
                    s.pos += 1
                    self._emit_token(s, int(next_tok[s.slot]))
                sp.set(emitted=self.tokens_emitted - e0,
                       retired=self._retired - r0)
        it.set(queue_depth=queue_depth, decode_slots=len(active),
               prefill_slots=prefill_slots, admitted=admitted,
               prefill_chunks=prefill_chunks, prefill_tokens=prefill_tokens,
               emitted=self.tokens_emitted - emitted0,
               retired=self._retired - retired0)

    def _stage_decode(self, active):
        """The decode dispatch's operands, placed on the device: per slot
        the last token, the write position and the PRNG key (which the
        speculative step, greedy, does not take)."""
        import jax.numpy as jnp

        toks = np.zeros(self.num_slots, np.int32)
        pos = np.zeros(self.num_slots, np.int32)
        keys = np.zeros((self.num_slots, 2), np.uint32)
        for s in self._slots:
            if s is not None and s.req.state is not RequestState.DECODE:
                # the decode dispatch writes one KV row at `pos` for EVERY
                # slot, active or not. An owned but non-decoding slot
                # (PARKED awaiting handoff, mid-chunk PREFILL) must not
                # take that dummy write at row 0 of its live pages — aim
                # it at the slot's own next-write row instead: beyond
                # `filled`, never attended, and overwritten by the slot's
                # next real fill
                pos[s.slot] = min(int(s.pos), self.pool.max_len - 1)
        for s in active:
            if s.shared and s.pos < s.shared:
                # copy-on-write break: this decode writes inside pages
                # the sequence still shares. Its slot rows are already
                # the private copy, so only the share is severed —
                # unreachable with page-aligned matching (decode writes
                # at pos >= plen >= shared), but enforced, not assumed.
                self.pool.prefix.cow_break(s.req.id, s.pos)
                s.shared = (s.pos // self.pool.page_size
                            ) * self.pool.page_size
            toks[s.slot] = s.last_tok
            pos[s.slot] = s.pos
            keys[s.slot] = s.key
        return (jnp.asarray(toks), jnp.asarray(pos),
                None if self.spec_tokens else jnp.asarray(keys))

    def _spec_iterate(self, params, state, tracer, active, toks,
                      pos) -> None:
        """One SPECULATIVE decode iteration (scheduler thread only):
        draft-propose + fused multi-query verify in ONE dispatch
        (`spec_decode_all`), then host-side emission of each slot's
        accepted prefix. The write-back pointer (`s.pos`) advances only
        over accepted tokens — a rejected suffix is rolled back by NOT
        advancing it, never by touching the cache (its rows are masked
        out and rewritten before any later query can attend them), so
        other slots' pages are never involved. `toks` / `pos` are the
        staged device operands."""
        draft = self.draft_model
        with tracer.span("serve.spec_verify", slots=len(active),
                         k=self.spec_tokens):
            t0 = time.monotonic()
            with tracer.span("serve.decode_dispatch"):
                emitted, counts, n_acc, self._caches, self._draft_caches = \
                    self._spec_fn(params, state, self._caches, draft.params,
                                  draft.state, self._draft_caches, toks, pos)
            with tracer.span("serve.decode_fetch"):
                emitted = np.asarray(emitted)
                counts = np.asarray(counts)
                n_acc = np.asarray(n_acc)  # sync
            dt = time.monotonic() - t0
        with tracer.span("serve.emit") as sp:
            e0, r0 = self.tokens_emitted, self._retired
            # acceptance counts RAW verify matches (draft quality, not
            # the emission cap's m-1 — a perfect draft reads 1.0, not
            # (k-1)/k), but only proposals that could still MATTER: a
            # slot with r budget tokens left can use at most r-1
            # proposals, and queries past the budget (which is also the
            # cache edge, plen+max_new <= max_len) are garbage whose
            # argmax matches mean nothing
            proposed = accepted = 0
            for s in active:
                useful = min(self.spec_tokens,
                             s.req.max_new_tokens - s.emitted - 1)
                if useful <= 0:
                    continue
                proposed += useful
                accepted += min(int(n_acc[s.slot]), useful)
            self._spec_proposed += proposed
            self._spec_accepted += accepted
            self._c_spec_proposed.inc(proposed)
            self._c_spec_accepted.inc(accepted)
            if proposed:
                rate = accepted / proposed
                old = self._ewma_spec_accept
                self._ewma_spec_accept = rate if old is None else \
                    (1 - self._EWMA_ALPHA) * old + self._EWMA_ALPHA * rate
                self._g_spec_accept.set(self._ewma_spec_accept,
                                        pool=self.pool.label)
            self._observe_decode_iter(dt)
            now = time.monotonic()
            for s in active:
                m = int(counts[s.slot])
                for i in range(m):
                    self._h_itl.observe((now - s.t_last_emit) * 1e3)
                    s.t_last_emit = now
                    self.pool.extend(s.req.id, 1)
                    s.pos += 1
                    self._emit_token(s, int(emitted[s.slot, i]))
                    if s.req.state is not RequestState.DECODE:
                        break  # retired (EOS/budget): the rest of the
                        #        window is garbage past the sequence end
            sp.set(emitted=self.tokens_emitted - e0,
                   retired=self._retired - r0)

    def _maybe_resize(self, tracer) -> None:
        """Apply the pending resize (scheduler thread only). The
        migration is itself a resharding schedule: gated by the FFTA06x
        analysis family (old + new arrays coexist during the copy, so
        scratch = the new arrays' bytes vs HBM) and priced with the
        machine model's collective terms BEFORE any device work. Only
        rows the page tables still OWN are copied (`owned_view`) — a
        freed sequence's stale rows can never ship into the new arrays
        (asserted, and pinned by tests/test_mesh_resize.py)."""
        import jax.numpy as jnp

        ticket = self._pending_resize
        if ticket is None:
            return
        target = ticket.target_slots
        if target == self.num_slots:
            with self._cv:
                self._pending_resize = None
            ticket._finish({"from": target, "to": target,
                            "direction": "noop", "migrated_rows": 0,
                            "in_flight": 0, "predicted_us": 0.0,
                            "wall_ms": 0.0, "noop": True})
            return
        if self.pool.live_sequences() > target:
            return  # shrink defers until enough sequences finish
        direction = "shrink" if target < self.num_slots else "grow"
        t0 = time.monotonic()
        with tracer.span("serve.resize", slots_from=self.num_slots,
                         slots_to=target) as sp:
            from ...analysis import PlanAnalysisError, check_redistribution
            from ...resharding import plan_slot_migration, schedule_cost_us
            from ...resharding.plan import leaf_itemsize
            from ...search.machine_model import make_machine_model
            from .kvpool import PoolExhausted

            # the draft's slot-dense caches (speculative decoding) ride
            # the same migration: same slot map, same owned-row spans
            # (draft row p mirrors target row p), priced together
            cache_sets = [("kv", self._caches)]
            if self._draft_caches is not None:
                cache_sets.append(("draft_kv", self._draft_caches))
            kv_shapes = {
                f"{tag}/{name}/{part}": (tuple(int(d) for d in arr.shape),
                                         leaf_itemsize(arr.dtype))
                for tag, caches in cache_sets
                for name, pair in caches.items()
                for part, arr in pair.items()
            }
            live = [s for s in self._slots if s is not None]
            n_rows = sum(hi - lo
                         for s in live
                         for _, lo, hi in self.pool.owned_view(s.req.id))
            machine = make_machine_model(
                self.model.config, max(1, self.model.config.total_devices))
            schedule = plan_slot_migration(kv_shapes, self.num_slots,
                                           target, n_rows)
            try:
                check_redistribution(schedule, machine=machine)
            except PlanAnalysisError as err:
                with self._cv:
                    self._pending_resize = None
                ticket._fail(err)
                return
            predicted_us = schedule_cost_us(schedule, machine)
            try:
                moves = self.pool.resize(target)
            except PoolExhausted:
                return  # a request landed since the check: defer again
            # row coordinates, built ONLY from what the page tables own
            src_sl: List[np.ndarray] = []
            src_rw: List[np.ndarray] = []
            dst_sl: List[np.ndarray] = []
            dst_rw: List[np.ndarray] = []
            slot_map: Dict[object, int] = {}
            for seq_id, old_slot, new_slot, n_pages in moves:
                slot_map[seq_id] = new_slot
                owned_rows = 0
                for slot, lo, hi in self.pool.owned_view(seq_id):
                    # the stale-page guard: every copied row lies inside
                    # a page this sequence's table owns, in its slot
                    assert slot == new_slot and hi <= self.max_len, \
                        (seq_id, slot, new_slot, lo, hi)
                    src_sl.append(np.full(hi - lo, old_slot, np.int32))
                    src_rw.append(np.arange(lo, hi, dtype=np.int32))
                    dst_sl.append(np.full(hi - lo, new_slot, np.int32))
                    dst_rw.append(np.arange(lo, hi, dtype=np.int32))
                    owned_rows += hi - lo
                assert owned_rows <= n_pages * self.pool.page_size, \
                    (seq_id, owned_rows, n_pages)
            copied = int(sum(a.size for a in src_rw))
            if copied:
                c_src_sl = np.concatenate(src_sl)
                c_src_rw = np.concatenate(src_rw)
                c_dst_sl = np.concatenate(dst_sl)
                c_dst_rw = np.concatenate(dst_rw)
            # the device allocation + gather/scatter runs OUTSIDE the
            # lock (the cache arrays are touched only by this scheduler
            # thread); server threads keep submitting/reading stats while
            # the copy is in flight — only the pointer swap is locked
            def migrate(old_caches):
                new_caches: Dict[str, Dict[str, object]] = {}
                for name, pair in old_caches.items():
                    new_caches[name] = {}
                    for part, arr in pair.items():
                        buf = jnp.zeros((target,) + tuple(arr.shape[1:]),
                                        arr.dtype)
                        if copied:
                            buf = buf.at[c_dst_sl, c_dst_rw].set(
                                arr[c_src_sl, c_src_rw])
                        new_caches[name][part] = buf
                return new_caches

            new_caches = migrate(self._caches)
            new_draft = (migrate(self._draft_caches)
                         if self._draft_caches is not None else None)
            with self._cv:
                self._caches = new_caches
                self._draft_caches = new_draft
                new_slot_list: List[Optional[_Slot]] = [None] * target
                for s in live:
                    s.slot = slot_map[s.req.id]
                    new_slot_list[s.slot] = s
                self._slots = new_slot_list
                prev = self.num_slots
                self.num_slots = target
                self._pending_resize = None
            result = {
                "from": prev, "to": target, "direction": direction,
                "migrated_rows": copied, "in_flight": len(moves),
                "predicted_us": round(float(predicted_us), 2),
                "wall_ms": round((time.monotonic() - t0) * 1e3, 3),
            }
            self._resizes.append(result)
            self._c_resizes.inc(direction=direction)
            sp.set(**result)
        ticket._finish(result)
        with self._cv:
            self._cv.notify_all()

    def _pop_next_locked(self) -> GenRequest:
        """Take the next request off the queue (caller holds self._cv).
        FIFO, unless expert-affine admission is on: then the best
        signature-overlap pick within the fairness window (affinity.py),
        with picks counted and the winner's overlap folded into the
        EWMA gauge."""
        if self._affinity_probe is None or len(self._queue) < 2:
            return self._queue.pop(0)
        from .affinity import pick_affine

        active = [s.req.expert_sig for s in self._slots
                  if s is not None and s.req.expert_sig]
        idx, outcome, frac = pick_affine(self._queue, active,
                                         self.affinity_window)
        for passed in self._queue[:idx]:
            passed.affinity_skips += 1
        req = self._queue.pop(idx)
        self._c_affinity.inc(outcome=outcome)
        old = self._ewma_affinity_overlap
        self._ewma_affinity_overlap = frac if old is None else \
            (1 - self._EWMA_ALPHA) * old + self._EWMA_ALPHA * frac
        self._g_affinity_overlap.set(self._ewma_affinity_overlap,
                                     pool=self.pool.label)
        return req

    def _admit_new(self, params, state, tracer) -> int:
        """Move queued requests into free slots; returns how many. One-shot
        mode runs the whole prefill here (the pre-chunking behavior);
        chunked mode pins + installs any cached prefix and leaves the slot
        in the resumable PREFILL state for `_step_prefills`."""
        import jax
        import jax.numpy as jnp

        admitted = 0
        while True:
            with self._cv:
                if self._pending_resize is not None:
                    # hold admissions while a resize is pending: a shrink
                    # is waiting for live sequences to drain, and filling
                    # freed slots would starve it
                    return admitted
                if not self._queue or self.pool.free_slot_count() == 0:
                    return admitted
                req = self._pop_next_locked()
            admitted += 1
            req.state = RequestState.PREFILL
            req.queue_wait_s = self.admission.on_scheduled(req.id)
            plen = req.prompt.size
            slot_idx = self.pool.alloc(req.id, plen)
            key = np.asarray(jax.random.PRNGKey(req.seed), np.uint32)
            s = _Slot(req, slot_idx, key)
            s.plen = plen
            self._slots[slot_idx] = s
            self._sync_active_gauge()
            if self._stateful:
                # the sequence starts from the zeroed batch-1 state below
                # (one-shot: `prefill_one`'s own), which overwrites the
                # slot's previous tenant's at the install
                self._state_resets += 1

            if self.prefill_chunk_tokens == 0:
                padded = np.zeros((1, self.window), np.int32)
                padded[0, :plen] = req.prompt
                with tracer.resume(req.trace), \
                        tracer.span("serve.prefill", request=req.id,
                                    tokens=plen, head_rows=1):
                    t0 = time.monotonic()
                    tok, self._caches = self._prefill_fn(
                        params, state, self._caches, jnp.asarray(padded),
                        slot_idx, plen, jnp.asarray(key))
                    tok = int(tok)  # sync: the dispatch really ran
                    self._observe_prefill(plen, time.monotonic() - t0)
                self._c_head_rows.inc(pool=self.pool.label)
                s.pos = plen
                s.last_tok = tok
                self._first_token(s, tok)
                continue

            s.small = self._zero_small()
            if self.draft_model is not None:
                # the draft prefills the WHOLE prompt through its own
                # chunk stream — even on a prefix-cache hit (the band
                # holds target-geometry pages the draft cannot install)
                s.draft_small = self._zero_small(self.draft_model)
                s.draft_filled = 0
            prefix = self.pool.prefix
            if prefix is not None:
                # leave >= 1 suffix token: the first output token's logits
                # come from the last prompt position, so the final position
                # always runs through a chunk
                max_pages = (plen - 1) // self.pool.page_size
                matched, entries = prefix.acquire(req.id, req.prompt,
                                                  max_pages=max_pages)
                if entries:
                    ps = self.pool.page_size
                    src_slot = np.zeros(self.max_len, np.int32)
                    src_row = np.zeros(self.max_len, np.int32)
                    for b, e in enumerate(entries):
                        bslot, roff = self.pool.band_coords(e.page)
                        src_slot[b * ps:(b + 1) * ps] = bslot
                        src_row[b * ps:(b + 1) * ps] = (
                            roff + np.arange(ps))
                    with tracer.resume(req.trace), \
                            tracer.span("serve.prefix_install",
                                        request=req.id, tokens=matched):
                        s.small = self._install_fn(
                            s.small, self._band, jnp.asarray(src_slot),
                            jnp.asarray(src_row),
                            jnp.asarray(matched, jnp.int32))
                    s.filled = s.shared = matched
                    req.prefix_tokens = matched
                    req.cache_hit = True

    def _step_prefills(self, params, state, tracer):
        """One prefill chunk for every slot in the PREFILL state; a slot
        whose prompt completes scatters its cache span into the pool,
        emits its first token, and joins this iteration's decode. Returns
        (slots prefilling, chunks run, prompt tokens in them)."""
        import jax.numpy as jnp

        chunk = self.prefill_chunk_tokens
        prefilling = [x for x in self._slots
                      if x is not None and x.req.state is RequestState.PREFILL]
        chunks = chunk_tokens = 0
        for s in prefilling:
            if self.draft_model is not None and s.draft_filled < s.plen:
                self._step_draft_prefill(s, tracer)
            off = s.filled
            n = min(chunk, s.plen - off)
            last = off + n >= s.plen
            if (last and self.draft_model is not None
                    and s.draft_filled < s.plen):
                # hold the target's fused final chunk (which emits the
                # first token and arms decode) until the draft's cache
                # has the full prompt — the next spec iteration needs
                # both sides of the sequence
                continue
            chunks += 1
            chunk_tokens += n
            self._count_mla_prefill(s.small, off)
            with tracer.resume(s.req.trace), \
                    tracer.span("serve.prefill", request=s.req.id,
                                offset=off, tokens=n, head_rows=int(last)):
                tokens = np.zeros((1, chunk), np.int32)
                tokens[0, :n] = s.req.prompt[off:off + n]
                if not last:
                    s.small = self._chunk_fn(
                        params, state, s.small, jnp.asarray(tokens),
                        jnp.asarray(off, jnp.int32))
                    s.filled = off + n
                    continue
                # final chunk: fused chunk + cache-span scatter + first
                # token — a prompt that fits one chunk costs ONE dispatch,
                # like the one-shot path did
                t0 = time.monotonic()
                tok, self._caches = self._last_chunk_fn(
                    params, state, self._caches, s.small,
                    jnp.asarray(tokens), jnp.asarray(off, jnp.int32),
                    s.slot, jnp.asarray(s.plen - 1 - off, jnp.int32),
                    jnp.asarray(s.plen - 1, jnp.int32),
                    jnp.asarray(s.key))
                tok = int(tok)  # sync: int() blocks on the dispatch
                self._observe_prefill(n, time.monotonic() - t0)
            self._c_head_rows.inc(pool=self.pool.label)
            s.small = None
            s.filled = s.pos = s.plen
            s.last_tok = tok
            self._insert_prefix(s, tracer)
            with tracer.resume(s.req.trace), \
                    tracer.span("serve.emit", request=s.req.id, emitted=1):
                self._first_token(s, tok)
        return len(prefilling), chunks, chunk_tokens

    def _count_mla_prefill(self, small, off: int) -> None:
        """Count a prefill chunk at offset `off` for every latent attention
        op, by the rows of its batch-1 holder `small` the chunk is attended
        over (`LatentAttentionOp.prefill_prefix_rows`, the rule its
        lowering follows): host numbers alone, nothing read from the
        device."""
        for op in self._mla_ops:
            rows = op.prefill_prefix_rows(small[op.name]["c_kv"].shape[1],
                                          off, self.prefill_chunk_tokens)
            self._c_mla_prefill.inc(op=op.name, rows=rows)

    def _step_draft_prefill(self, s: _Slot, tracer) -> None:
        """One DRAFT prefill chunk for a speculative slot (scheduler
        thread only): same chunk stream as the target's, against the
        draft's own batch-1 caches; the final chunk scatters the span
        into the draft's pool slot (no token pick — only K/V matter)."""
        import jax.numpy as jnp

        chunk = self.prefill_chunk_tokens
        draft = self.draft_model
        doff = s.draft_filled
        dn = min(chunk, s.plen - doff)
        dtokens = np.zeros((1, chunk), np.int32)
        dtokens[0, :dn] = s.req.prompt[doff:doff + dn]
        dlast = doff + dn >= s.plen
        with tracer.resume(s.req.trace), \
                tracer.span("serve.draft_prefill", request=s.req.id,
                            offset=doff, tokens=dn):
            if not dlast:
                s.draft_small = self._draft_chunk_fn(
                    draft.params, draft.state, s.draft_small,
                    jnp.asarray(dtokens), jnp.asarray(doff, jnp.int32))
            else:
                import jax

                t0 = time.monotonic()
                self._draft_caches = self._draft_last_fn(
                    draft.params, draft.state, self._draft_caches,
                    s.draft_small, jnp.asarray(dtokens),
                    jnp.asarray(doff, jnp.int32), s.slot)
                # sync the final draft chunk (one per request, mirroring
                # the target's per-request sync) so the measured wall is
                # a real dispatch, feeding the admission model's
                # draft-prefill credit
                jax.block_until_ready(self._draft_caches)
                self._observe_draft_prefill(dn, time.monotonic() - t0)
                s.draft_small = None
        s.draft_filled = doff + dn

    def _insert_prefix(self, s: _Slot, tracer) -> None:
        """Register the finished prefill's full prefix pages in the cache
        — ONE device copy for all new pages; already-cached blocks just
        refresh their LRU tick."""
        prefix = self.pool.prefix
        if prefix is None:
            return
        import jax.numpy as jnp

        ps = self.pool.page_size

        def copy_pages(pairs) -> None:
            # fixed-shape coordinate arrays (one jit compile): pad by
            # repeating the last real page — a duplicate scatter writes
            # the same rows the same values, so padding is idempotent
            cap = self.pool.full_pages_per_slot
            padded = pairs + [pairs[-1]] * (cap - len(pairs))
            n = cap * ps
            src = np.empty(n, np.int32)
            dst_slot = np.empty(n, np.int32)
            dst_row = np.empty(n, np.int32)
            for i, (block, page) in enumerate(padded):
                bslot, roff = self.pool.band_coords(page)
                rows = slice(i * ps, (i + 1) * ps)
                src[rows] = block * ps + np.arange(ps)
                dst_slot[rows] = bslot
                dst_row[rows] = roff + np.arange(ps)
            self._band = self._insert_fn(
                self._band, self._caches, jnp.asarray(s.slot, jnp.int32),
                jnp.asarray(src), jnp.asarray(dst_slot),
                jnp.asarray(dst_row))

        with tracer.resume(s.req.trace), \
                tracer.span("serve.prefix_insert", request=s.req.id):
            prefix.insert(s.req.prompt, s.plen, copy_pages)

    def _first_token(self, s: _Slot, tok: int) -> None:
        """Prefill complete: the request starts decoding and its TTFT is
        recorded, split by prefix-cache outcome. A prefill-only request
        (disaggregated serving) PARKS instead: first token emitted, KV
        resident, slot held — `on_parked` tells the fleet handoff plane;
        if the hook itself fails, the request degrades to local decode
        (zero-drop: a broken coordinator never strands traffic)."""
        req = s.req
        req.state = RequestState.DECODE
        req.t_first_token = time.monotonic()
        self._h_ttft.observe(
            (req.t_first_token - req.t_submit) * 1e3,
            exemplar=req.trace_id,
            cache="hit" if req.cache_hit else "miss")
        self._sync_active_gauge()
        self._emit_token(s, tok)
        if req.prefill_only and req.state is RequestState.DECODE:
            with self._cv:
                req.state = RequestState.PARKED
                self._parked[req.id] = s
            cb = self.on_parked
            if cb is not None:
                try:
                    cb(req)
                except Exception:
                    self.resume_parked(req)

    def _emit_token(self, s: _Slot, tok: int) -> None:
        """Deliver one generated token; retire the request when it hits
        EOS or its budget — releasing the slot and pages IMMEDIATELY so
        the next iteration can reuse them."""
        req = s.req
        req._emit(tok)
        s.last_tok = tok
        s.emitted += 1
        self.tokens_emitted += 1
        self._c_tokens.inc()
        if ((req.eos_id is not None and tok == req.eos_id)
                or s.emitted >= req.max_new_tokens):
            self._retire(s)

    def _retire(self, s: _Slot) -> None:
        self._retired += 1
        self._slots[s.slot] = None
        with self._cv:
            self._parked.pop(s.req.id, None)
        self.pool.free(s.req.id)
        self.admission.release(s.req.id)
        self._completed += 1
        self._c_requests.inc(outcome="completed")
        self._sync_active_gauge()
        s.req._finish()
        with self._cv:
            self._cv.notify_all()

    def _sync_active_gauge(self) -> None:
        self._g_active.set(sum(1 for s in self._slots if s is not None),
                           pool=self.pool.label)

    def _fail_pending_resize(self, err: BaseException) -> None:
        with self._cv:
            ticket, self._pending_resize = self._pending_resize, None
        if ticket is not None and not ticket.done():
            ticket._fail(err)

    def _drain_queue(self, err: BaseException) -> None:
        with self._cv:
            pending, self._queue = self._queue, []
        for req in pending:
            self.admission.release(req.id)
            self._failed += 1
            self._c_requests.inc(outcome="failed")
            req._fail(err)

    def _fail_all(self, err: BaseException) -> None:
        with self._cv:
            self._running = False
            slots, self._slots = list(self._slots), [None] * self.num_slots
            self._parked.clear()
        for s in slots:
            if s is None:
                continue
            self.pool.free(s.req.id)
            self.admission.release(s.req.id)
            self._failed += 1
            self._c_requests.inc(outcome="failed")
            s.req._fail(err)
        self._drain_queue(err)
        self._fail_pending_resize(err)
        self._fail_pending_handoffs(err)
