"""Continuous-batching generation subsystem (ISSUE 5).

Role parity: the iteration-level scheduling loop of Orca-style serving and
the paged KV allocation of vLLM, grafted onto the repo's incremental
decoding path (serving/generate.py) instead of the lockstep
one-batch-at-a-time `GenerativeSession.generate`:

 - `PagedKVPool` (kvpool.py): the KV cache block-allocated in fixed-size
   pages with a per-sequence page table; capacity derived from the machine
   spec's HBM via the analysis memory model (`analysis.plan_memory_bytes`).
 - `PrefixCache` (kvpool.py, ISSUE 6): hash-addressed, refcounted,
   copy-on-write store of immutable prefix pages in a device-side band —
   identical page-aligned prompt prefixes are prefilled once and installed
   into new slots by device copy, with LRU eviction under a page budget.
 - `ContinuousBatcher` (continuous.py): per-request state machine
   (QUEUED -> PREFILL -> DECODE -> FINISHED); every decode iteration steps
   ALL active slots at their own positions (the vector-decode_pos path in
   ops/attention.py), finished requests free their slot and pages
   immediately, queued requests prefill into freed slots while the rest
   keep decoding, and prefills run in fixed-size CHUNKS interleaved with
   decode (the chunk-offset scalar-decode_pos path) so long prompts never
   stall in-flight decodes. `request_resize` shrinks/grows the decode
   mesh capacity under load — live sequences' OWNED cache rows migrate
   into the new arrays between iterations (resharding/, FFTA06x-gated)
   and in-flight requests keep decoding token-identically.
 - `AdmissionController` (admission.py): bounded queue + admit-time page
   budget (crediting expected prefix sharing) so every accepted request
   can finish; typed backpressure the HTTP endpoint maps to 429.
 - `serve-bench` (bench.py): the load generator that measures the win
   over the lockstep path, incl. shared-prefix and long-prefill
   scenarios (docs/serving.md).
"""
from .admission import (AdmissionController, AdmissionError, QueueFull,
                        PoolSaturated, RequestTooLarge, SLOExceeded)
from .continuous import (BatcherStopped, ContinuousBatcher, GenRequest,
                         RequestCancelled, RequestState, ResizeTicket)
from .kvpool import (PagedKVPool, PoolExhausted, PrefixCache,
                     RingCacheUnsupported, SequenceStateUnsupported,
                     derive_num_slots, kv_bytes_per_token, kv_cache_spec,
                     prefix_route_chain, prefix_route_key,
                     ring_bytes_per_slot, state_bytes_per_slot,
                     write_slot_span, write_slot_state, zero_kv_caches)

__all__ = [
    "AdmissionController", "AdmissionError", "QueueFull", "PoolSaturated",
    "RequestTooLarge", "BatcherStopped", "ContinuousBatcher", "GenRequest",
    "RequestCancelled", "RequestState", "ResizeTicket", "PagedKVPool",
    "PoolExhausted", "PrefixCache", "SLOExceeded",
    "RingCacheUnsupported", "SequenceStateUnsupported", "derive_num_slots",
    "kv_bytes_per_token", "ring_bytes_per_slot",
    "kv_cache_spec", "prefix_route_chain", "prefix_route_key",
    "state_bytes_per_slot", "write_slot_span", "write_slot_state",
    "zero_kv_caches",
]
