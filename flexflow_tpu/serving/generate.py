"""Autoregressive generation with KV caches (reference role: the
incremental-decoding side of the Triton inference prototype,
triton/src/model.cc — here TPU-native: one jitted prefill over the prompt
window + one jitted decode step reused for every position, caches carried in
the executor's functional state)."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from ..ffconst import CompMode
from ..obs.tracing import first_call


def sampling_logits(probs, temperature: float, top_k):
    """THE sampling policy core, shared by the lockstep batched `_pick`
    and the continuous batcher's per-row pick (serving/sched/continuous
    .py) so the two decode paths can never drift: log-probs at
    `temperature`, optionally truncated to the top_k most likely tokens
    via a kth-largest threshold (O(V log k), the hot decode path). Works
    on (V,) rows and (b, V) batches alike."""
    import jax
    import jax.numpy as jnp

    logits = jnp.log(probs.astype(jnp.float32) + 1e-9) / temperature
    if top_k is not None:
        kk = int(top_k)
        if kk < 1:
            raise ValueError(f"top_k={top_k}: must be >= 1")
        kk = min(kk, logits.shape[-1])
        kth = jax.lax.top_k(logits, kk)[0][..., -1:]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    return logits


class GenerativeSession:
    """Incremental decoding session over a compiled causal-transformer
    FFModel whose final tensor is a distribution over the vocabulary.

    max_len: cache capacity (max prompt + generated tokens). The model's
    declared input seq length is the PREFILL window; prompts are padded to
    it (cache positions past the prompt are overwritten as decoding
    proceeds)."""

    def __init__(self, model, max_len: int):
        import jax

        self.model = model
        self.max_len = int(max_len)
        window = model.input_ops[0].outputs[0].dims[1]
        if self.max_len < window:
            raise ValueError(
                f"max_len={self.max_len} smaller than the model's prefill "
                f"window ({window}); the cache must hold at least one "
                "full prefill")
        self.attn_ops = [op for op in model.graph.ops.values()
                         if op.kv_cache_arrays()
                         or op.sequence_state_arrays()]
        if not self.attn_ops:
            raise ValueError("generation needs an op that keeps a serving"
                             " cache")
        # ONE cache-geometry definition (per-token and per-sequence arrays
        # per op + compute dtype — bf16 under mixed precision, the dominant
        # serving memory) shared with the continuous batcher and the pool's
        # HBM sizing
        from .sched.kvpool import zero_kv_caches

        self._caches: Dict[str, Dict[str, object]] = zero_kv_caches(
            model, model.config.batch_size, self.max_len)

        executor = model.executor
        final_guid = model.final_tensor.guid
        input_name = model.input_ops[0].name

        def prefill(params, state, tokens, prompt_len):
            # prompt_len: the prompts' real tokens inside the padded window,
            # so that per-sequence state stops where they do
            values, new_state, _ = executor.forward_values(
                params, state, {input_name: tokens}, None,
                CompMode.COMP_MODE_INFERENCE, fill_kv_cache=True,
                valid_len=prompt_len)
            return values[final_guid], new_state

        def decode(params, state, token, pos):
            values, new_state, _ = executor.forward_values(
                params, state, {input_name: token}, None,
                CompMode.COMP_MODE_INFERENCE, decode_pos=pos)
            return values[final_guid], new_state

        self._prefill = first_call(jax.jit(prefill), "prefill", self,
                                   "_prefill")
        self._decode = first_call(jax.jit(decode, donate_argnums=(1,)),
                                  "decode", self, "_decode")
        self._decode_raw = decode
        self._decode_scans: Dict[tuple, object] = {}

    @staticmethod
    def _pick(probs, pos, base_key, temperature: float,
              top_k: Optional[int]):
        """Next token from a (b, vocab) distribution. temperature<=0 =
        greedy argmax; otherwise categorical sampling at the given
        temperature, optionally truncated to the top_k most likely tokens.
        The key is fold_in(base_key, pos) — a function of the POSITION, so
        chunked and per-step decoding draw identical samples."""
        import jax
        import jax.numpy as jnp

        if temperature <= 0.0:
            return jnp.argmax(probs, axis=-1).astype(jnp.int32)
        logits = sampling_logits(probs, temperature, top_k)
        key = jax.random.fold_in(base_key, pos)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    def _decode_scan(self, k: int, temperature: float,
                     top_k: Optional[int]):
        """Jitted scan of k greedy decode steps — ONE dispatch per k tokens
        (fit(steps_per_execution) applied to serving: the host pays one
        dispatch per k tokens instead of one per token; the gain is not
        measured on the current set-up)."""
        cache_key = (k, float(temperature), top_k)
        fn = self._decode_scans.get(cache_key)
        if fn is not None:
            return fn
        import jax

        decode = self._decode_raw
        pick = self._pick

        def chunk(params, state, tok, pos0, base_key):
            import jax.numpy as jnp

            def body(carry, i):
                state, tok = carry
                probs, state = decode(params, state, tok[:, None], pos0 + i)
                tok = pick(probs[:, 0, :], pos0 + i, base_key, temperature,
                           top_k)
                return (state, tok), tok

            (state, tok), toks = jax.lax.scan(
                body, (state, tok), jnp.arange(k, dtype=jnp.int32))
            return state, tok, toks  # toks: (k, batch)

        fn = jax.jit(chunk, donate_argnums=(1,))
        self._decode_scans[cache_key] = fn
        return fn

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 eos_id: Optional[int] = None,
                 tokens_per_dispatch: int = 1,
                 temperature: float = 0.0,
                 top_k: Optional[int] = None,
                 seed: int = 0) -> np.ndarray:
        """Decoding. prompt_ids: (batch, prompt_len) int tokens. Returns
        (batch, generated) token ids. temperature=0 (default) is greedy
        argmax; temperature>0 samples categorically (optionally truncated
        to top_k), with per-POSITION rng keys so the same seed yields the
        same tokens at any tokens_per_dispatch.

        tokens_per_dispatch > 1: K decode steps run in one jitted scan
        dispatch, with the NEXT chunk dispatched before the previous
        chunk's tokens are fetched (the carry lives on device, so chunks
        chain without host round trips). Token-identical to the per-step
        loop; with an eos_id the stop happens on the same step, at the
        cost of up to one speculative chunk of discarded compute."""
        import jax.numpy as jnp

        model = self.model
        b = model.config.batch_size
        window = model.input_ops[0].outputs[0].dims[1]
        prompt_ids = np.asarray(prompt_ids)
        if prompt_ids.ndim != 2 or prompt_ids.shape[0] < 1:
            raise ValueError(
                "prompt_ids must be a non-empty (n_prompts, prompt_len) "
                f"array of token ids; got shape {prompt_ids.shape}")
        n_real = prompt_ids.shape[0]
        if n_real > b:
            raise ValueError(
                f"{n_real} prompts exceed the session batch size {b}")
        if n_real < b:
            # pad partial batches by tiling the last real prompt: rows
            # decode independently (each has its own KV-cache rows), so
            # the real rows' tokens are exact; padded rows are marked
            # finished from step 0 below, so an eos early stop never
            # waits on them
            prompt_ids = np.concatenate(
                [prompt_ids, np.tile(prompt_ids[-1:], (b - n_real, 1))],
                axis=0)
        prompt_len = prompt_ids.shape[1]
        if prompt_len > window:
            raise ValueError(
                f"prompt length {prompt_len} exceeds the prefill window "
                f"({window})")
        if prompt_len + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the cache capacity "
                f"({self.max_len})")

        if max_new_tokens <= 0:
            return np.zeros((n_real, 0), dtype=np.int32)

        padded = np.zeros((b, window), dtype=np.int32)
        padded[:, :prompt_len] = prompt_ids
        state = {**model.state, **self._caches}
        import jax

        base_key = jax.random.PRNGKey(seed)
        probs, state = self._prefill(model.params, state, jnp.asarray(padded),
                                     jnp.asarray(prompt_len, jnp.int32))
        # next token from the last REAL prompt position
        tok = self._pick(probs[:, prompt_len - 1, :],
                         jnp.asarray(prompt_len - 1, jnp.int32), base_key,
                         temperature, top_k)

        out = []
        finished = np.zeros(b, dtype=bool)
        # padding rows are DONE before the first step: under sampling (or
        # any future non-tiled padding) they would otherwise emit tokens
        # of their own and hold the whole batch past the real rows' eos
        finished[n_real:] = True
        K = max(1, int(tokens_per_dispatch))
        if K > 1:
            # chunked decode: tok holds the NEXT token to emit; each scan
            # chunk consumes it and produces the k tokens that follow.
            # One-deep pipeline: chunk i's tokens are fetched AFTER chunk
            # i+1 is dispatched (the scan carry chains on device, so the
            # next chunk never waits on a host round trip); the queue
            # stays one execution deep.
            def absorb(device_rows) -> bool:
                """Fetch + append a chunk's rows; True = stop decoding.
                The np.asarray transfer happens HERE — after the next
                chunk is already dispatched — so it overlaps device
                execution."""
                for row in np.asarray(device_rows):
                    out.append(row)
                    if eos_id is not None:
                        finished[:] |= row == eos_id
                        if finished.all():
                            return True
                    if len(out) >= max_new_tokens:
                        return True
                return False

            pos = prompt_len
            dispatched = 1  # the prefill's token
            pending = tok[None, :]  # (1, b) device array
            while dispatched < max_new_tokens:
                k = min(K, max_new_tokens - dispatched)
                state, tok, toks = self._decode_scan(
                    k, temperature, top_k)(
                    model.params, state, tok, jnp.asarray(pos, jnp.int32),
                    base_key)
                pos += k
                dispatched += k
                if absorb(pending):  # overlap: toks still computing
                    return np.stack(out, axis=1)[:n_real]
                pending = toks
            absorb(pending)
            return np.stack(out, axis=1)[:n_real]
        for step in range(max_new_tokens):
            out.append(np.asarray(tok))
            if eos_id is not None:
                finished |= out[-1] == eos_id
                if finished.all():
                    break
            pos = jnp.asarray(prompt_len + step, jnp.int32)
            probs, state = self._decode(
                model.params, state, tok[:, None], pos)
            tok = self._pick(probs[:, 0, :], pos, base_key, temperature,
                             top_k)
        return np.stack(out, axis=1)[:n_real]
