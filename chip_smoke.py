"""chip_smoke.py — the quickest proof that flexflow_tpu still starts on a chip.

One process, normal entry points only (FFModel -> compile() -> fit();
GenerativeSession / ContinuousBatcher), at the full width of the model the
repo has always benchmarked: the BERT encoder of bench.py (hidden 1024, 16
heads, seq 512, vocab 30,522, batch 8) and a causal LM of the same width
(serve-bench's own builder). Weights are random, made from --seed; step and
request counts are a handful — the widths are what is full-size.

    python chip_smoke.py              # one chip: train, kernels, search,
                                      # serve, latent, hybrid, window
    python chip_smoke.py --chips 4    # ONLY the mesh phase + its 1-device twin

It needs a TPU: with none it exits non-zero before doing any work. There is
no CPU switch — tests/test_chip_smoke.py rehearses the phases at toy width by
calling them directly. Every phase prints one JSON line; any phase that
raises ends the run with a traceback and a non-zero code. The last line of
stdout is {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from flexflow_tpu.runtime.platform import require_tpu


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Widths are bench.py's (and serve-bench --hidden 1024 --layers 12
    --heads 16 --vocab 30522); only counts and depths may be cut."""

    hidden: int = 1024
    heads: int = 16
    layers: int = 12
    seq: int = 512
    vocab: int = 30522
    batch: int = 8
    steps_per_execution: int = 4   # the K > 1 dispatch shape
    train_dispatches: int = 2      # K-step dispatches (K=1 run: K x this)
    kernel_layers: int = 1         # depth of the flash parity model
    search_layers: int = 2         # identical layers share one measurement
    search_budget: int = 4
    search_devices: int = 4        # the machine the one-chip search plans for
    # serving: slots x declared prefill window; one prompt >= 512 tokens
    slots: int = 4
    window: int = 1024
    max_len: int = 1536
    page_size: int = 16
    prompts: Tuple[int, ...] = (24, 200, 520, 75)
    new_tokens: int = 8
    # the latent layer: Mistral-Small-4's heads, q / kv ranks, nope / rope /
    # value head sizes
    latent: Tuple[int, ...] = (32, 1024, 256, 64, 64, 128)
    # the hybrid block: Falcon-H1-34B's 20 query heads on 4 KV heads of 128,
    # and its mixer: inner width, heads, state, groups
    hybrid: Tuple[int, ...] = (20, 4, 128, 4096, 32, 256, 2)
    # the window pair: Laguna-XS.2's 48 (full) and 64 (window) query heads
    # on 8 KV heads of 128, and a window the longer prompts wrap 4 times
    window_pair: Tuple[int, ...] = (48, 64, 8, 128, 128)
    # --chips 4: a global batch at which every plan's per-chip share is
    # past the flash crossover, so the kernels run inside the mesh step
    mesh_batch: int = 32
    mesh_layers: int = 4
    mesh_steps: int = 4   # the 2nd step on a mesh recompiles; median of 3


# tolerances, stated once
LOSS_DISPATCH_RTOL = 1e-2   # K=1 vs K>1 loss, same math, other fusion
KERNEL_LOSS_RTOL = 2e-2     # pallas vs reference loss (bf16 kernels)
KERNEL_UPDATE_RTOL = 5e-2   # ... and the SGD update they produce (= grads)
MESH_LOSS_RTOL = 2e-2       # mesh vs one device (bf16, reassociated sums)
NEAR_TIE_LOGPROB = 5e-2     # a greedy flip is a near-tie only within this


# ---------------------------------------------------------------------------
# plumbing
# ---------------------------------------------------------------------------
class CompileClock:
    """Seconds spent in backend compiles (persistent-cache lookups included)
    and the persistent cache's hit/miss counts, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += secs

    def _on_event(self, event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def run_phase(name: str, clock: CompileClock, fn, *args) -> Dict:
    """Run one phase and print its line. Exceptions propagate."""
    c0, h0, m0 = clock.snapshot()
    t0 = time.perf_counter()
    out = fn(*args)
    c1, h1, m1 = clock.snapshot()
    line = {"phase": name,
            "seconds": round(time.perf_counter() - t0, 1),
            "compile_seconds": round(c1 - c0, 1),
            "cache_hits": h1 - h0, "cache_misses": m1 - m0, **out}
    print(json.dumps(line), flush=True)
    return line


def cold_start_line() -> Dict:
    """What the process has recorded of its own cold start so far
    (docs/observability.md "Cold start"): every one-shot phase and every
    program's first call, seconds by name — a bring-up on a new machine
    shows where its start-up went without the benchmark."""
    from flexflow_tpu.obs.startup import startup_table

    line = {"phase": "cold_start",
            "seconds": {name: round(s, 3) for name, s in startup_table()}}
    print(json.dumps(line), flush=True)
    return line


def _check_close(what: str, got: float, want: float, rtol: float) -> float:
    delta = abs(got - want) / max(abs(want), 1e-8)
    if not (np.isfinite(got) and delta <= rtol):
        raise AssertionError(
            f"{what}: {got!r} vs {want!r} (rel {delta:.2e} > {rtol:g})")
    return delta


def _selected(family: str, since: Dict[str, int] = None) -> Dict[str, int]:
    """What the registry's own selection counter says the lowerings of
    `family` chose ({impl: count}), so far or since an earlier reading."""
    from flexflow_tpu.obs.registry import REGISTRY

    fam = REGISTRY.get("ff_kernel_selected_total")
    since = since or {}
    return {impl: (int(fam.value(op=family, impl=impl)) if fam else 0)
            - since.get(impl, 0)
            for impl in ("pallas", "reference")}


def _compiled_text(jitted, *args) -> str:
    """Optimized HLO of a jitted step for these arguments (a second compile
    of the same program: a persistent-cache read, not a recompile)."""
    import jax

    def shape_of(a):
        # only COMMITTED arrays pin a placement, as in a real call
        committed = getattr(a, "committed", False)
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if committed else None)

    fn = getattr(jitted, "__wrapped__", jitted)
    return fn.lower(*jax.tree.map(shape_of, args)).compile().as_text()


# ---------------------------------------------------------------------------
# models
# ---------------------------------------------------------------------------
def build_bert(sizes: Sizes, seed: int, *, layers: int = None,
               num_devices: int = 1,
               optimizer: str = "adam", search_budget: int = 0,
               parallel_axes=None, compile_model: bool = True):
    """bench.py's model (same builder, bf16 Adam moments) at `layers`."""
    import jax.numpy as jnp

    import flexflow_tpu as ff
    from flexflow_tpu.models import TransformerConfig, build_bert_encoder

    config = ff.FFConfig()
    config.num_devices = num_devices
    config.batch_size = sizes.batch
    config.seed = seed
    config.search_budget = search_budget
    model = ff.FFModel(config)
    tokens = model.create_tensor([sizes.batch, sizes.seq],
                                 ff.DataType.DT_INT32)
    build_bert_encoder(model, tokens, TransformerConfig(
        hidden_size=sizes.hidden, embedding_size=sizes.hidden,
        num_heads=sizes.heads,
        num_layers=sizes.layers if layers is None else layers,
        sequence_length=sizes.seq, vocab_size=sizes.vocab))
    if compile_model:
        opt = (ff.AdamOptimizer(model, alpha=1e-4,
                                moments_dtype=jnp.bfloat16)
               if optimizer == "adam"
               else ff.SGDOptimizer(model, lr=1.0, weight_decay=0.0))
        model.compile(
            optimizer=opt,
            loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
            metrics=[], parallel_axes=parallel_axes)
    return model


def bert_data(sizes: Sizes, seed: int, n_batches: int):
    rng = np.random.RandomState(seed)
    n = sizes.batch * n_batches
    x = rng.randint(0, sizes.vocab, size=(n, sizes.seq)).astype(np.int32)
    y = rng.randint(0, 2, size=(n, sizes.seq, 1)).astype(np.int32)
    return x, y


def _step_losses(model) -> List[float]:
    return [r["loss"] for r in model.step_stats.records()]


def _steady_ms(model) -> float:
    """Median per-optimizer-step wall ms, first (compiling) dispatch left
    out. fit() fetches every step's loss, so a step is complete when it is
    recorded."""
    recs = model.step_stats.records()[1:]
    return float(np.median([r["step_ms"] for r in recs])) if recs else 0.0


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------
def phase_train(sizes: Sizes, seed: int) -> Dict:
    """BERT at full depth through fit(): per-step dispatch (K=1) and K
    steps per dispatch give the same losses; the compiled step holds the
    Pallas custom call when the registry chose flash."""
    import jax

    k = sizes.steps_per_execution
    n_steps = k * sizes.train_dispatches
    x, y = bert_data(sizes, seed, n_steps)

    m1 = build_bert(sizes, seed)
    # the compiled per-step program, as text, before fit() runs it; tracing
    # it is also what makes the registry record its attention choice
    inputs, label = m1._prep_step_batch([x], y, 0, sizes.batch)
    text = _compiled_text(m1._train_step, m1.params, m1.opt_state, m1.state,
                          inputs, label, jax.random.PRNGKey(0))
    attention = _selected("attention")
    has_kernel = "tpu_custom_call" in text
    if attention["pallas"] and jax.default_backend() == "tpu":
        assert has_kernel, ("registry chose pallas attention but the compiled"
                            " train step holds no tpu_custom_call")
    m1.fit([x], y, epochs=1)
    losses_1 = _step_losses(m1)
    ms_1 = _steady_ms(m1)
    del m1

    mk = build_bert(sizes, seed)   # same seed: identical initial weights
    mk.fit([x], y, epochs=1, steps_per_execution=k)
    losses_k = _step_losses(mk)    # one K-step mean per dispatch
    ms_k = _steady_ms(mk)

    assert len(losses_1) == n_steps and len(losses_k) == sizes.train_dispatches
    worst = 0.0
    for d, lk in enumerate(losses_k):
        want = float(np.mean(losses_1[d * k:(d + 1) * k]))
        worst = max(worst, _check_close(
            f"loss of dispatch {d} at K={k} vs K=1", lk, want,
            LOSS_DISPATCH_RTOL))
    return {
        "model": f"bert {sizes.layers}L/{sizes.hidden}/{sizes.heads}h"
                 f"/seq{sizes.seq}/vocab{sizes.vocab}/batch{sizes.batch}",
        "attention_impl": attention, "tpu_custom_call": has_kernel,
        "losses_k1": [round(v, 5) for v in losses_1],
        f"losses_k{k}": [round(v, 5) for v in losses_k],
        "compared": f"K={k} dispatch-mean loss vs K=1, rel <="
                    f" {LOSS_DISPATCH_RTOL:g}",
        "worst_rel": float(f"{worst:.2e}"),
        "step_ms_k1": round(ms_1, 2), f"step_ms_k{k}": round(ms_k, 2),
        "samples_per_s_k1": round(sizes.batch / ms_1 * 1e3, 1) if ms_1 else 0,
        f"samples_per_s_k{k}":
            round(sizes.batch / ms_k * 1e3, 1) if ms_k else 0,
    }


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def _one_sgd_step(model, x, y):
    """(loss, update) of one plain-SGD step at lr 1: the update IS the
    gradient, so comparing updates compares backward passes."""
    import jax

    before = jax.tree.map(np.asarray, model.params)
    hist = model.fit([x], y, epochs=1)
    update = jax.tree.map(lambda a, b: np.asarray(a, np.float32)
                          - np.asarray(b, np.float32), model.params, before)
    return hist[0]["loss"], update


def _update_error(got, want) -> float:
    """Relative L2 error of a whole update tree."""
    import jax

    num = sum(float(np.sum((g - w) ** 2)) for g, w in zip(
        jax.tree.leaves(got), jax.tree.leaves(want)))
    den = sum(float(np.sum(w ** 2)) for w in jax.tree.leaves(want))
    return float(np.sqrt(num / max(den, 1e-30)))


def phase_kernels(sizes: Sizes, seed: int) -> Dict:
    """Flash attention forced on through KERNELS.override, compiled (not
    interpreted) on the chip, against the reference lowering of the same
    model and seed: forward loss and the gradient-carrying SGD update.
    (The two decode families have no training graph: the serve phase
    forces them.)"""
    from flexflow_tpu.kernels.registry import KERNELS
    from flexflow_tpu.runtime.platform import pallas_interpret

    x, y = bert_data(sizes, seed, 1)

    def bert_step(impl):
        with KERNELS.override("attention", impl):
            return _one_sgd_step(
                build_bert(sizes, seed, layers=sizes.kernel_layers,
                           optimizer="sgd"), x, y)

    want_loss, want_update = bert_step("reference")
    before = _selected("attention")
    loss, update = bert_step("pallas")
    assert _selected("attention", before)["pallas"] > 0, (
        "attention: forced pallas but the lowering never selected it")
    d_loss = _check_close("attention pallas-vs-reference loss", loss,
                          want_loss, KERNEL_LOSS_RTOL)
    d_update = _update_error(update, want_update)
    assert d_update <= KERNEL_UPDATE_RTOL, (
        "attention: SGD update differs from the reference lowering's"
        f" by rel L2 {d_update:.2e} > {KERNEL_UPDATE_RTOL:g}")
    return {"interpret": pallas_interpret(),
            "families": {"attention": {
                "loss_rel": float(f"{d_loss:.1e}"),
                "update_rel_l2": float(f"{d_update:.1e}")}},
            "compared": f"forced-pallas vs reference: loss rel <="
                        f" {KERNEL_LOSS_RTOL:g}, lr-1 SGD update rel L2 <="
                        f" {KERNEL_UPDATE_RTOL:g}"}


# ---------------------------------------------------------------------------
# phase: search
# ---------------------------------------------------------------------------
def phase_search(sizes: Sizes, seed: int) -> Dict:
    """The Unity search with MEASURED op costs, every op timed on this
    device. compile() only searches when it has more than one device, so on
    one chip this calls what compile() calls — unity_optimize — for a
    described `search_devices`-chip machine (each op is measured at its
    per-shard shape, exactly as on the real mesh); --chips 4 runs the
    search inside compile(). Any measurement failure fails the phase."""
    from flexflow_tpu.core.graph import Graph
    from flexflow_tpu.search.machine_model import make_machine_model
    from flexflow_tpu.search.simulator import Simulator, get_op_cost_cache
    from flexflow_tpu.search.unity import unity_optimize

    n = sizes.search_devices
    model = build_bert(sizes, seed, layers=sizes.search_layers,
                       search_budget=sizes.search_budget,
                       compile_model=False)
    config = model.config
    machine = make_machine_model(config, n)
    cache = get_op_cost_cache(config)
    measured_before = cache.misses
    sim = Simulator(machine, config, measured=cache)
    result = unity_optimize(Graph(model.ops), config, machine,
                            sizes.batch, n, simulator=sim)
    assert not cache.failures, f"op-cost measurement failed: {cache.failures}"
    assert sim.analytic_fallbacks == 0, (
        f"{sim.analytic_fallbacks} ops priced analytically, not measured")
    measured = cache.misses - measured_before
    assert measured > 0, "the search measured nothing"
    return {"planned_for_devices": n, "chip": machine.chip.name,
            "mesh_axes": result.mesh_axes,
            "predicted_step_us": round(result.predicted_step_us, 1),
            "ops_measured": measured, "cache_hits": cache.hits,
            "analytic_fallbacks": sim.analytic_fallbacks,
            "failures": len(cache.failures)}


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------
def _first_mismatch(got, want) -> int:
    for i, (a, b) in enumerate(zip(got, want)):
        if a != b:
            return i
    return -1


def _near_tie(model, prompt, ref_tokens, i, tok_a, tok_b) -> float:
    """Log-prob gap between the two candidate tokens at the first differing
    position, under one reference forward over prompt + agreed tokens."""
    window = model.input_ops[0].outputs[0].dims[1]
    context = np.concatenate([prompt, np.asarray(ref_tokens[:i], np.int32)])
    padded = np.zeros((1, window), np.int32)
    padded[0, :context.size] = context
    row = np.asarray(model.predict(padded, batch_size=1),
                     np.float32)[0, context.size - 1]
    logp = np.log(row + 1e-30)
    top = float(logp.max())
    gap = max(top - float(logp[tok_a]), top - float(logp[tok_b]))
    return gap


def _count_identical(label, model, outs, refs, prompts, near_ties) -> int:
    """How many of `outs` equal `refs` token for token; a first mismatch
    must be a near-tie under one reference forward, and is appended to
    `near_ties`."""
    identical = 0
    for r, (out, ref, prompt) in enumerate(zip(outs, refs, prompts)):
        assert len(out) == len(ref), (label, r, out)
        i = _first_mismatch(out, ref)
        if i < 0:
            identical += 1
            continue
        gap = _near_tie(model, prompt, ref, i, int(out[i]), int(ref[i]))
        near_ties.append({"run": label, "request": r, "position": i,
                          "tokens": [int(out[i]), int(ref[i])],
                          "logprob_gap": round(gap, 5)})
        assert gap <= NEAR_TIE_LOGPROB, (
            f"{label}: request {r} token {i} is {int(out[i])}, the reference"
            f" says {int(ref[i])}, and it is no near-tie: log-prob gap"
            f" to the top {gap:.4f} > {NEAR_TIE_LOGPROB:g}")
    return identical


def _print_near_ties(near_ties: List[Dict]) -> None:
    if near_ties:
        print("chip_smoke: greedy near-tie flipped (matmul precision);"
              f" accepted within log-prob {NEAR_TIE_LOGPROB:g}:"
              f" {json.dumps(near_ties)}", flush=True)


def phase_serve(sizes: Sizes, seed: int) -> Dict:
    """serve-bench's causal LM at the BERT width through ContinuousBatcher,
    greedy, against the lockstep GenerativeSession: once with the
    registry's default selection, once with both decode kernel families
    forced on. The lockstep prefill runs the declared window (>= 512), so
    it crosses the flash crossover."""
    from flexflow_tpu.kernels.registry import KERNELS
    from flexflow_tpu.serving.generate import GenerativeSession
    from flexflow_tpu.serving.sched import ContinuousBatcher
    from flexflow_tpu.serving.sched.bench import build_tiny_lm

    # build_tiny_lm seeds from FFConfig's default; the prompts carry --seed
    model = build_tiny_lm(sizes.slots, sizes.window, vocab=sizes.vocab,
                          hidden=sizes.hidden, heads=sizes.heads,
                          layers=sizes.layers)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, sizes.vocab, size=(n,)).astype(np.int32)
               for n in sizes.prompts]
    assert max(sizes.prompts) <= sizes.window
    attention_before = _selected("attention")
    session = GenerativeSession(model, max_len=sizes.max_len)
    refs = [np.asarray(session.generate(p[None, :], sizes.new_tokens)[0])
            for p in prompts]
    prefill_attention = _selected("attention", attention_before)
    del session

    runs = {}
    near_ties = []
    for label, forced in (("default", ()),
                          ("decode_kernels_forced",
                           ("attention_decode", "attention_decode_mq"))):
        before = {f: _selected(f) for f in forced}
        with contextlib.ExitStack() as stack:
            for fam in forced:
                stack.enter_context(KERNELS.override(fam, "pallas"))
            with ContinuousBatcher(
                    model, max_len=sizes.max_len, num_slots=sizes.slots,
                    page_size=sizes.page_size,
                    max_queue=len(prompts)) as batcher:
                handles = [batcher.submit(p, sizes.new_tokens)
                           for p in prompts]
                outs = [np.asarray(h.result(timeout=900.0)) for h in handles]
        for fam in forced:
            assert _selected(fam, before[fam])["pallas"] > 0, (
                f"{fam}: forced pallas but the decode step never selected it")
        identical = _count_identical(label, model, outs, refs, prompts,
                                     near_ties)
        runs[label] = f"{identical}/{len(prompts)} identical"
    _print_near_ties(near_ties)
    return {"model": f"causal lm {sizes.layers}L/{sizes.hidden}/"
                     f"{sizes.heads}h/vocab{sizes.vocab}, {sizes.slots} slots,"
                     f" window {sizes.window}, f32",
            "prompt_lengths": list(sizes.prompts),
            "new_tokens": sizes.new_tokens,
            "lockstep_prefill_attention": prefill_attention,
            "compared": "greedy tokens vs lockstep GenerativeSession"
                        f" (near-tie log-prob tolerance {NEAR_TIE_LOGPROB:g})",
            "token_parity": runs, "near_ties": len(near_ties)}


def _served_both_ways(family: str, model, prompts, new_tokens: int,
                       counting_op: str, **batcher_kw):
    """`prompts` through a ContinuousBatcher, greedy, with the decode kernel
    family forced to its reference and to its kernel: ({impl: tokens a
    prompt}, {impl: `counting_op`'s row counters}, what else the last
    batcher says: its rings and op counters). Either side must have been
    selected by the decode step it was forced on."""
    from flexflow_tpu.kernels.registry import KERNELS
    from flexflow_tpu.obs.attention_rows import (
        publish_attention_row_metrics)
    from flexflow_tpu.serving.sched import ContinuousBatcher

    outs, rows, last = {}, {}, {}
    for impl in ("reference", "pallas"):
        before = _selected(family)
        with KERNELS.override(family, impl), ContinuousBatcher(
                model, max_queue=len(prompts), **batcher_kw) as batcher:
            handles = [batcher.submit(p, new_tokens) for p in prompts]
            outs[impl] = [np.asarray(h.result(timeout=900.0))
                          for h in handles]
            last = {"rings": dict(batcher._rings),
                    "counters": batcher.op_counters()}
            rows[impl] = publish_attention_row_metrics(
                model, batcher.registry, state=last["counters"])[counting_op]
        selected = _selected(family, before)
        assert selected[impl] > 0, (
            f"{family} forced to {impl}, but the decode step never"
            f" selected it: {selected}")
    return outs, rows, last


def _over_read(rows) -> Dict[str, float]:
    return {impl: round(r["rows_read"] / r["rows_filled"], 3)
            for impl, r in rows.items()}


# ---------------------------------------------------------------------------
# phase: latent
# ---------------------------------------------------------------------------
def phase_latent(sizes: Sizes, seed: int) -> Dict:
    """One latent attention layer (ops/latent_attention.py) under a causal
    LM head, through ContinuousBatcher, greedy: the `latent_decode` family
    forced to its reference and to its kernel, token against token. The
    prompts' positions span several of the kernel's row blocks."""
    import flexflow_tpu as ff

    heads, q_rank, kv_rank, nope, rope, v_dim = sizes.latent
    config = ff.FFConfig()
    config.batch_size = 1
    config.allow_mixed_precision = False
    config.num_devices = 1
    model = ff.FFModel(config)
    tokens = model.create_tensor([1, sizes.window], ff.DataType.DT_INT32)
    t = model.embedding(tokens, sizes.vocab, sizes.hidden,
                        ff.AggrMode.AGGR_MODE_NONE, name="emb")
    attn = model.latent_attention(t, heads, q_rank, kv_rank, nope, rope,
                                  v_dim, name="attn")
    t = model.layer_norm(model.add(t, attn), [-1], name="ln")
    model.softmax(model.dense(t, sizes.vocab, name="lm_head"))
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.0),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, sizes.vocab, size=(n,)).astype(np.int32)
               for n in sizes.prompts]

    outs, rows, _ = _served_both_ways(
        "latent_decode", model, prompts, sizes.new_tokens, "attn",
        max_len=sizes.max_len, num_slots=sizes.slots,
        page_size=sizes.page_size)
    near_ties: List[Dict] = []
    identical = _count_identical("latent_decode_forced", model,
                                 outs["pallas"], outs["reference"], prompts,
                                 near_ties)
    _print_near_ties(near_ties)
    return {"model": f"latent attention {heads}h q{q_rank}/kv{kv_rank}/"
                     f"nope{nope}/rope{rope}/v{v_dim} at hidden"
                     f" {sizes.hidden}, {sizes.slots} slots x"
                     f" {sizes.max_len} rows, f32",
            "prompt_lengths": list(sizes.prompts),
            "new_tokens": sizes.new_tokens,
            "compared": "greedy tokens, kernel vs reference (near-tie"
                        f" log-prob tolerance {NEAR_TIE_LOGPROB:g})",
            "token_parity": f"{identical}/{len(prompts)} identical",
            "near_ties": len(near_ties),
            "rows_read_over_filled": _over_read(rows)}


# ---------------------------------------------------------------------------
# phase: hybrid
# ---------------------------------------------------------------------------
def phase_hybrid(sizes: Sizes, seed: int) -> Dict:
    """One block of a state-space mixer beside grouped-KV rotary attention
    (ops/ssm.py, ops/attention.py) under a causal LM head, through
    ContinuousBatcher, greedy, against the lockstep GenerativeSession on
    the same weights: chunked prefill that carries the recurrent state
    (chunks that do not divide the scan's block), one prompt more than
    there are slots, so one slot is REUSED and its state reset. The
    attention's decode core (`attention_decode`) forced to its reference
    chain and to its kernel, each against the session."""
    import flexflow_tpu as ff
    from flexflow_tpu.serving.generate import GenerativeSession

    heads, kv_heads, head_dim, d_ssm, ssm_heads, d_state, groups = \
        sizes.hybrid
    config = ff.FFConfig()
    config.batch_size = 1
    config.allow_mixed_precision = False
    config.num_devices = 1
    model = ff.FFModel(config)
    tokens = model.create_tensor([1, sizes.window], ff.DataType.DT_INT32)
    t = model.embedding(tokens, sizes.vocab, sizes.hidden,
                        ff.AggrMode.AGGR_MODE_NONE, name="emb")
    h = model.rms_norm(t, [-1], eps=1e-5, name="ln1")
    mixed = model.ssm_mixer(h, d_ssm, ssm_heads, d_state, n_groups=groups,
                            name="mixer")
    attn = model.multihead_attention(
        h, h, h, sizes.hidden, heads, kdim=head_dim, vdim=head_dim,
        bias=False, causal=True, kv_heads=kv_heads,
        rope_parameters={"rope_theta": 1e11}, name="attn")
    t = model.layer_norm(model.add(t, model.add(mixed, attn)), [-1],
                         name="ln")
    model.softmax(model.dense(t, sizes.vocab, name="lm_head"))
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.0),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.RandomState(seed)
    lengths = tuple(sizes.prompts) + (sizes.prompts[0] + 7,) * max(
        0, sizes.slots + 1 - len(sizes.prompts))
    prompts = [rng.randint(1, sizes.vocab, size=(n,)).astype(np.int32)
               for n in lengths]
    session = GenerativeSession(model, max_len=sizes.max_len)
    refs = [np.asarray(session.generate(p[None, :], sizes.new_tokens)[0])
            for p in prompts]
    del session
    chunk = 7 * sizes.page_size      # no multiple of the scan's block
    outs, rows, last = _served_both_ways(
        "attention_decode", model, prompts, sizes.new_tokens, "attn",
        max_len=sizes.max_len, num_slots=sizes.slots,
        page_size=sizes.page_size, prefill_chunk_tokens=chunk)
    counts = last["counters"]["mixer"]
    assert counts["state_resets"] == len(prompts) > sizes.slots
    near_ties: List[Dict] = []
    identical = min(_count_identical(f"hybrid_{impl}", model, outs[impl],
                                     refs, prompts, near_ties)
                    for impl in sorted(outs))
    _print_near_ties(near_ties)
    return {"model": f"ssm mixer {ssm_heads}h x {d_ssm // ssm_heads} x"
                     f" {d_state} beside attention {heads}/{kv_heads}h of"
                     f" {head_dim} (rope) at hidden {sizes.hidden},"
                     f" {sizes.slots} slots x {sizes.max_len} rows, f32",
            "prompt_lengths": list(lengths), "new_tokens": sizes.new_tokens,
            "prefill_chunk_tokens": chunk,
            "compared": "greedy tokens, attention's decode kernel and its"
                        " reference chain, each vs lockstep GenerativeSession"
                        f" (near-tie log-prob tolerance {NEAR_TIE_LOGPROB:g})",
            "token_parity": f"{identical}/{len(prompts)} identical",
            "near_ties": len(near_ties),
            "rows_read_over_filled": _over_read(rows),
            "state_resets": int(counts["state_resets"]),
            "ssm_steps": int(counts["ssm_steps"])}


# ---------------------------------------------------------------------------
# phase: window
# ---------------------------------------------------------------------------
def phase_window(sizes: Sizes, seed: int) -> Dict:
    """One WINDOW attention layer and one full layer of differing head
    counts, each with its own rotation and a per-head gate
    (ops/attention.py), under a causal LM head, through ContinuousBatcher,
    greedy, against the lockstep GenerativeSession on the same weights:
    the window layer's cache is a ring of its window's rows in the pool,
    the batch-1 prefill holder and the session alike, the longer prompts
    wrap it, and one prompt more than there are slots reuses a slot whose
    ring the previous tenant left full. The full layer's decode core
    (`attention_decode`) forced to its reference chain and to its kernel,
    each against the session; the ring returns before that choice."""
    import flexflow_tpu as ff
    from flexflow_tpu.serving.generate import GenerativeSession

    full_heads, swa_heads, kv_heads, head_dim, window = sizes.window_pair
    config = ff.FFConfig()
    config.batch_size = 1
    config.allow_mixed_precision = False
    config.num_devices = 1
    model = ff.FFModel(config)
    tokens = model.create_tensor([1, sizes.window], ff.DataType.DT_INT32)
    t = model.embedding(tokens, sizes.vocab, sizes.hidden,
                        ff.AggrMode.AGGR_MODE_NONE, name="emb")
    attend = lambda x, heads, name, **kw: model.multihead_attention(
        x, x, x, sizes.hidden, heads, kdim=head_dim, vdim=head_dim,
        bias=False, causal=True, kv_heads=kv_heads, head_gate=True,
        name=name, **kw)
    h = model.rms_norm(t, [-1], eps=1e-6, name="ln1")
    t = model.add(t, attend(h, swa_heads, "l0_swa", window=window,
                            rope_parameters={"rope_theta": 1e4}))
    h = model.rms_norm(t, [-1], eps=1e-6, name="ln2")
    t = model.add(t, attend(h, full_heads, "l1_attn", rope_parameters={
        "rope_theta": 5e5, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}))
    t = model.layer_norm(t, [-1], name="ln")
    model.softmax(model.dense(t, sizes.vocab, name="lm_head"))
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.0),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.RandomState(seed)
    lengths = tuple(sizes.prompts) + (sizes.prompts[0] + 7,) * max(
        0, sizes.slots + 1 - len(sizes.prompts))
    prompts = [rng.randint(1, sizes.vocab, size=(n,)).astype(np.int32)
               for n in lengths]
    assert max(lengths) + sizes.new_tokens > 2 * window, \
        "no prompt wraps the ring"
    session = GenerativeSession(model, max_len=sizes.max_len)
    refs = [np.asarray(session.generate(p[None, :], sizes.new_tokens)[0])
            for p in prompts]
    del session
    chunk = 7 * sizes.page_size      # no divisor or multiple of the window
    outs, rows, last = _served_both_ways(
        "attention_decode", model, prompts, sizes.new_tokens, "l1_attn",
        max_len=sizes.max_len, num_slots=sizes.slots,
        page_size=sizes.page_size, prefill_chunk_tokens=chunk)
    rings = last["rings"]
    assert rings == {"l0_swa": min(window, sizes.max_len)}
    assert "l0_swa" not in last["counters"]   # a ring's rows follow from pos
    near_ties: List[Dict] = []
    identical = min(_count_identical(f"window_{impl}", model, outs[impl],
                                     refs, prompts, near_ties)
                    for impl in sorted(outs))
    _print_near_ties(near_ties)
    return {"model": f"window-{window} attention {swa_heads}/{kv_heads}h"
                     f" (ring of {rings['l0_swa']} rows) under full attention"
                     f" {full_heads}/{kv_heads}h of {head_dim}, gated, at"
                     f" hidden {sizes.hidden}, {sizes.slots} slots x"
                     f" {sizes.max_len} rows, f32",
            "prompt_lengths": list(lengths), "new_tokens": sizes.new_tokens,
            "prefill_chunk_tokens": chunk, "ring_rows": rings["l0_swa"],
            "compared": "greedy tokens, the full layer's decode kernel and"
                        " its reference chain, each vs lockstep"
                        " GenerativeSession (near-tie log-prob tolerance"
                        f" {NEAR_TIE_LOGPROB:g})",
            "token_parity": f"{identical}/{len(prompts)} identical",
            "near_ties": len(near_ties),
            "rows_read_over_filled": _over_read(rows)}


# ---------------------------------------------------------------------------
# phase: mesh (--chips 4)
# ---------------------------------------------------------------------------
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _spread(tree) -> Dict:
    """How a pytree of arrays really sits on the devices."""
    import jax

    leaves = jax.tree.leaves(tree)
    devices = set()
    split = 0
    for a in leaves:
        shards = a.addressable_shards
        devices |= {s.device.id for s in shards}
        if shards[0].data.shape != a.shape:
            split += 1
    return {"devices": sorted(devices), "arrays": len(leaves),
            "arrays_split": split}


def _mesh_run(sizes: Sizes, seed: int, x, y, **build_kw) -> Dict:
    """Build, place, compile and fit one plan; on a mesh also prove that
    the batch and the parameters are really spread over its devices and
    that the compiled step holds the collectives the plan implies."""
    import jax

    model = build_bert(sizes, seed, layers=sizes.mesh_layers, **build_kw)
    axes = dict(model.parallel_axes)
    out = {"parallel_axes": axes}
    if model.mesh is not None:
        n_mesh = int(np.prod(list(axes.values())))
        inputs, label = model._prep_step_batch([x], y, 0, sizes.batch)
        batch = _spread(inputs)
        text = _compiled_text(model._train_step, model.params,
                              model.opt_state, model.state, inputs, label,
                              jax.random.PRNGKey(0))
        found = [c for c in _COLLECTIVES if c in text]
        assert "all-reduce" in found or "reduce-scatter" in found, (
            f"a {axes} plan must reduce gradients; collectives: {found}")
        out.update(mesh_devices=n_mesh, batch=batch, collectives=found,
                   tpu_custom_call="tpu_custom_call" in text)
    model.fit([x], y, epochs=1)
    if model.mesh is not None:
        # after a step the parameters are the step's own outputs: where
        # the compiled program keeps them, not where init put them
        params = _spread(model.params)
        out["params"] = params
        assert len(params["devices"]) == n_mesh, (
            f"parameters on devices {params['devices']}, mesh has {n_mesh}")
        if axes.get("data", 1) > 1:
            assert (len(batch["devices"]) == n_mesh
                    and batch["arrays_split"] > 0), (
                f"dp plan, but the batch is not split: {batch}")
        if axes.get("model", 1) > 1:
            assert params["arrays_split"] > 0, "tp plan, no weight is split"
    out["losses"] = [round(v, 5) for v in _step_losses(model)]
    out["step_ms"] = round(_steady_ms(model), 2)
    return out


def phase_mesh(sizes: Sizes, seed: int, n: int = 4) -> Dict:
    """The same BERT width on `n` chips: (a) a fixed dp x tp mesh, (b) the
    plan the Unity search (measured costs, inside compile()) picks — each
    against a one-device run of the same seed and global batch."""
    from flexflow_tpu.search.simulator import get_op_cost_cache

    sizes = dataclasses.replace(sizes, batch=sizes.mesh_batch)
    x, y = bert_data(sizes, seed, sizes.mesh_steps)
    one = _mesh_run(sizes, seed, x, y, num_devices=1)
    fixed = _mesh_run(sizes, seed, x, y, num_devices=n,
                      parallel_axes={"data": n // 2, "model": 2})
    assert fixed["mesh_devices"] == n
    searched = _mesh_run(sizes, seed, x, y, num_devices=n,
                         search_budget=sizes.search_budget)
    cache = get_op_cost_cache(None)
    assert not cache.failures, f"op-cost measurement failed: {cache.failures}"
    worst = 0.0
    for name, run in (("dp x tp", fixed), ("searched", searched)):
        for s, (got, want) in enumerate(zip(run["losses"], one["losses"])):
            worst = max(worst, _check_close(
                f"{name} step {s} loss vs one device", got, want,
                MESH_LOSS_RTOL))
    return {"model": f"bert {sizes.mesh_layers}L/{sizes.hidden}/"
                     f"{sizes.heads}h/seq{sizes.seq}/batch{sizes.batch}",
            "one_device": one, "dp_x_tp": fixed, "searched": searched,
            "search_ops_measured": cache.misses,
            "search_failures": len(cache.failures),
            "compared": f"per-step loss vs one device, rel <="
                        f" {MESH_LOSS_RTOL:g}",
            "worst_rel": float(f"{worst:.2e}")}


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the multi-chip phase and its comparison")
    args = ap.parse_args(argv)

    from flexflow_tpu.runtime.platform import enable_compile_cache

    devices = require_tpu("chip_smoke", args.chips)  # first thing

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({"phase": "device", **device,
                      "compile_cache": cache_dir}), flush=True)

    sizes = Sizes()
    if args.chips == 4:
        run_phase("mesh", clock, phase_mesh, sizes, args.seed, 4)
    else:
        run_phase("train", clock, phase_train, sizes, args.seed)
        run_phase("kernels", clock, phase_kernels, sizes, args.seed)
        run_phase("search", clock, phase_search, sizes, args.seed)
        run_phase("serve", clock, phase_serve, sizes, args.seed)
        run_phase("latent", clock, phase_latent, sizes, args.seed)
        run_phase("hybrid", clock, phase_hybrid, sizes, args.seed)
        run_phase("window", clock, phase_window, sizes, args.seed)
    cold_start_line()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
