"""Pallas flash-attention kernel vs naive attention (interpret mode on CPU;
align-test strategy per SURVEY.md §4 applied to kernels)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels.flash_attention import (
    attention_reference,
    flash_attention,
)


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "b,lq,lk,h,d,bq,bk",
    [
        (2, 64, 64, 2, 32, 32, 32),     # even blocks
        (1, 40, 56, 2, 16, 32, 32),     # ragged lengths -> padding paths
        (2, 128, 128, 4, 64, 128, 128), # single block pair
    ],
)
def test_flash_forward_matches_reference(causal, b, lq, lk, h, d, bq, bk):
    q, k, v = _rand((b, lq, h, d), 0), _rand((b, lk, h, d), 1), _rand((b, lk, h, d), 2)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    b, l, h, d = 1, 48, 2, 16  # ragged vs 32-blocks: exercises padded bwd
    q, k, v = _rand((b, l, h, d), 3), _rand((b, l, h, d), 4), _rand((b, l, h, d), 5)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                              interpret=True)
        return jnp.sum(out * jnp.cos(out))

    def loss_ref(q, k, v):
        out = attention_reference(q, k, v, causal=causal)
        return jnp.sum(out * jnp.cos(out))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_flash_in_jit_and_vjp_composes():
    b, l, h, d = 2, 32, 2, 16
    q, k, v = _rand((b, l, h, d), 6), _rand((b, l, h, d), 7), _rand((b, l, h, d), 8)
    fn = jax.jit(functools.partial(flash_attention, interpret=True))
    out = fn(q, k, v)
    assert out.shape == (b, l, h, d)
    assert np.isfinite(np.asarray(out)).all()


def test_bert_train_step_through_flash():
    """Full compile+fit with the attention op forced onto the Pallas kernel
    (interpret mode on CPU)."""
    import flexflow_tpu as ff

    batch, seq, hidden, heads = 2, 16, 32, 4
    config = ff.FFConfig()
    config.batch_size = batch
    config.allow_mixed_precision = False
    model = ff.FFModel(config)
    inp = model.create_tensor([batch, seq, hidden])
    t = model.multihead_attention(inp, inp, inp, hidden, heads, use_flash=True)
    t = model.dense(t, 2)
    model.softmax(t)
    model.compile(
        optimizer=ff.SGDOptimizer(model, lr=0.01),
        loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
    )
    x = np.random.RandomState(0).randn(batch, seq, hidden).astype(np.float32)
    y = np.zeros((batch, seq, 1), dtype=np.int32)
    hist = model.fit([x], y, batch_size=batch, epochs=2)
    assert np.isfinite(hist[-1]["loss"])
    assert hist[-1]["loss"] <= hist[0]["loss"] + 1e-6


@pytest.mark.parametrize("causal", [False, True])
def test_bhld_layout_matches_blhd(causal):
    """layout="bhld" (projection-fused layout, no swapaxes) is numerically
    identical to the default layout on the same logical tensors."""
    b, l, h, d = 2, 48, 2, 16
    q, k, v = _rand((b, l, h, d), 9), _rand((b, l, h, d), 10), _rand((b, l, h, d), 11)

    def loss(fn):
        def wrapped(q, k, v):
            return jnp.sum(jnp.sin(fn(q, k, v)))
        return wrapped

    f_blhd = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32, interpret=True))
    f_bhld = loss(lambda q, k, v: jnp.swapaxes(flash_attention(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal, block_q=32, block_k=32, interpret=True,
        layout="bhld"), 1, 2))
    np.testing.assert_allclose(np.asarray(f_blhd(q, k, v)),
                               np.asarray(f_bhld(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    g1 = jax.grad(f_blhd, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_bhld, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [16,   # three blocks a side
                                   32,   # multi-block: online softmax path
                                   64])  # single padded block: plain softmax
def test_packed_kernel_matches_reference(causal, block):
    """flash_attention_packed on (b, l, h*d) matches the naive oracle on the
    equivalent (b, l, h, d) tensors — values and input gradients."""
    from flexflow_tpu.kernels.flash_attention import flash_attention_packed

    b, l, h, d = 2, 48, 4, 16
    q, k, v = _rand((b, l, h, d), 12), _rand((b, l, h, d), 13), _rand((b, l, h, d), 14)

    def loss_packed(q, k, v):
        out = flash_attention_packed(
            q.reshape(b, l, h * d), k.reshape(b, l, h * d),
            v.reshape(b, l, h * d), h, causal=causal, block_q=block,
            block_k=block, interpret=True)
        return jnp.sum(jnp.sin(out.reshape(b, l, h, d)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(attention_reference(q, k, v, causal=causal)))

    np.testing.assert_allclose(np.asarray(loss_packed(q, k, v)),
                               np.asarray(loss_ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    gp = jax.grad(loss_packed, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_flash_vs_einsum_attention_op_grads_parity():
    """Weight gradients agree between the einsum path and the flash (bhld)
    path — guards the projection-layout restructuring in the op's lower()."""
    import flexflow_tpu as ff

    batch, seq, hidden, heads = 2, 24, 32, 4
    grads = []
    for use_flash in (False, True):
        config = ff.FFConfig()
        config.batch_size = batch
        config.allow_mixed_precision = False
        model = ff.FFModel(config)
        inp = model.create_tensor([batch, seq, hidden])
        model.multihead_attention(inp, inp, inp, hidden, heads,
                                  use_flash=use_flash, name="attn")
        model.compile(
            optimizer=ff.SGDOptimizer(model, lr=0.0),
            loss_type=ff.LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
            metrics=[],
        )
        x = np.random.RandomState(1).randn(batch, seq, hidden).astype(np.float32)
        y = np.random.RandomState(2).randn(batch, seq, hidden).astype(np.float32)
        key = jax.random.PRNGKey(0)
        inputs = {model.input_ops[0].name: model.executor.shard_batch(x)}
        grads.append(model._grad_step(model.params, model.state, inputs,
                                      jnp.asarray(y), key))
    flat0 = jax.tree_util.tree_leaves(grads[0])
    flat1 = jax.tree_util.tree_leaves(grads[1])
    assert len(flat0) == len(flat1) and len(flat0) > 0
    for a, b_ in zip(flat0, flat1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def test_flash_attention_tp_heads_matches_single_device(tmp_path):
    """use_flash=True under a model=2 mesh (heads tensor-parallel) matches
    single-device numerics — regression for the packed path's TP guard:
    the packed (e, h*d) weight reshape would merge the sharded heads axis,
    so TP meshes must stay on the head-separated kernels."""
    import json

    import flexflow_tpu as ff
    from flexflow_tpu.ffconst import CompMode

    batch, seq, hidden, heads = 2, 24, 32, 4
    x = np.random.RandomState(3).randn(batch, seq, hidden).astype(np.float32)

    def build(import_file=None):
        config = ff.FFConfig()
        config.batch_size = batch
        config.allow_mixed_precision = False
        if import_file:
            config.import_strategy_file = import_file
        model = ff.FFModel(config)
        inp = model.create_tensor([batch, seq, hidden])
        t = model.multihead_attention(inp, inp, inp, hidden, heads,
                                      use_flash=True, name="attn")
        model.final_tensor = t
        model.compile(optimizer=ff.SGDOptimizer(model, lr=0.0),
                      loss_type=ff.LossType.LOSS_IDENTITY)
        return model, t

    single, out_s = build()
    feeds = {single.input_ops[0].name: x}
    vals, _, _ = single.executor.forward_values(
        single.params, single.state, feeds, None,
        CompMode.COMP_MODE_INFERENCE)
    ref = np.asarray(vals[out_s.guid])

    strat = {
        "mesh_axes": {"model": 2},
        "cost_us": 0.0, "memory_bytes": 0.0,
        "ops": {"attn": {"dp": 1, "tp": 2, "ep": 1, "ap": 1,
                         "tp_row": False}},
    }
    path = str(tmp_path / "strategy.json")
    with open(path, "w") as f:
        json.dump(strat, f)
    sharded, out_p = build(import_file=path)
    feeds = {sharded.input_ops[0].name: x}
    vals_p, _, _ = sharded.executor.forward_values(
        sharded.params, sharded.state, feeds, None,
        CompMode.COMP_MODE_INFERENCE)
    np.testing.assert_allclose(np.asarray(vals_p[out_p.guid]), ref,
                               rtol=2e-5, atol=2e-5)


def test_flash_vs_einsum_attention_op_parity():
    """The attention op produces the same output with use_flash on and off."""
    import flexflow_tpu as ff

    batch, seq, hidden, heads = 2, 24, 32, 4
    preds = []
    for use_flash in (False, True):
        config = ff.FFConfig()
        config.batch_size = batch
        config.allow_mixed_precision = False
        model = ff.FFModel(config)
        inp = model.create_tensor([batch, seq, hidden])
        model.multihead_attention(inp, inp, inp, hidden, heads,
                                  use_flash=use_flash, name="attn")
        model.compile(
            optimizer=ff.SGDOptimizer(model, lr=0.0),
            loss_type=ff.LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
            metrics=[],
        )
        x = np.random.RandomState(1).randn(batch, seq, hidden).astype(np.float32)
        preds.append(model.predict([x]))
    np.testing.assert_allclose(preds[0], preds[1], rtol=2e-5, atol=2e-5)


@functools.lru_cache(maxsize=None)
def _flash_bert_losses(parallel_axes):
    """Per-step losses of a 1-layer use_flash=True BERT fit; parallel_axes
    a tuple of (axis, size) pairs, () = one device."""
    import flexflow_tpu as ff
    from flexflow_tpu.models import TransformerConfig, build_bert_encoder

    batch, seq = 8, 16
    rng = np.random.RandomState(11)
    x = rng.randint(0, 64, size=(2 * batch, seq)).astype(np.int32)
    y = rng.randint(0, 2, size=(2 * batch, seq, 1)).astype(np.int32)
    config = ff.FFConfig()
    config.batch_size = batch
    config.num_devices = 4 if parallel_axes else 1
    config.allow_mixed_precision = False
    config.seed = 5
    model = ff.FFModel(config)
    tokens = model.create_tensor([batch, seq], ff.DataType.DT_INT32)
    build_bert_encoder(model, tokens, TransformerConfig(
        hidden_size=32, embedding_size=32, num_heads=4, num_layers=1,
        sequence_length=seq, vocab_size=64), use_flash=True)
    model.compile(
        optimizer=ff.SGDOptimizer(model, lr=0.1),
        loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[], parallel_axes=dict(parallel_axes) or None)
    model.fit([x], y, epochs=1)
    return [r["loss"] for r in model.step_stats.records()]


@pytest.mark.parametrize("axes", [{"data": 2, "model": 2}, {"data": 4}],
                         ids=["dp2_tp2_blhd", "dp4_packed"])
def test_flash_train_step_on_mesh_matches_single_device(axes):
    """The flash kernels inside a jitted train step on a mesh: GSPMD cannot
    partition a Mosaic kernel ("cannot be automatically partitioned" — the
    chip's compiler refuses the step), so the attention op runs them under
    shard_map on each device's batch/head shard. fwd+bwd, both layouts,
    against one device at the same seed."""
    np.testing.assert_allclose(_flash_bert_losses(tuple(axes.items())),
                               _flash_bert_losses(()), rtol=1e-4)
