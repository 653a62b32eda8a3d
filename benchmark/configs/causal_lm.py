"""Builder of `lm_osdi22w`: the causal twin of the BERT widths, built through
the public FFModel calls (embedding, multihead_attention(causal=True), dense,
layer_norm, softmax), compiled, given the benchmark's seeded weights, and put
behind a ContinuousBatcher with the configuration's deployment."""
from __future__ import annotations

from typing import Dict

from .transformer_stack import check_tree, stack_spec


def param_spec(cfg: Dict) -> Dict:
    return stack_spec(cfg, "l", "emb", "lm_head", int(cfg["vocab_size"]))


def build_model(cfg: Dict, seed: int):
    import flexflow_tpu as ff

    dep = cfg["deployment"]
    hidden = int(cfg["hidden_size"])
    config = ff.FFConfig()
    # the graph is declared for ONE sequence of `window` tokens: the batcher's
    # dispatches are batch-polymorphic (slots for decode, 1 for a chunk), and
    # compile()'s static memory gate prices the declared batch as a training
    # step (48 x 1,024 tokens of vocabulary logits would be refused)
    config.batch_size = int(dep["declared_batch"])
    config.allow_mixed_precision = False   # float32 weights, cache, activations
    config.num_devices = 1
    model = ff.FFModel(config)
    tokens = model.create_tensor([int(dep["declared_batch"]),
                                  int(dep["window"])],
                                 ff.DataType.DT_INT32)
    t = model.embedding(tokens, int(cfg["vocab_size"]), hidden,
                        ff.AggrMode.AGGR_MODE_NONE, name="emb")
    for i in range(int(cfg["num_hidden_layers"])):
        attn = model.multihead_attention(
            t, t, t, hidden, int(cfg["num_attention_heads"]),
            kdim=int(cfg["head_dim"]), vdim=int(cfg["head_dim"]),
            causal=True, name=f"l{i}_attn")
        t = model.layer_norm(model.add(t, attn), [-1], name=f"l{i}_ln1")
        h = model.dense(t, int(cfg["intermediate_size"]),
                        ff.ActiMode.AC_MODE_GELU, name=f"l{i}_ff1")
        h = model.dense(h, hidden, name=f"l{i}_ff2")
        t = model.layer_norm(model.add(t, h), [-1], name=f"l{i}_ln2")
    model.softmax(model.dense(t, int(cfg["vocab_size"]), name="lm_head"))
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.0),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    install_weights(model, cfg, seed)
    return model


def install_weights(model, cfg: Dict, seed: int) -> None:
    from .. import weights

    spec = param_spec(cfg)
    check_tree(model.params, spec, "lm_osdi22w")
    model.params = None   # free the program's own initial weights first
    model.params = weights.make_weights(spec, seed, "float32")


def build_batcher(model, cfg: Dict):
    """The deployment, not started."""
    from flexflow_tpu.serving.sched.continuous import ContinuousBatcher

    dep = cfg["deployment"]
    # the whole backlog of a closed-loop cell waits in the queue before the
    # window opens: both admission bounds are sized for it
    return ContinuousBatcher(
        model, max_len=int(dep["max_len"]), num_slots=int(dep["num_slots"]),
        page_size=int(dep["page_size"]), max_queue=int(dep["max_queue"]),
        queue_pages_budget=int(dep["max_queue"]) * (
            int(dep["max_len"]) // int(dep["page_size"])),
        prefill_chunk_tokens=int(dep["prefill_chunk_tokens"]))


def build_program(cfg: Dict, traffic: Dict, chips: int, seed: int):
    model = build_model(cfg, seed)
    return model, build_batcher(model, cfg)
