"""Builder of `bert_osdi22`: the program's own `build_bert_encoder` ->
`compile()`, then the benchmark's seeded weights in place of the program's
initial ones. Normal entry points only."""
from __future__ import annotations

from typing import Dict

from .transformer_stack import check_tree, stack_spec


def param_spec(cfg: Dict) -> Dict:
    return stack_spec(cfg, "layer", "tok_emb", "cls", int(cfg["num_labels"]))


def build_program(cfg: Dict, traffic: Dict, chips: int, seed: int):
    """A compiled FFModel ready for `fit(steps_per_execution=K)`, holding the
    seeded weights and fresh optimizer state."""
    import flexflow_tpu as ff
    import jax.numpy as jnp
    from flexflow_tpu.models import TransformerConfig, build_bert_encoder

    dep = cfg["deployment"]
    batch = int(dep["per_chip_batch"]) * chips
    seq = int(cfg["sequence_length"])
    config = ff.FFConfig()
    config.num_devices = chips
    config.batch_size = batch
    model = ff.FFModel(config)
    tokens = model.create_tensor([batch, seq], ff.DataType.DT_INT32)
    tcfg = TransformerConfig(
        hidden_size=int(cfg["hidden_size"]),
        embedding_size=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_layers=int(cfg["num_hidden_layers"]), sequence_length=seq,
        ffn_mult=int(cfg["intermediate_size"]) // int(cfg["hidden_size"]),
        vocab_size=int(cfg["vocab_size"]))
    build_bert_encoder(model, tokens, tcfg, num_classes=int(cfg["num_labels"]))
    opt = cfg["optimizer"]
    axes = traffic.get("parallel_axes")
    model.compile(
        optimizer=ff.AdamOptimizer(
            model, alpha=float(opt["alpha"]), beta1=float(opt["beta1"]),
            beta2=float(opt["beta2"]), epsilon=float(opt["epsilon"]),
            weight_decay=float(opt["weight_decay"]),
            moments_dtype=jnp.dtype(opt["moments_dtype"])),
        loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        parallel_axes=dict(axes) if axes else None)
    install_weights(model, cfg, seed)
    return model


def install_weights(model, cfg: Dict, seed: int) -> None:
    """Seeded weights where the program's initial ones were (same tree, same
    placement), and optimizer state made anew from them."""
    from .. import weights

    spec = param_spec(cfg)
    check_tree(model.params, spec, "bert_osdi22")
    model.params = None
    model.opt_state = None
    model.params = weights.make_weights(spec, seed, "float32",
                                        weights.replicated(model.mesh))
    model.opt_state = model.optimizer.init_state(model.params)
