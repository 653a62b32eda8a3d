"""Builder of `mistral_small4_ep4`: a latent-attention, sparse-expert causal
LM as one chip's share of an expert-parallel deployment, built through the
public FFModel calls (embedding, rms_norm, latent_attention, moe_router,
gated_experts, dense, multiply, add, softmax), compiled, given the
benchmark's seeded bf16 weights ONE GROUP AT A TIME (embedding, each layer,
head: the reference is handed the same groups), and put behind a
ContinuousBatcher with the configuration's deployment."""
from __future__ import annotations

from typing import Dict, List, Tuple

from .causal_lm import build_batcher  # noqa: F401  (the same deployment keys)
from .transformer_stack import check_tree


def _layer_spec(cfg: Dict, i: int) -> Dict:
    h = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    qr, kvr = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd, f = int(cfg["v_head_dim"]), int(cfg["moe_intermediate_size"])
    held = int(cfg["n_routed_experts"])
    p = f"l{i}"
    return {
        f"{p}_ln1": {"gamma": ((h,), "gain")},
        f"{p}_attn": {
            "wq_a": ((h, qr), "matrix"), "q_norm": ((qr,), "gain"),
            "wq_b": ((qr, heads, nope + rope), "matrix"),
            "wkv_a": ((h, kvr + rope), "matrix"),
            "kv_norm": ((kvr,), "gain"),
            "wkv_b": ((kvr, heads, nope + vd), "matrix"),
            "wo": ((heads, vd, h), "matrix")},
        f"{p}_ln2": {"gamma": ((h,), "gain")},
        f"{p}_router": {"kernel": ((h, int(cfg["router_width"])), "matrix")},
        f"{p}_experts": {"w_gate": ((held, h, f), "matrix"),
                         "w_up": ((held, h, f), "matrix"),
                         "w_down": ((held, f, h), "matrix")},
        f"{p}_shared_gate": {"kernel": ((h, f), "matrix")},
        f"{p}_shared_up": {"kernel": ((h, f), "matrix")},
        f"{p}_shared_down": {"kernel": ((f, h), "matrix")},
    }


def param_groups(cfg: Dict) -> List[Tuple[str, Dict]]:
    """[(group name, spec)]: "emb", "l0" .. , "head". A group is what is
    made, and handed to the reference, at one time."""
    h, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    groups = [("emb", {"emb": {"weight": ((v, h), "matrix")}})]
    groups += [(f"l{i}", _layer_spec(cfg, i))
               for i in range(int(cfg["num_hidden_layers"]))]
    groups.append(("head", {"final_norm": {"gamma": ((h,), "gain")},
                            "lm_head": {"kernel": ((h, v), "matrix")}}))
    return groups


def param_spec(cfg: Dict) -> Dict:
    return {op: ws for _g, spec in param_groups(cfg) for op, ws in spec.items()}


def make_group(cfg: Dict, seed: int, name: str) -> Dict:
    """One group's seeded bf16 weights: its own key from (seed, position of
    the group), so a group can be made alone."""
    from .. import weights

    groups = param_groups(cfg)
    position = [g for g, _ in groups].index(name)
    return weights.make_weights(groups[position][1], int(seed) * 64 + position,
                                cfg.get("tensor_dtype", "bfloat16"))


def build_model(cfg: Dict, seed: int):
    import flexflow_tpu as ff

    dep = cfg["deployment"]
    hidden, vocab = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    f = int(cfg["moe_intermediate_size"])
    if cfg["hidden_act"] != "silu" or int(cfg["n_shared_experts"]) != 1 \
            or int(cfg["first_k_dense_replace"]) != 0:
        raise ValueError("mla_moe_lm: a layer is latent attention + SiLU"
                         " experts with one shared expert, every layer")
    config = ff.FFConfig()
    config.batch_size = int(dep["declared_batch"])
    config.allow_mixed_precision = False   # every tensor is declared bf16
    # (a test may state float32 tensors, to hold the program to the
    # reference at a rounding error)
    dt = ff.DataType(cfg.get("tensor_dtype", "bfloat16"))
    config.num_devices = 1
    model = ff.FFModel(config)
    tokens = model.create_tensor(
        [int(dep["declared_batch"]), int(dep["window"])], ff.DataType.DT_INT32)
    t = model.embedding(tokens, vocab, hidden, ff.AggrMode.AGGR_MODE_NONE,
                        dtype=dt, name="emb")
    eps = float(cfg["rms_norm_eps"])
    local = (int(cfg["first_local_expert"]), int(cfg["n_routed_experts"]))
    for i in range(int(cfg["num_hidden_layers"])):
        a = model.latent_attention(
            model.rms_norm(t, [-1], eps=eps, name=f"l{i}_ln1"),
            int(cfg["num_attention_heads"]), int(cfg["q_lora_rank"]),
            int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"]),
            int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]),
            rope_parameters=cfg["rope_parameters"], eps=eps,
            name=f"l{i}_attn")
        t = model.add(t, a)
        h = model.rms_norm(t, [-1], eps=eps, name=f"l{i}_ln2")
        w, idx = model.moe_router(
            h, int(cfg["router_width"]), int(cfg["num_experts_per_tok"]),
            scale=float(cfg["routed_scaling_factor"]), name=f"l{i}_router")
        routed = model.gated_experts(
            h, w, idx, int(cfg["router_width"]), f, local_experts=local,
            name=f"l{i}_experts")
        gate = model.dense(h, f, ff.ActiMode.AC_MODE_SILU, use_bias=False,
                           name=f"l{i}_shared_gate")
        up = model.dense(h, f, use_bias=False, name=f"l{i}_shared_up")
        shared = model.dense(model.multiply(gate, up), hidden, use_bias=False,
                             name=f"l{i}_shared_down")
        t = model.add(t, model.add(routed, shared))
    t = model.rms_norm(t, [-1], eps=eps, name="final_norm")
    # bf16 kernel, float32 logits: the softmax over the vocabulary and the
    # greedy pick see what the float32 accumulation gave
    model.softmax(model.dense(
        t, vocab, use_bias=False, datatype=ff.DataType.DT_FLOAT,
        kernel_datatype=dt, name="lm_head"))
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.0),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    install_weights(model, cfg, seed)
    return model


def install_weights(model, cfg: Dict, seed: int) -> None:
    check_tree(model.params, param_spec(cfg), cfg["name"])
    model.params = None   # free the program's own initial weights first
    params = {}
    for name, _spec in param_groups(cfg):
        params.update(make_group(cfg, seed, name))
    model.params = params


def build_program(cfg: Dict, traffic: Dict, chips: int, seed: int):
    model = build_model(cfg, seed)
    return model, build_batcher(model, cfg)
