"""Builder of `laguna_xs2_1chip`: a causal LM whose layers mix window and
full grouped-KV rotary attention of differing head counts, a per-head gate
on every attention, a leading dense MLP and sigmoid-routed sparse experts
beside a shared one, built through the public FFModel calls (embedding,
rms_norm, multihead_attention(kv_heads, rope_parameters, window, head_gate),
moe_router(scoring), gated_experts, dense, multiply, add, softmax),
compiled, given the benchmark's seeded bf16 weights ONE GROUP AT A TIME
(embedding, each layer, head: the reference is handed the same groups), and
put behind a ContinuousBatcher with the configuration's deployment (the
prefix cache's default is off for a model with a ring: no page names its
rows).

The published lists (`layer_types`, `mlp_layer_types`,
`num_attention_heads_per_layer`) stay whole in the file; the first
`num_hidden_layers` entries are built. A full layer's attention op is
`l<i>_attn`, a window layer's `l<i>_swa`, so that a metric can select
either by its device scope."""
from __future__ import annotations

from typing import Dict, List, Tuple

from .causal_lm import build_batcher  # noqa: F401  (the same deployment keys)
from .transformer_stack import check_tree

FULL, SLIDING = "full_attention", "sliding_attention"


def attention_name(cfg: Dict, i: int) -> str:
    return f"l{i}_attn" if cfg["layer_types"][i] == FULL else f"l{i}_swa"


def _layer_spec(cfg: Dict, i: int) -> Dict:
    h, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    heads = int(cfg["num_attention_heads_per_layer"][i])
    kvh = int(cfg["num_key_value_heads"])
    p = f"l{i}"
    spec = {
        f"{p}_ln1": {"gamma": ((h,), "gain")},
        attention_name(cfg, i): {
            "wq": ((h, heads, d), "matrix"), "wk": ((h, kvh, d), "matrix"),
            "wv": ((h, kvh, d), "matrix"), "wo": ((heads, d, h), "matrix"),
            "wg": ((h, heads), "matrix")},
        f"{p}_ln2": {"gamma": ((h,), "gain")},
    }
    mlp = lambda name, f: {
        f"{p}_{name}_gate": {"kernel": ((h, f), "matrix")},
        f"{p}_{name}_up": {"kernel": ((h, f), "matrix")},
        f"{p}_{name}_down": {"kernel": ((f, h), "matrix")}}
    if cfg["mlp_layer_types"][i] == "dense":
        spec.update(mlp("mlp", int(cfg["intermediate_size"])))
        return spec
    f, held = int(cfg["moe_intermediate_size"]), int(cfg["n_routed_experts"])
    spec.update({
        f"{p}_router": {"kernel": ((h, int(cfg["num_experts"])), "matrix")},
        f"{p}_experts": {"w_gate": ((held, h, f), "matrix"),
                         "w_up": ((held, h, f), "matrix"),
                         "w_down": ((held, f, h), "matrix")}})
    spec.update(mlp("shared", int(cfg["shared_expert_intermediate_size"])))
    return spec


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
    held, total = int(cfg["n_routed_experts"]), int(cfg["num_experts"])
    if cfg.get("hidden_act", "silu") != "silu" or held != total:
        raise ValueError("swa_moe_lm: SiLU experts, all of them held here")
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

    def gated_mlp(x, f, name):
        gate = model.dense(x, f, ff.ActiMode.AC_MODE_SILU, use_bias=False,
                           name=f"{name}_gate")
        up = model.dense(x, f, use_bias=False, name=f"{name}_up")
        return model.dense(model.multiply(gate, up), hidden, use_bias=False,
                           name=f"{name}_down")

    for i in range(int(cfg["num_hidden_layers"])):
        kind = cfg["layer_types"][i]
        h = model.rms_norm(t, [-1], eps=eps, name=f"l{i}_ln1")
        a = model.multihead_attention(
            h, h, h, hidden, int(cfg["num_attention_heads_per_layer"][i]),
            kdim=int(cfg["head_dim"]), vdim=int(cfg["head_dim"]), bias=False,
            causal=True, kv_heads=int(cfg["num_key_value_heads"]),
            rope_parameters=cfg["rope_parameters"][kind],
            window=int(cfg["sliding_window"]) if kind == SLIDING else 0,
            head_gate=True, name=attention_name(cfg, i))
        t = model.add(t, a)
        u = model.rms_norm(t, [-1], eps=eps, name=f"l{i}_ln2")
        if cfg["mlp_layer_types"][i] == "dense":
            t = model.add(t, gated_mlp(u, int(cfg["intermediate_size"]),
                                       f"l{i}_mlp"))
            continue
        w, idx = model.moe_router(
            u, total, int(cfg["num_experts_per_tok"]),
            scale=float(cfg["moe_routed_scaling_factor"]),
            scoring="sigmoid", name=f"l{i}_router")
        routed = model.gated_experts(
            u, w, idx, total, int(cfg["moe_intermediate_size"]),
            local_experts=(0, held), name=f"l{i}_experts")
        shared = gated_mlp(u, int(cfg["shared_expert_intermediate_size"]),
                           f"l{i}_shared")
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
