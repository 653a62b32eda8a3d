"""Builder of `falcon_h1_34b_1chip`: a causal LM whose every block runs a
Mamba-2 state-space mixer and grouped-KV rotary attention side by side,
built through the public FFModel calls (embedding, scalar_multiply,
rms_norm, ssm_mixer, multihead_attention(kv_heads, rope_parameters), dense,
sigmoid, multiply, add, softmax), compiled, given seeded bf16 weights ONE
GROUP AT A TIME (embedding, each layer, head: the reference is handed the
same groups), and put behind a ContinuousBatcher with the configuration's
deployment and the prefix cache off (recurrent state is per sequence: no
page of it can be shared).

Seeded weights are normal with a std PER MATRIX: 1 / (sqrt(fan-in) x the
muP multipliers that stand between the matrix and the next norm), so that
under the published multipliers every projection's output, every branch's
contribution and the residual stream are all of order one (std 0.02
everywhere leaves the three branches at a few percent of the embedding and
the logits at 0.01, and a comparison blind to what the blocks compute).
`A_log` and `dt_bias` come from the family's initialisation ranges.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

from .transformer_stack import check_tree

# elements above which a leaf is drawn in row blocks: the normal draw is
# float32 before its cast, and the embedding's 1.34 G values would be 5.3 GB
_BLOCK_ELEMENTS = 1 << 27


def _stds(cfg: Dict) -> Dict[str, float]:
    """{kind: std} of the normal matrices."""
    e = int(cfg["hidden_size"])
    inv = lambda fan_in, *mult: 1.0 / (math.sqrt(fan_in) * math.prod(mult))
    heads, d = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    sm = [float(m) for m in cfg["ssm_multipliers"]]
    return {
        "emb": 1.0 / float(cfg["embedding_multiplier"]),
        "wq": inv(e, float(cfg["attention_in_multiplier"])),
        "wk": inv(e, float(cfg["attention_in_multiplier"]),
                  float(cfg["key_multiplier"])),
        "wv": inv(e, float(cfg["attention_in_multiplier"])),
        "wo": inv(heads * d, float(cfg["attention_out_multiplier"])),
        # one std for the five slices: their multipliers' geometric mean
        "w_in": inv(e, float(cfg["ssm_in_multiplier"]),
                    math.prod(sm) ** (1.0 / len(sm))),
        "conv_w": inv(int(cfg["mamba_d_conv"])),
        "w_out": inv(int(cfg["mamba_d_ssm"]),
                     float(cfg["ssm_out_multiplier"])),
        "gate": inv(e, float(cfg["mlp_multipliers"][0])),
        "up": inv(e),
        "down": inv(int(cfg["intermediate_size"]),
                    float(cfg["mlp_multipliers"][1])),
        "head": inv(e, float(cfg["lm_head_multiplier"])),
        "bias": 0.1,
    }


def _layer_spec(cfg: Dict, i: int) -> Dict:
    e, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    heads, kvh = int(cfg["num_attention_heads"]), int(
        cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    d_ssm, mh = int(cfg["mamba_d_ssm"]), int(cfg["mamba_n_heads"])
    conv_dim = d_ssm + 2 * int(cfg["mamba_n_groups"]) * int(
        cfg["mamba_d_state"])
    p = f"l{i}"
    return {
        f"{p}_ln1": {"gamma": ((e,), "gain")},
        f"{p}_mixer": {
            "w_in": ((e, d_ssm + conv_dim + mh), "w_in"),
            "conv_w": ((int(cfg["mamba_d_conv"]), conv_dim), "conv_w"),
            "conv_b": ((conv_dim,), "bias"),
            "dt_bias": ((mh,), "dt_bias"), "A_log": ((mh,), "a_log"),
            "D": ((mh,), "gain"), "norm": ((d_ssm,), "gain"),
            "w_out": ((d_ssm, e), "w_out")},
        f"{p}_attn": {"wq": ((e, heads, d), "wq"), "wk": ((e, kvh, d), "wk"),
                      "wv": ((e, kvh, d), "wv"), "wo": ((heads, d, e), "wo")},
        f"{p}_ln2": {"gamma": ((e,), "gain")},
        f"{p}_mlp_gate": {"kernel": ((e, f), "gate")},
        f"{p}_mlp_up": {"kernel": ((e, f), "up")},
        f"{p}_mlp_down": {"kernel": ((f, e), "down")},
    }


def param_groups(cfg: Dict) -> List[Tuple[str, Dict]]:
    """[(group name, spec)]: "emb", "l0" .. , "head". A group is what is
    made, and handed to the reference, at one time."""
    e, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    groups = [("emb", {"emb": {"weight": ((v, e), "emb")}})]
    groups += [(f"l{i}", _layer_spec(cfg, i))
               for i in range(int(cfg["num_hidden_layers"]))]
    groups.append(("head", {"final_norm": {"gamma": ((e,), "gain")},
                            "lm_head": {"kernel": ((e, v), "head")}}))
    return groups


def param_spec(cfg: Dict) -> Dict:
    return {op: ws for _g, spec in param_groups(cfg) for op, ws in spec.items()}


def _draw(key, shape, kind: str, stds: Dict[str, float], dt):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if kind == "gain":
        return (1.0 + 0.02 * jax.random.normal(key, shape, f32)).astype(dt)
    if kind == "a_log":      # A = -exp(A_log), A in [-16, -1]
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)
                       ).astype(dt)
    if kind == "dt_bias":    # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        step = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                          math.log(1e-1)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
    std = stds[kind]
    n = math.prod(shape)
    if n <= _BLOCK_ELEMENTS:
        return (jax.random.normal(key, shape, f32) * std).astype(dt)
    blocks = -(-n // _BLOCK_ELEMENTS)
    while shape[0] % blocks:
        blocks += 1
    part = (shape[0] // blocks,) + tuple(shape[1:])
    rows = jax.lax.map(
        lambda i: (jax.random.normal(jax.random.fold_in(key, i), part, f32)
                   * std).astype(dt), jnp.arange(blocks))
    return rows.reshape(shape)


def make_group(cfg: Dict, seed: int, name: str) -> Dict:
    """One group's seeded weights in ONE jitted call: its own key from
    (seed, position of the group), so a group can be made alone."""
    import jax
    import jax.numpy as jnp

    from .. import weights

    groups = param_groups(cfg)
    position = [g for g, _ in groups].index(name)
    spec = groups[position][1]
    dt = jnp.dtype(cfg.get("tensor_dtype", "bfloat16"))
    stds = _stds(cfg)
    flat = [(op, w, tuple(shape), kind) for op, ws in sorted(spec.items())
            for w, (shape, kind) in sorted(ws.items())]

    def build(key):
        out: Dict[str, Dict[str, object]] = {}
        for i, (op, w, shape, kind) in enumerate(flat):
            out.setdefault(op, {})[w] = _draw(
                jax.random.fold_in(key, i), shape, kind, stds, dt)
        return out

    return jax.jit(build)(weights.seed_key(int(seed) * 64 + position))


def build_model(cfg: Dict, seed: int):
    import flexflow_tpu as ff
    from flexflow_tpu.runtime.initializers import ZeroInitializer

    dep = cfg["deployment"]
    e, vocab = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    f = int(cfg["intermediate_size"])
    if cfg["hidden_act"] != "silu" or not cfg["mamba_rms_norm"] \
            or cfg["mamba_norm_before_gate"] or cfg.get("rope_scaling") \
            or int(cfg["mamba_d_ssm"]) != int(cfg["mamba_n_heads"]) * int(
                cfg["mamba_d_head"]):
        raise ValueError("hybrid_ssm_lm: a block is a gate-then-norm Mamba-2"
                         " mixer beside unscaled rotary attention and a SiLU"
                         " gated MLP")
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
    # the embedding's and the head's own initial values are thrown away a
    # moment later: zeros cost no random draw of 1.34 G values each
    zero = ZeroInitializer()
    t = model.scalar_multiply(
        model.embedding(tokens, vocab, e, ff.AggrMode.AGGR_MODE_NONE,
                        dtype=dt, kernel_initializer=zero, name="emb"),
        float(cfg["embedding_multiplier"]))
    eps = float(cfg["rms_norm_eps"])
    rope = {"rope_theta": float(cfg["rope_theta"]), "rope_type": "default"}
    for i in range(int(cfg["num_hidden_layers"])):
        h = model.rms_norm(t, [-1], eps=eps, name=f"l{i}_ln1")
        mixed = model.ssm_mixer(
            model.scalar_multiply(h, float(cfg["ssm_in_multiplier"])),
            int(cfg["mamba_d_ssm"]), int(cfg["mamba_n_heads"]),
            int(cfg["mamba_d_state"]), n_groups=int(cfg["mamba_n_groups"]),
            d_conv=int(cfg["mamba_d_conv"]),
            chunk_size=int(cfg["mamba_chunk_size"]),
            slice_multipliers=cfg["ssm_multipliers"],
            conv_bias=bool(cfg["mamba_conv_bias"]), eps=eps,
            state_dtype=ff.DataType(cfg.get("ssm_state_dtype", "float32")),
            name=f"l{i}_mixer")
        u = model.scalar_multiply(h, float(cfg["attention_in_multiplier"]))
        attn = model.multihead_attention(
            u, u, u, e, int(cfg["num_attention_heads"]),
            kdim=int(cfg["head_dim"]), vdim=int(cfg["head_dim"]),
            bias=bool(cfg["attention_bias"]), causal=True,
            kv_heads=int(cfg["num_key_value_heads"]), rope_parameters=rope,
            key_multiplier=float(cfg["key_multiplier"]), name=f"l{i}_attn")
        t = model.add(t, model.add(
            model.scalar_multiply(mixed, float(cfg["ssm_out_multiplier"])),
            model.scalar_multiply(attn,
                                  float(cfg["attention_out_multiplier"]))))
        h = model.rms_norm(t, [-1], eps=eps, name=f"l{i}_ln2")
        gate = model.scalar_multiply(
            model.dense(h, f, use_bias=False, name=f"l{i}_mlp_gate"),
            float(cfg["mlp_multipliers"][0]))
        up = model.dense(h, f, use_bias=False, name=f"l{i}_mlp_up")
        act = model.multiply(model.multiply(gate, model.sigmoid(gate)), up)
        t = model.add(t, model.scalar_multiply(
            model.dense(act, e, use_bias=False, name=f"l{i}_mlp_down"),
            float(cfg["mlp_multipliers"][1])))
    t = model.rms_norm(t, [-1], eps=eps, name="final_norm")
    # bf16 kernel, float32 logits: the softmax over the vocabulary and the
    # greedy pick see what the float32 accumulation gave
    model.softmax(model.scalar_multiply(
        model.dense(t, vocab, use_bias=False, datatype=ff.DataType.DT_FLOAT,
                    kernel_datatype=dt, kernel_initializer=zero,
                    name="lm_head"),
        float(cfg["lm_head_multiplier"])))
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


def build_batcher(model, cfg: Dict):
    """The deployment, not started: the prefix cache off (`prefix_cache_pages`
    0), as a model that keeps state per sequence must run."""
    from flexflow_tpu.serving.sched.continuous import ContinuousBatcher

    dep = cfg["deployment"]
    return ContinuousBatcher(
        model, max_len=int(dep["max_len"]), num_slots=int(dep["num_slots"]),
        page_size=int(dep["page_size"]), max_queue=int(dep["max_queue"]),
        queue_pages_budget=int(dep["max_queue"]) * (
            int(dep["max_len"]) // int(dep["page_size"])),
        prefill_chunk_tokens=int(dep["prefill_chunk_tokens"]),
        prefix_cache_pages=0)


def build_program(cfg: Dict, traffic: Dict, chips: int, seed: int):
    model = build_model(cfg, seed)
    return model, build_batcher(model, cfg)
