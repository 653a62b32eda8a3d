"""The tiny size of the `laguna_xs2_1chip` rehearsals: hidden 64, 6 query
heads (full) / 8 (sliding) on 2 KV heads of 16, window 8, a dense MLP of 128
in layer 0, 16 experts of 32 chosen 4 at a time and a shared one of 32, TWO
periods (9 layers: both layer kinds, both rotations, the ring wrapped many
times), vocabulary 128."""
from benchmark.tests.tiny import tiny_context

WIDTHS = dict(num_hidden_layers=9, hidden_size=64, num_key_value_heads=2,
              head_dim=16, intermediate_size=128, sliding_window=8,
              ring_rows=8, num_experts=16, n_routed_experts=16,
              num_experts_per_tok=4, moe_intermediate_size=32,
              shared_expert_intermediate_size=32, vocab_size=128,
              num_attention_heads_per_layer=[6, 8, 8, 8] * 10,
              # the fragile rule's threshold is read at the cell's size; at
              # this width nearly every margin over 8 expert layers of 16
              # experts is under it
              check_rule={"router_margin_min": 0.0})


def tiny_lgx_context(seed: int = 2**31 + 11, seconds: float = 2.0,
                     trace: bool = False, **over):
    ctx = tiny_context("lgx_decode_sat", seed=seed, seconds=seconds,
                       trace=trace)
    ctx.config.update(WIDTHS)
    ctx.config.update(over)
    return ctx
