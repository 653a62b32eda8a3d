"""The tiny size of the `mistral_small4_ep4` rehearsals: hidden 64, 4 heads,
q 32 / kv 16 / nope 8 / rope 8 / v 16, 8 experts top-2 of which 4 are held
here, plus the shared expert, 2 layers, vocabulary 128."""
from benchmark.tests.tiny import tiny_context

WIDTHS = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
              q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
              qk_rope_head_dim=8, v_head_dim=16, qk_head_dim=16, head_dim=16,
              moe_intermediate_size=32, router_width=8, n_routed_experts=4,
              first_local_expert=2, num_experts_per_tok=2, vocab_size=128)


def tiny_ms4_context(seed: int = 2**31 + 11, seconds: float = 2.0,
                     trace: bool = False, **over):
    ctx = tiny_context("ms4_decode_sat", seed=seed, seconds=seconds,
                       trace=trace)
    ctx.config.update(WIDTHS)
    ctx.config.update(over)
    return ctx
