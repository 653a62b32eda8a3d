"""The tiny size of the `falcon_h1_34b_1chip` rehearsals: hidden 64, 4 query
heads on 2 KV heads of 16, MLP 128, a mixer of 4 heads x 16 channels with a
16-wide state in 2 groups scanned in blocks of 8, 2 layers, vocabulary 128;
float32 multipliers as published."""
from benchmark.tests.tiny import tiny_context

WIDTHS = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, intermediate_size=128,
              mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
              mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=8,
              vocab_size=128)


def tiny_fh1_context(seed: int = 2**31 + 11, seconds: float = 2.0,
                     trace: bool = False, **over):
    ctx = tiny_context("fh1_decode_sat", seed=seed, seconds=seconds,
                       trace=trace)
    ctx.config.update(WIDTHS)
    ctx.config.update(over)
    return ctx
