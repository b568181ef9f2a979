"""rwkv6-3b [ssm] — Finch, data-dependent token shift and decay,
attention-free. [arXiv:2404.05892; hf RWKV/v6-Finch-3B-HF]

The adapter ranks (32 for the token shift, 64 for the decay) are the
modelling code's convention at this width (RWKV-LM ``x060``), not keys of
the published ``config.json``; the GroupNorm eps is 1e-5 *
head_size_divisor**2 with head_size_divisor 8."""
from repro.configs.base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="rwkv6-3b",
    family="ssm",
    num_layers=32,
    d_model=2560,
    num_heads=0,
    num_kv_heads=0,
    d_ff=8960,
    vocab_size=65536,
    norm="layernorm",
    activation="rwkv",            # channel-mix (squared-relu gated)
    rope="none",
    attention_kind="none",
    ssm=SSMConfig(kind="rwkv6", head_size=64, chunk_size=64, mix_rank=32,
                  decay_rank=64, group_norm_eps=6.4e-4),
    notes="Attention-free; WKV6 data-dependent per-channel decay; constant-size "
          "recurrent state => long_500k decode runs",
)
