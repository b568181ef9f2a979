"""How a configuration file becomes the program's own ``ModelConfig``."""
from __future__ import annotations

import weights as W


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file; refuses
    what the program cannot run as the file states it."""
    from repro.configs.base import ModelConfig

    m = W.dims(cfg)
    as_run = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
              "logits_scaling": 1.0,
              "attention_multiplier": m["hd"] ** -0.5,
              "rms_norm_eps": 1e-5, "hidden_act": "silu",
              "tie_word_embeddings": True, "attention_bias": False}
    for k, v in as_run.items():
        if cfg[k] != v:
            raise ValueError(f"the program runs {k}={v!r}; the configuration "
                             f"states {cfg[k]!r}")
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=m["L"], d_model=m["d"],
        num_heads=m["hq"], num_kv_heads=m["hkv"], head_dim=m["hd"],
        d_ff=m["f"], vocab_size=m["V"], qkv_bias=False, norm="rmsnorm",
        activation="swiglu", rope="rope", rope_theta=cfg["rope_theta"],
        tie_embeddings=True)
