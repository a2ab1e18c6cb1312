"""Small configurations of the benchmark's models, for the CPU."""
import dataclasses

import jax.numpy as jnp

from repro.configs import registry


def gpt(dtype=jnp.float32):
    cfg = dataclasses.replace(registry.reduced_config("paper-gpt3-large", 4),
                              dtype=dtype)
    conf = {"name": "tiny-gpt", "reference": "gpt", "arch": "paper-gpt3-large",
            "n_layers": 4, "d_model": 64, "n_heads": cfg.num_heads,
            "d_head": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "learning_rate": 1e-3,
            "activation": cfg.act, "dtype": str(jnp.dtype(dtype))}
    return cfg, conf


def zamba2(dtype=jnp.float32):
    cfg = dataclasses.replace(registry.reduced_config("zamba2-1.2b", 4),
                              layer_pattern=("mamba",) * 4, dtype=dtype)
    conf = {"name": "tiny-zamba2", "reference": "zamba2",
            "arch": "zamba2-1.2b", "n_layers": 4, "d_model": 64,
            "n_heads": cfg.num_heads, "d_head": cfg.resolved_head_dim,
            "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "learning_rate": 1e-3, "activation": cfg.act,
            "dtype": str(jnp.dtype(dtype)), "d_state": cfg.ssm.d_state,
            "d_conv": cfg.ssm.d_conv, "ssm_head_dim": cfg.ssm.head_dim,
            "ssm_heads": cfg.ssm.num_heads(64), "chunk": cfg.ssm.chunk,
            "shared_period": cfg.shared_attn_period}
    return cfg, conf


def cell(runtime: str, stages: int = 2, **limits):
    return {"name": f"tiny.{runtime}", "config": "tiny", "runtime": runtime,
            "stages": stages, "schedule": "rrfp", "hint": "bf", "seq": 32,
            "microbatches": 4, "mb_rows": 1, "chips": 1, "limits": limits}
