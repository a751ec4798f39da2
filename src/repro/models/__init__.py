from .model import (ModelConfig, init_params, forward, loss_fn, init_cache,
                    decode_step, prefill, encode,
                    ATTN, ATTN_WINDOW, MAMBA, DENSE, MOE_MLP, MOE_DENSE, NONE)

__all__ = ["ModelConfig", "init_params", "forward", "loss_fn", "init_cache",
           "decode_step", "prefill", "encode",
           "ATTN", "ATTN_WINDOW", "MAMBA", "DENSE", "MOE_MLP", "MOE_DENSE",
           "NONE"]
