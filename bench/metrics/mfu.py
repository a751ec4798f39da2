"""The whole step's share of the chip's peak: model FLOPs of the live
tokens the window processed (prompts in prefill, generated tokens fed back
through decode, each attending over its real context, and the head where
logits are sampled) over the window's wall time times peak FLOP/s.  Idle
slots and padding are not work."""

from bench.flops import request_model_flops


def read(obs):
    if obs.peak is None or not obs.requests or obs.window_s <= 0:
        return None
    flops = sum(request_model_flops(obs.spec.shape, r.prompt_len,
                                    max(r.served - 1, 0))
                for r in obs.requests)
    return 100.0 * flops / (obs.window_s * obs.peak.flops)
