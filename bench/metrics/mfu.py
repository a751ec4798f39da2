"""The whole step's share of the chip's peak: model FLOPs of the live
tokens the window processed (prompts in prefill, generated tokens fed back
through decode, each attending over its real context, and the head where
logits are sampled) over the window's wall time times peak FLOP/s.  Idle
slots and padding are not work."""


def read(obs):
    if obs.peak is None or not obs.requests or obs.window_s <= 0:
        return None
    count = obs.spec.shape.request_model_flops
    flops = sum(count(r.prompt_len, max(r.served - 1, 0))
                for r in obs.requests)
    return 100.0 * flops / (obs.window_s * obs.peak.flops)
