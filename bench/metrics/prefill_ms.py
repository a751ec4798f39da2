"""Mean duration of the program's ``engine.prefill`` spans in the window:
one prompt through the model, its cache merged into the slot, its first
token sampled."""

from bench.observe import PREFILL


def read(obs):
    spans = obs.named(PREFILL)
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans) / len(spans)
