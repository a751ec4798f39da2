"""Mean duration of the program's ``engine.tick`` spans in the window: one
decode step over every slot, its logits to the host and sampled."""

from bench.observe import TICK


def read(obs):
    spans = obs.named(TICK)
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans) / len(spans)
