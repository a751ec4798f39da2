"""Mean host time of a decode tick: each ``engine.tick`` span in the window
less its ``engine.tick.wait`` child, the time the host spent blocked on
the decode's logits.  What is left is the host's own work per tick
(building the inputs and dispatching, the logits to the host, sampling,
retiring), during which no decode work is queued on the device."""

from bench.observe import TICK, SpanIndex

WAIT = "engine.tick.wait"


def read(obs):
    ticks = SpanIndex(obs.named(TICK))
    waited = {}
    for w in obs.named(WAIT):
        tick = ticks.around(w.t0, w.t1)
        if tick is not None:
            waited[id(tick)] = (tick, w.dur)
    if not waited:
        return None
    return 1e3 * sum(t.dur - w for t, w in waited.values()) / len(waited)
