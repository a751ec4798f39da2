"""Mean wait of a request in the admission queue: the ``queue_wait_s`` of
the program's ``engine.admit`` spans in the window, from the request's
arrival to the start of its admission into a slot."""

ADMIT, ATTR = "engine.admit", "queue_wait_s"


def read(obs):
    waits = [s.attrs[ATTR] for s in obs.named(ADMIT) if ATTR in s.attrs]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
