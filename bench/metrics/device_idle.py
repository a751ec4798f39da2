"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device's operation intervals over the window."""


def read(obs):
    trace = obs.trace
    if trace is None or trace.window_s <= 0 or not trace.ops:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
