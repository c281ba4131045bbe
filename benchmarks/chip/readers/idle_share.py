"""Share of the window in which no program ran on the device."""


def read(trace, spec: dict, ctx: dict):
    if trace.window_s <= 0 or trace.busy_s <= 0:
        return None
    return 1.0 - trace.busy_s / trace.window_s
