"""Device time in all-gathers, reduce-scatters and all-reduces over device
busy time, mean over the chips (trace)."""


def read(result, trace):
    if trace is None or trace["busy_s"] <= 0:
        return None
    return 100.0 * trace["collective_share"]
