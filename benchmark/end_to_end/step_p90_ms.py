"""90th percentile of the stamp-to-stamp interval in the window, host
clock: the tail a periodic stall shows in."""


def read(facts):
    return facts["window"] and facts["window"]["step_p90_ms"]
