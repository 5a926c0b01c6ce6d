"""Model step: the median stamp-to-stamp interval of the traced run's
window, host clock. The steadier statistic beside ``window_tokens_per_s``: a
single stall moves the rate and not the median, a slower step both."""


def read(facts):
    return facts["window"] and facts["window"]["step_median_ms"]
