"""Committed tokens per second: the tokens of every step committed in the
window over the whole window, both edges on step stamps
(estimator.window), host clock."""


def read(facts):
    return facts["window"] and facts["window"]["tokens_per_s"]
