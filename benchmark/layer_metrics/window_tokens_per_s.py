"""Model step: committed tokens per second, the tokens of every step
committed in the TRACED run's window over the whole window, both edges
on step stamps (estimator.window), host clock; stalls and failed steps
stay in the time. Until PR 32 this was the end-to-end ``tokens_per_s``.
It holds no bound now: the machine stops for 1-3 s in some windows (every
process on it at once, PERF.md section 7), one such stop moves a 51 s
rate by 2-7%, and no bound of at most 10% fits both a check that meets
stops and one that does not. ``step_p90_ms`` is the end-to-end number a
slower step moves; this is the rate kept beside it."""


def read(facts):
    return facts["window"] and facts["window"]["tokens_per_s"]
