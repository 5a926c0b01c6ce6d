"""Schedules: the cell's rate over the raw loop's - same process, same
seed, same estimator; the raw loop runs a few steps after the window of
a traced one-group run."""


def read(facts):
    raw = facts.get("raw") or {}
    if not raw.get("tokens_per_s") or not facts.get("window"):
        return None
    return facts["window"]["tokens_per_s"] / raw["tokens_per_s"]
