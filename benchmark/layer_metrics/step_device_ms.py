"""Model step: device busy time per traced step, from the trace (union of
the XLA Ops intervals over the traced steps)."""


def read(facts):
    trace = facts.get("trace")
    if not trace:
        return None
    return trace["busy_s"] / trace["steps"] * 1e3
