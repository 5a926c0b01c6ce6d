"""Step transaction: mean seconds per step in Manager's ``quorum`` timer
(group 0, the whole life of the process, warm-up included)."""


def read(facts):
    timer = (facts.get("manager_metrics") or {}).get("timers_s", {}).get("quorum")
    if not timer or not timer.get("n"):
        return None
    return timer["total_s"] / timer["n"] * 1e3
