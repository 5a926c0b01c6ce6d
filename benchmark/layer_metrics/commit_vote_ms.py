"""Step transaction: mean seconds per step in Manager's ``commit_vote``
timer (group 0, the whole life of the process)."""


def read(facts):
    timer = (facts.get("manager_metrics") or {}).get("timers_s", {}).get("commit_vote")
    if not timer or not timer.get("n"):
        return None
    return timer["total_s"] / timer["n"] * 1e3
