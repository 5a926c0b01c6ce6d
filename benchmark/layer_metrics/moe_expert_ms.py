"""Model step: device time a traced step in the grouped expert matmuls of
``moe/experts``, forward and backward (``jax.lax.ragged_dot``, which the
TPU compiler makes Mosaic custom calls named ``ragged-dot-none``), from
the trace's breakdown: matched on name and kind.

The breakdown keeps the ten longest operations and names one by its
output, so a layer's nine matmuls show as up to four labels, and a label
under the tenth is missing (in ``olmoe-ft1`` the one call that writes
W_down's gradient). Each of the nine requires the same operations
(2 x rows x d x f), so the visible time is scaled to all of them by
calls / visible calls (the family's ``calls_by_output`` says how many
calls write which shape): the reading does not jump when another
operation crosses the tenth place. In ``olmoe-ft1`` 8 of 9 are visible,
41.2 ms, and the reading is 46.4 against 46.44 ms summed by hand from
the whole trace (PERF.md section 5). ``facts["trace"]["kernels_s"]
["ragged-dot-none"]`` holds that unscaled sum of all nine since PR 32;
this reader keeps its arithmetic so that its ledger line stays one
quantity. None where no call is visible, or one writes a shape the
family did not count."""

import re


def visible(facts):
    """(seconds of the traced steps in the visible ``ragged-dot-none``
    calls, how many of a step's calls those are, the family's
    ``expert_matmuls`` from ``facts["family"]``), or None."""
    trace = facts.get("trace")
    experts = (facts.get("family") or {}).get("expert_matmuls")
    if not trace or not experts:
        return None
    seconds, calls = 0.0, 0
    for label, s in trace["device_ops"]:
        name, _, rest = label.partition(" ")
        if name != "ragged-dot-none" or not label.endswith("custom-call"):
            continue
        shape = re.search(r"\[([\d,]*)\]", rest)
        count = experts["calls_by_output"].get(shape.group(1)) if shape else None
        if count is None:
            return None  # a shape the family did not count: nothing to scale by
        seconds += s
        calls += count
    return (seconds, calls, experts) if calls else None


def read(facts):
    found = visible(facts)
    if found is None:
        return None
    seconds, calls, experts = found
    return seconds / facts["trace"]["steps"] * 1e3 * experts["calls"] / calls
