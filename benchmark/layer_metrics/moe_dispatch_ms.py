"""Model step: device time a traced step in the gathers of ``moe/dispatch``
and ``moe/combine`` that carry every claim's row to its expert's group and
back, forward and backward. On the chip they are fusions with no name of
their own, and they are found by what they write: a bf16 matrix of
claims x model width (``facts["family"]["expert_matmuls"]``: ``rows``,
``width``). That is every
fusion of ``olmoe-ft1``'s step that writes this shape today (four gathers,
16.05 ms by scope from the whole trace against 16.11 read here; PERF.md
section 5), but an elementwise fusion writing the same shape elsewhere in
the layer would be counted too. The reading by scope is in the same facts
since PR 32 (``program.scope_ms(facts, "moe/dispatch")`` plus
``"moe/combine"``, which also hold the argsorts and the weighted sum, not
the gathers alone); this reader keeps its arithmetic so that its ledger
line stays one quantity. None where the breakdown's ten longest
operations do not hold the label."""


def read(facts):
    trace = facts.get("trace")
    experts = (facts.get("family") or {}).get("expert_matmuls")
    if not trace or not experts:
        return None
    label = f"fusion bf16[{experts['rows']},{experts['width']}] fusion"
    seconds = sum(s for name, s in trace["device_ops"] if name == label)
    return seconds / trace["steps"] * 1e3 if seconds else None
