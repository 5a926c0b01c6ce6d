"""Kernels: the least time the chip could take for the matmuls a step
REQUIRES over the held experts - nine a layer over the held claims
(``facts["family"]["held_expert_matmuls"]``, from shapes: the larger of
FLOPs over the bf16 peak and bytes over the HBM peak) - over the traced
time of ``moe_held_expert_ms``. The claims are those the traced steps
REALISED: the run's ``routing`` read on the state just before the traced
steps and just after them, on the pool's batches those steps were fed
(``facts["trace"]["batches"]``), the mean of the two. Since PR 40 the share
does the work of the claims it holds, so the EXPECTED claims' least time
over the realised claims' time read the routing and could pass 100 by
routing alone; a run that says no routing is therefore not read. What is
left under 100 is the padding of every expert's claims to whole tiles, a
heavy expert's rows that no token claimed, the loop's gathers and
scatter-adds, and the matmuls' own efficiency. The routers move from step
to step under the generator's rate, so the two readings bracket the traced
steps and do not repeat them: PERF.md section 3 says by how much."""

from benchmark import common

moe_held_expert_ms = common.load_by_name("layer_metrics", "moe_held_expert_ms")


def realised_rows(facts, expected):
    """Held claims a layer in the traced steps, as the run's routing
    bracketed them; None where the run read no routing around a trace."""
    routing, trace = facts.get("routing") or {}, facts.get("trace") or {}
    ends = [routing[end]["held_claims"] for end in ("traced", "open") if end in routing]
    fed = trace.get("batches")
    if len(ends) < 2 or not fed:
        return None
    return expected * sum(end[b] for end in ends for b in fed) / (len(ends) * len(fed))


def read(facts):
    peaks, ms = facts.get("peaks"), moe_held_expert_ms.read(facts)
    if not peaks or not ms:
        return None
    experts = facts["family"]["held_expert_matmuls"]
    rows = realised_rows(facts, experts["rows"])
    if rows is None:
        return None
    least = max(
        experts["flops_per_row"] * rows / peaks["bf16_flops_per_s"],
        (experts["bytes_weights"] + experts["bytes_per_row"] * rows) / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / (ms * 1e-3)
