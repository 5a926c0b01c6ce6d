"""Model step: device time a traced step in what carries a rank's held
claims to its experts and back (``moe/dispatch``: each token's weight on
each held expert; ``moe/combine``: the weights applied), forward and
backward, by scope: a path is matched by the scope's own name with ``moe``
before it. None where the family states no held share or nothing ran
under either name."""


def held_scope_ms(facts, scopes):
    """Milliseconds a traced step under any of ``scopes`` after ``moe``."""
    paths_s = (facts.get("trace") or {}).get("paths_s")
    if not paths_s or not (facts.get("family") or {}).get("held_expert_matmuls"):
        return None
    seconds = 0.0
    for paths in paths_s.values():
        for path, s in paths.items():
            names = path.split("/")
            if "moe" in names and scopes & set(names[names.index("moe"):]):
                seconds += s
    return seconds / facts["trace"]["steps"] * 1e3 if seconds else None


def read(facts):
    return held_scope_ms(facts, {"dispatch", "combine"})
