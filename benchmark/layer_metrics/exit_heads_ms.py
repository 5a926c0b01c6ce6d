"""Model step: device time a traced step in a looped model's exits, both
directions and the recomputation between them: everything under the scopes
``exits`` (the gate, the exit distribution, the loss over it), ``readout``
(each pass's logits) and ``loss`` (each exit's cross entropy a position),
inside the loop or after it. A path is counted once whichever of the names
it holds. None where the program names no ``exits`` (every model but a
looped one)."""

from benchmark.common import load_by_name

_loop = load_by_name("layer_metrics", "loop_stack_ms")


def read(facts):
    paths_s = (facts.get("trace") or {}).get("paths_s")
    if not paths_s:
        return None
    found = [
        (set(path.split("/")), s) for paths in paths_s.values() for path, s in paths.items()
    ]
    if not any("exits" in names for names, _ in found):
        return None
    seconds = sum(s for names, s in found if names & _loop.EXITS)
    return seconds / facts["trace"]["steps"] * 1e3
