"""Model step: device time a traced step in a looped model's stack: every
operation under the scope ``loop`` (``models/olmoe.py``: ``_looped``, the
scan over the passes) that is of the layers - the T passes forward, the
same passes recomputed in the backward scan, and their backward pass -
and not of the exits, which run inside the loop too and are
``exit_heads_ms``'s. None where the program names no such scope (a
program without the loop: the parent of PR 43).

Paths are read by name (``reduce/spans.py``: ``loop/while/body/
closed_call/attn/flash_fwd``); what runs under ``loop`` itself - the scan's
glue around its body: the stacked carries, the float32 sum of the weights'
gradient - is the loop's too. The scan's own ``while`` operation is an event
of the device's line as well, one that ENCLOSES its body's, but it comes
under no name (``unscoped``: PERF.md section 7) and so is not read here."""

EXITS = {"exits", "readout", "loss"}  # what a pass's exit runs under
RECOMPUTED = {"rematted_computation"}  # JAX's name for a checkpoint's second forward


def loop_ms(facts, wanted=None, unwanted=frozenset()):
    """Milliseconds a traced step below the scope ``loop`` in paths that
    hold one of ``wanted`` (any, if None) and none of ``unwanted``."""
    paths_s = (facts.get("trace") or {}).get("paths_s")
    if not paths_s:
        return None
    seconds = 0.0
    for paths in paths_s.values():
        for path, s in paths.items():
            names = path.split("/")
            if "loop" not in names:
                continue
            below = set(names[names.index("loop") + 1:])
            if not below & unwanted and (wanted is None or below & wanted):
                seconds += s
    return seconds / facts["trace"]["steps"] * 1e3 if seconds else None


def read(facts):
    return loop_ms(facts, unwanted=EXITS)
