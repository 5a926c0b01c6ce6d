"""Model step: device time a traced step in what the diffusion objective
costs beside the stack, both directions: every path that holds ``noise``
(the draw of ``(t, m)`` from the batch, the noised copy), ``readout`` (the
noised copy's logits) or ``loss`` (the masked positions' cross entropy, the
routers' balance loss). A path is counted once whichever of the names it
holds. None where the family states no block mask (every model but a
diffusion one) or the run was not traced."""

HEAD = {"noise", "readout", "loss"}


def read(facts):
    paths_s = (facts.get("trace") or {}).get("paths_s")
    if not paths_s or not (facts.get("family") or {}).get("block_flash"):
        return None
    seconds = sum(
        s for paths in paths_s.values() for path, s in paths.items()
        if HEAD & set(path.split("/"))
    )
    return seconds / facts["trace"]["steps"] * 1e3 if seconds else None
