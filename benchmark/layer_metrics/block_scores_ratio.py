"""Kernels: the (query, key) pairs the flash kernels' schedule computes
under the block-diffusion mask over the pairs the mask shows
(``facts["family"]["block_flash"]``: the program's own count of the tiles
its walk meets, and ``L^2 + L B``), a head of a sequence; least 1.0. What
is above 1 is hidden pairs inside the whole tiles on the edges of the
mask's three regions. None where the family states no block mask."""


def read(facts):
    flash = (facts.get("family") or {}).get("block_flash")
    if not flash or not flash.get("required_pairs"):
        return None
    return flash["computed_pairs"] / flash["required_pairs"]
