"""Model step: device time a traced step in what stands between a
latent-attention layer's maps and its flash kernels (``attn/mla/qk_rows``:
the one rotated key broadcast to every head, the rotary embedding of q's and
k's last numbers, the transposes to the kernels' rows and the padding of q,
k and v to one width of lanes), forward and backward, every such layer: the
part of latent attention's time that is layout and no product. None where
the program names no such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "attn/mla/qk_rows")
