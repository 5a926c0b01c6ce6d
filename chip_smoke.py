#!/usr/bin/env python3
"""The quickest proof that the step transaction still starts on the chip.

    python chip_smoke.py

drives the main path once — native Lighthouse + Manager + HostCollectives
+ OptimizerWrapper / PipelinedDDP, started through ``torchft_tpu.launcher``
with one replica group per chip — at the full width of the 111M "big"
dense LM (``models.big_config()``, B16 x S2048, flash attention, bf16
compute copy over f32 master params, random weights from a seed), and
checks what comes out by the repo's own means. It exits 0 only if every
phase passed and then prints, as the last line of stdout::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases, each in its own child process(es); any failure is fatal:

  probe    a short-lived child reports platform / device_kind / chip count
           and exits, releasing the chips. No TPU -> exit 1, no result.
  build    ``make -C native`` into a clean build directory: the tracked
           sources are what runs, not a stale ``_libtorchft.so``.
  kernels  (one chip) every Pallas kernel the repo wrote, COMPILED (the
           lowered module must carry a Mosaic custom call) and compared
           with its reference: flash attention forward+backward against
           the dense float32 path, the q8/bf16 wire kernels against the
           numpy oracle of the CPU tests.
  fleet    N = min(4, chips) groups, each pinned to its own chip by the
           launcher: K committed per-step-sync steps, then PipelinedDDP
           (transport="plan", compress="q8") steps that must report
           ``device_pack: true``, then one SIGKILL -> restart -> streamed
           heal -> rejoin, with parameter digests equal across groups.
           With one chip N = 1 and the heal has no peer: printed as skipped.
  mesh     (one process owning four chips) the sharded step over a
           data:2 x model:2 mesh, flash under shard_map. Skipped, and
           printed as skipped, below four chips.

One process per chip: this parent never initialises a JAX backend (it
would hold the chips its children need), and every child that needs the
chip runs with ``JAX_PLATFORMS=tpu`` so a missing chip is an
initialisation error, not JAX's own quiet CPU fallback. The compile cache
is ``JAX_COMPILATION_CACHE_DIR`` where set, else the checkout's fixed
``.jax_cache`` (torchft_tpu.platform).
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import signal
import subprocess
import sys
import time
from datetime import timedelta
from typing import Any, Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

BATCH, SEQ = 16, 2048  # the big config's history: B16 x S2048
SYNC_STEPS = 4         # committed OptimizerWrapper steps
PLAN_STEPS = 3         # PipelinedDDP(transport="plan", compress="q8")

# Flash vs the dense float32 reference, elementwise, as a fraction of
# max|ref|. Inputs are bf16 and the kernel feeds the MXU bf16 operands
# with f32 accumulation, so p, ds and every output are each rounded to 8
# significant bits (2^-9 = 0.2% relative) while the reference keeps f32
# throughout; a handful of such roundings stays under 1% of the largest
# magnitude. 3e-2 is the figure the repo's own bf16 CPU test uses
# (tests/test_flash_attention.py::test_under_jit_bf16).
FLASH_TOL = 3e-2


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# children: shared set-up
# ---------------------------------------------------------------------------


def _cache_counts() -> Dict[str, int]:
    """Persistent-compile-cache hits and misses of this process, from
    the program's own count of JAX's events (torchft_tpu/startup.py,
    heard from ``apply_compilation_cache_env`` on) — a cache that works
    shows up as hits in a restarted group and in a second run of the
    same checkout."""
    from torchft_tpu import startup

    counters = startup.record().snapshot()["counters"]
    return {
        "cache_hits": counters.get("compile_cache_hits", 0),
        "cache_misses": counters.get("compile_cache_misses", 0),
    }


def _child_setup(expect_chips: Optional[int] = None) -> Any:
    """First lines of every child that needs the chip: the one compile
    cache, then the backend — which must be the TPU."""
    from torchft_tpu.platform import apply_compilation_cache_env

    apply_compilation_cache_env()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"expected the tpu backend, JAX initialised {devices[0].platform!r}"
        )
    if expect_chips is not None and len(devices) != expect_chips:
        raise RuntimeError(
            f"expected {expect_chips} chip(s), this process sees "
            f"{len(devices)}: {devices}"
        )
    return devices


def _assert_mosaic(lowered: Any, want: int, what: str) -> None:
    n = lowered.as_text().count("tpu_custom_call")
    if n != want:
        raise AssertionError(
            f"{what}: lowered module has {n} Mosaic custom call(s), want "
            f"{want} — the kernel did not compile for the chip"
        )


# ---------------------------------------------------------------------------
# phase: probe
# ---------------------------------------------------------------------------


def child_probe() -> None:
    import jax

    devices = jax.devices()
    print(json.dumps({
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }), flush=True)


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------


def _dense_attention_f32(
    q: Any, k: Any, v: Any, window: Optional[int],
    block_mask: Optional[Tuple[int, int]] = None,
) -> Any:
    """The model's dense path (models/transformer.py _attention_impl: scaled
    scores, causal mask, f32 softmax, probs @ v) kept in float32 end to
    end, with the kernel's sliding window as one more mask term; or, under
    ``block_mask`` (B, L), the block-diffusion mask written out pair by
    pair: a clean query (rows 0..L-1) sees the clean keys of its own block
    and of earlier ones, a noised query (L..2L-1) the clean keys of earlier
    blocks and the noised keys of its own."""
    import jax
    import jax.numpy as jnp

    S, D = q.shape[1], q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(S)[None, :]
    mask = q_pos >= k_pos
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    if block_mask is not None:
        size, length = block_mask
        q_blk, k_blk = (q_pos % length) // size, (k_pos % length) // size
        q_noised, k_noised = q_pos >= length, k_pos >= length
        mask = jnp.where(
            k_noised, q_noised & (k_blk == q_blk),
            jnp.where(q_noised, k_blk < q_blk, k_blk <= q_blk),
        )
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _check_flash(
    name: str, B: int, S: int, H: int, D: int, window: Optional[int] = None,
    block_mask: Optional[Tuple[int, int]] = None, d_v: Optional[int] = None,
    fused: bool = False,
) -> None:
    """Flash forward + backward at (B, S, H, D), compiled, against the
    dense float32 reference on a seeded sample of 2 batch rows x 2 heads
    (attention is independent per (batch, head), so the sample's outputs
    and gradients are exactly the full problem's). ``fused``: through
    ``flash_attention_qkv`` on the three laid side by side as a fused
    projection has them (the dense models' call), not the three arrays.
    ``block_mask``: the block-diffusion mask in place of the causal one.
    ``d_v``: v, the output and dv at that width beside q and k at D (latent
    attention), through ``flash_attention_rows``, the one entry that takes
    two widths: dq and dk were NaN here when the kernels' blocks were all
    cut at q's (PERF.md section 6, PR 50)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.ops import flash_attention, flash_attention_qkv, flash_attention_rows

    keys = jax.random.split(jax.random.PRNGKey(B * 1000003 + S * 131 + D), 4)
    q, k, v, cot = (
        jax.random.normal(kk, (B, S, H, width), jnp.bfloat16)
        for kk, width in zip(keys, (D, D, d_v or D, d_v or D))
    )

    def flash_loss(q, k, v, cot):
        if fused:
            qkv = jnp.concatenate(
                [t.reshape(B, S, H * D) for t in (q, k, v)], axis=-1
            )
            out = flash_attention_qkv(qkv, H).reshape(B, S, H, D)
        elif d_v:
            rows = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, -1)  # noqa: E731
            out = flash_attention_rows(
                rows((q * jnp.float32(D ** -0.5)).astype(q.dtype)), rows(k), rows(v)
            ).reshape(B, H, S, d_v).transpose(0, 2, 1, 3)
        else:
            out = flash_attention(
                q, k, v, window=window, causal=block_mask is None, block_mask=block_mask
            )
        return jnp.sum(out.astype(jnp.float32) * cot.astype(jnp.float32)), out

    def ref_loss(q, k, v, cot):
        out = _dense_attention_f32(q, k, v, window, block_mask)
        return jnp.sum(out * cot), out

    def grad_of(loss):
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))

    lowered = grad_of(flash_loss).lower(q, k, v, cot)
    # forward kernel + the one fused backward kernel
    _assert_mosaic(lowered, 2, f"flash {name}")
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    (_, out), grads = jax.block_until_ready(compiled(q, k, v, cot))

    def sample(x):
        return x[:min(B, 2), :, :min(H, 2)].astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        (_, ref_out), ref_grads = jax.block_until_ready(
            grad_of(ref_loss)(sample(q), sample(k), sample(v), sample(cot))
        )

    errs = {}
    for label, got, ref in (
        ("out", out, ref_out),
        ("dq", grads[0], ref_grads[0]),
        ("dk", grads[1], ref_grads[1]),
        ("dv", grads[2], ref_grads[2]),
    ):
        got = np.asarray(sample(got))
        ref = np.asarray(ref)
        if not np.all(np.isfinite(got)):
            raise AssertionError(f"flash {name}: non-finite {label}")
        err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        errs[label] = round(err, 5)
        if err > FLASH_TOL:
            raise AssertionError(
                f"flash {name}: {label} differs from the dense f32 "
                f"reference by {err:.4f} of max|ref| (tolerance {FLASH_TOL})"
            )
    _say("kernels", (
        f"flash {name} B{B} S{S} H{H} D{D} window={window} block_mask={block_mask}"
        f"{f' d_v={d_v}' if d_v else ''}: compiled in "
        f"{compile_s:.1f}s, max err / max|ref| {errs} <= {FLASH_TOL}"
    ))


def _check_heads_to_rows(
    name: str, positions: str, S: int = 8192, H: int = 32, G: int = 4, D: int = 128,
) -> None:
    """``olmoe._heads_to_rows`` at the routed cells' shape - B 2, S 8192,
    32 query heads of 128 over 4 key heads, a norm per head, YaRN's blend
    (``positions`` "counted") or both halves of the sequence counting from
    0 ("stated") - compiled, q's pass and k's, against the plain
    composition ``_rmsnorm`` -> ``rope`` -> ``jnp.repeat`` -> the scale in
    float32: the rows, ``dx`` and the norm scale's gradient."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models import olmoe
    from torchft_tpu.models.transformer import _rmsnorm

    B, theta, eps = 2, 10000.0, 1e-6
    if positions == "stated":
        yarn, stated = None, jnp.tile(jnp.arange(S // 2), 2)
    else:
        yarn, stated = olmoe.Yarn(16.0, 512, attention_factor=1.2772588722239782), None
    keys = jax.random.split(jax.random.PRNGKey(S + D), 5)
    scale = (1.0 + 0.2 * jax.random.normal(keys[4], (D,))).astype(jnp.bfloat16)
    errs = {}
    for which, heads, group, mult in (("q", H, 1, D ** -0.5), ("k", G, H // G, 1.0)):
        spec = olmoe.HeadsToRows(heads, group, True, eps, mult)
        x = jax.random.normal(keys[0], (B, S, heads * D), jnp.bfloat16)
        cot = jax.random.normal(keys[1], (B * H, S, D), jnp.bfloat16)

        def mine(x, scale):
            tables = olmoe.rotary_tables(S, D, theta, yarn, stated)
            rows = olmoe._heads_to_rows(spec, x, scale, tables)
            return jnp.sum(rows.astype(jnp.float32) * cot.astype(jnp.float32)), rows

        def plain(x, scale):
            y = _rmsnorm(x.astype(jnp.float32).reshape(B, S, heads, D), scale.astype(jnp.float32), eps)
            y = jnp.repeat(olmoe.rope(y, theta, yarn, stated), group, axis=2) * mult
            rows = y.transpose(0, 2, 1, 3).reshape(-1, S, D)
            return jnp.sum(rows * cot.astype(jnp.float32)), rows

        def grad_of(loss):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))

        lowered = grad_of(mine).lower(x, scale)
        if "tpu_custom_call" in lowered.as_text():  # the pass is XLA's own
            raise AssertionError(f"heads_to_rows {name}: a Mosaic call in the pass")
        (_, rows), grads = jax.block_until_ready(lowered.compile()(x, scale))
        (_, want), want_grads = jax.block_until_ready(
            grad_of(plain)(x.astype(jnp.float32), scale.astype(jnp.float32))
        )
        for label, got, ref in (
            ("rows", rows, want), ("dx", grads[0], want_grads[0]), ("dw", grads[1], want_grads[1]),
        ):
            got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
            if not np.all(np.isfinite(got)):
                raise AssertionError(f"heads_to_rows {name}: non-finite {which} {label}")
            err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
            errs[f"{which}_{label}"] = round(err, 5)
            if err > FLASH_TOL:
                raise AssertionError(
                    f"heads_to_rows {name}: {which} {label} differs from the plain "
                    f"composition by {err:.4f} of max|ref| (tolerance {FLASH_TOL})"
                )
    _say("kernels", (
        f"heads_to_rows {name} B{B} S{S} H{H}/{G} D{D} positions {positions}: "
        f"max err / max|ref| {errs} <= {FLASH_TOL}"
    ))


def _check_delta_rule(name: str, S: int = 8192, H: int = 8, D: int = 128) -> None:
    """``ops.delta_rule.gated_delta_rule`` at a KDA layer's shape in
    ``ling3-ft1`` - bf16 q, k and v, float32 decays down to the bound of
    -5 - compiled under the chip's DEFAULT matmul precision, the output and
    the five gradients of its hand-written backward against the recurrence
    a position at a time in float32 at ``highest``
    (``benchmark/reference_ling.py``). The CPU tests hold the op at
    ``highest``; here the inverse by products and the solution made with it
    meet the one-pass bf16 products. Plain XLA: no Mosaic call, no
    triangular-solve call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_ling
    from torchft_tpu.ops.delta_rule import gated_delta_rule

    keys = jax.random.split(jax.random.PRNGKey(S + D), 6)
    q, k, v, cot = (jax.random.normal(kk, (1, S, H, D)) for kk in keys[:4])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q, k, v, cot = (t.astype(jnp.bfloat16) for t in (q, k, v, cot))
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[4], (1, S, H, D)) - 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, S, H)))

    def grad_of(fn):
        def loss(q, k, v, g, beta):
            out = fn(q, k, v, g, beta)
            return jnp.sum(out.astype(jnp.float32) * cot.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))

    def recurrence(q, k, v, g, beta):
        return jax.vmap(reference_ling.delta_rule)(q, k, v, jnp.exp(g), beta)

    lowered = grad_of(gated_delta_rule).lower(q, k, v, g, beta)
    text = lowered.as_text()
    if "tpu_custom_call" in text or "triangular_solve" in text:
        raise AssertionError(f"delta rule {name}: a Mosaic or triangular-solve call in the op")
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    (_, out), grads = jax.block_until_ready(compiled(q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = jax.block_until_ready(grad_of(recurrence)(
            *(t.astype(jnp.float32) for t in (q, k, v)), g, beta
        ))
    errs = {}
    for label, got, ref in zip(
        ("out", "dq", "dk", "dv", "dg", "dbeta"), (out,) + grads, (want,) + want_grads
    ):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        if not np.all(np.isfinite(got)):
            raise AssertionError(f"delta rule {name}: non-finite {label}")
        err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        errs[label] = round(err, 5)
        if err > FLASH_TOL:
            raise AssertionError(
                f"delta rule {name}: {label} differs from the recurrence by "
                f"{err:.4f} of max|ref| (tolerance {FLASH_TOL})"
            )
    _say("kernels", (
        f"delta rule {name} B1 S{S} H{H} D{D}: compiled in {compile_s:.1f}s, "
        f"max err / max|ref| {errs} <= {FLASH_TOL}"
    ))


def _check_ssd(
    name: str, S: int = 4096, H: int = 64, P: int = 64, N: int = 128, chunk: int = 256,
    groups: int = 1,
) -> None:
    """``ops.ssd.ssd_scan`` at a Mamba-2 layer's shape in ``granite4h-ft1`` -
    bf16 x, B and C, float32 steps as the mixer draws them (a softplus of a
    unit normal over a bias of log U(1e-3, 0.1)) under rates of -1 to -16 -
    compiled under the chip's DEFAULT matmul precision, the output and every
    cotangent of the op's OWN backward (``ssd_scan`` is a ``custom_vjp``:
    ``ops/ssd.py``, ``_backward``) against autodiff of the recurrence a
    position at a time in float32 at ``highest``
    (``benchmark/reference_granite.py``; with ``groups`` of B and C,
    ``nemotron3n-ft1``'s shape, the recurrence with its groups of
    ``benchmark/reference_nemotron.py``). Plain XLA: no Mosaic call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_granite, reference_nemotron
    from torchft_tpu.ops.ssd import ssd_scan

    by_position = reference_granite.recurrence if groups == 1 else reference_nemotron.recurrence
    maps = (1, S, N) if groups == 1 else (1, S, groups, N)
    keys = jax.random.split(jax.random.PRNGKey(S + P), 8)
    x, cot = (jax.random.normal(kk, (1, S, H, P)).astype(jnp.bfloat16) for kk in keys[:2])
    B, C = (jax.random.normal(kk, maps).astype(jnp.bfloat16) for kk in keys[2:4])
    drawn = jnp.exp(jax.random.uniform(keys[4], (H,), jnp.float32, np.log(1e-3), np.log(0.1)))
    dt = jax.nn.softplus(
        jax.random.normal(keys[5], (1, S, H)) + drawn + jnp.log(-jnp.expm1(-drawn))
    )
    A = -jax.random.uniform(keys[6], (H,), jnp.float32, 1.0, 16.0)
    D = jnp.ones((H,), jnp.float32)

    def grad_of(fn):
        def loss(x, dt, A, B, C, D):
            out = fn(x, dt, A, B, C, D)
            return jnp.sum(out.astype(jnp.float32) * cot.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)), has_aux=True))

    def recurrence(x, dt, A, B, C, D):
        return jax.vmap(
            lambda x, dt, B, C: by_position(x, dt, A, B, C, D)
        )(x, dt, B, C)

    lowered = grad_of(lambda *a: ssd_scan(*a, chunk=chunk)).lower(x, dt, A, B, C, D)
    if "tpu_custom_call" in lowered.as_text():
        raise AssertionError(f"ssd {name}: a Mosaic call in the op")
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    (_, out), grads = jax.block_until_ready(compiled(x, dt, A, B, C, D))
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = jax.block_until_ready(grad_of(recurrence)(
            x.astype(f32), dt, A, B.astype(f32), C.astype(f32), D
        ))
    errs = {}
    for label, got, ref in zip(
        ("out", "dx", "ddt", "dA", "dB", "dC", "dD"), (out,) + grads, (want,) + want_grads
    ):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        if not np.all(np.isfinite(got)):
            raise AssertionError(f"ssd {name}: non-finite {label}")
        err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        errs[label] = round(err, 5)
        if err > FLASH_TOL:
            raise AssertionError(
                f"ssd {name}: {label} differs from the recurrence by "
                f"{err:.4f} of max|ref| (tolerance {FLASH_TOL})"
            )
    _say("kernels", (
        f"ssd {name} B1 S{S} H{H} P{P} N{N}{f' G{groups}' if groups > 1 else ''} "
        f"chunk {chunk}, the op's own backward: compiled in {compile_s:.1f}s, "
        f"max err / max|ref| {errs} <= {FLASH_TOL}"
    ))


def _check_relu2_share(name: str, cfg: Any = None, N: int = 8192) -> None:
    """``olmoe._held_share`` with UNGATED experts at ``nemotron3n-ft1``'s
    shape - 8 of 128 experts of width 1,856 held at d 2,688, 6 a token,
    8,192 tokens in bf16 - compiled under the chip's DEFAULT matmul
    precision, against every held expert applied to every token in float32
    at ``highest`` (``W_down relu(W_up u) ** 2`` weighted by the token's
    weight on the expert, 0 where it chose another): the output and the
    cotangents of the tokens, both maps and the weights. The routing is a
    top-K of random scores with the FIRST held expert's raised, so that it
    is heavy (applied to all tokens in place) and the others light (tiles).
    Plain XLA: no Mosaic call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models import olmoe

    if cfg is None:
        from benchmark import common

        sizes = common.load_json("configs", "nemotron3-nano-l9-ep16.json")
        cfg = common.load_by_name("families", sizes["family"]).build(sizes)
    assert not cfg.gated
    (first, held), E, K = cfg.held, cfg.n_experts, cfg.experts_per_token
    d, f = cfg.d_model, cfg.expert_width
    keys = jax.random.split(jax.random.PRNGKey(N + f), 6)
    tokens, cot = (jax.random.normal(kk, (N, d)).astype(cfg.dtype) for kk in keys[:2])
    p = {
        "w_up": (jax.random.normal(keys[2], (held, d, f)) * d ** -0.5).astype(cfg.dtype),
        "w_down": (jax.random.normal(keys[3], (held, f, d)) * f ** -0.5).astype(cfg.dtype),
    }
    score = jax.random.uniform(keys[4], (N, E)).at[:, first].add(
        jnp.where(jnp.arange(N) % 8 == 0, 1.0, 0.0)  # an eighth of the tokens, and its own share
    )
    weights, chosen = jax.lax.top_k(score, K)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    def grad_of(fn):
        def loss(p, tokens, weights):
            out = fn(p, tokens, weights)
            return jnp.sum(out * cot.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))

    def share(p, tokens, weights):
        return olmoe._held_share(cfg, p, tokens, weights, chosen)[0]

    def every_expert(p, tokens, weights):
        out = jnp.zeros(tokens.shape, jnp.float32)
        for e in range(held):
            gate = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=1)
            hidden = jnp.square(jax.nn.relu(tokens @ p["w_up"][e]))
            out = out + gate[:, None] * (hidden @ p["w_down"][e])
        return out

    lowered = grad_of(share).lower(p, tokens, weights)
    if "tpu_custom_call" in lowered.as_text():
        raise AssertionError(f"share {name}: a Mosaic call in the share")
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    (_, out), grads = jax.block_until_ready(compiled(p, tokens, weights))
    wide = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), (p, tokens))
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = jax.block_until_ready(grad_of(every_expert)(*wide, weights))

    def labelled(out, grads):
        return {"out": out, "dw_up": grads[0]["w_up"], "dw_down": grads[0]["w_down"],
                "dtokens": grads[1], "dweights": grads[2]}

    got, ref, errs = labelled(out, grads), labelled(want, want_grads), {}
    for label in got:
        a, b = np.asarray(got[label], np.float32), np.asarray(ref[label], np.float32)
        if not np.all(np.isfinite(a)):
            raise AssertionError(f"share {name}: non-finite {label}")
        err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        errs[label] = round(err, 5)
        if err > FLASH_TOL:
            raise AssertionError(
                f"share {name}: {label} differs from every held expert on every token by "
                f"{err:.4f} of max|ref| (tolerance {FLASH_TOL})"
            )
    rows, tile, light_up_to = olmoe._share_buffer(cfg, N)
    _say("kernels", (
        f"share {name} N{N} D{d} F{f} held {held} of {E} top-{K}, tiles of {tile} rows, "
        f"heavy over {light_up_to}: compiled in {compile_s:.1f}s, max err / max|ref| {errs} "
        f"<= {FLASH_TOL}"
    ))


def _check_wire_kernels(name: str, shape: Sequence[int], seed: int) -> None:
    """quantize_q8_ef / dequantize_q8 / cast_bf16 on one payload, compiled,
    against the numpy oracle of the CPU tests
    (torchft_tpu.quantize.np_quantize_codes)."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    from torchft_tpu.ops import cast_bf16, dequantize_q8, quantize_q8_ef
    from torchft_tpu.quantize import np_quantize_codes

    rng = np.random.default_rng(seed)
    # gradient-like magnitudes, with a non-zero carry so the EF add counts
    x_np = (rng.standard_normal(shape) * 1e-2).astype(np.float32)
    res_np = (rng.standard_normal(shape) * 1e-5).astype(np.float32)
    x, res = jnp.asarray(x_np), jnp.asarray(res_np)

    quant = jax.jit(quantize_q8_ef)
    _assert_mosaic(quant.lower(x, res), 1, f"quantize_q8_ef {name}")
    q, s, r = (np.asarray(a) for a in quant(x, res))

    d, q_ref, scale_ref = np_quantize_codes(x_np, res_np)
    code_diff = np.abs(q.astype(np.int32) - q_ref.astype(np.int32))
    if int(code_diff.max()) > 1:
        raise AssertionError(
            f"quantize_q8_ef {name}: a code differs from the oracle by "
            f"{int(code_diff.max())} (at most 1 allowed)"
        )
    # The EF invariant, on the device's OWN codes and scale: the carry is
    # exactly what the wire dropped, res' = d - round32(q * scale).
    dq_dev = (q.astype(np.float32) * s).astype(np.float32)
    if (d - dq_dev).astype(np.float32).tobytes() != r.tobytes():
        raise AssertionError(
            f"quantize_q8_ef {name}: carry != d - q*scale on the device's "
            "own codes — error feedback would drift"
        )
    # TPU f32 division need not round like the CPU's: what was found
    found = {
        "scale_bit_identical": s.tobytes() == scale_ref.tobytes(),
        "codes_differing": int(np.count_nonzero(code_diff)),
        "codes_total": int(code_diff.size),
    }

    deq = jax.jit(dequantize_q8)
    qd, sd = jnp.asarray(q), jnp.asarray(s)
    _assert_mosaic(deq.lower(qd, sd), 1, f"dequantize_q8 {name}")
    if np.asarray(deq(qd, sd)).tobytes() != dq_dev.tobytes():
        raise AssertionError(f"dequantize_q8 {name}: != q * scale")

    cast = jax.jit(cast_bf16)
    _assert_mosaic(cast.lower(x), 1, f"cast_bf16 {name}")
    want = x_np.astype(ml_dtypes.bfloat16)
    if np.asarray(cast(x)).tobytes() != want.tobytes():
        raise AssertionError(
            f"cast_bf16 {name}: != numpy round-to-nearest-even"
        )
    _say("kernels", f"wire kernels {name} {tuple(shape)}: ok, q8 vs oracle {found}")


def _observe_link() -> Dict[str, float]:
    """d2h / h2d of one 256 MiB buffer — an observation of this run, not
    a metric (second of two passes; a fresh device array each pass, since
    jax caches an array's host copy)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    out = {}
    for i in range(2):
        x = jax.block_until_ready(jnp.full((64, 1024, 1024), i, jnp.float32))
        t0 = time.perf_counter()
        host = np.asarray(x)
        out["d2h_GBps"] = round(host.nbytes / (time.perf_counter() - t0) / 1e9, 2)
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(host))
        out["h2d_GBps"] = round(host.nbytes / (time.perf_counter() - t0) / 1e9, 2)
    return out


# (name, B, S, H, D, window[, block_mask[, d_v]]) of every flash shape the kernels
# phase runs:
# the big shape (the model slices the last token off: S 2047) and the
# head_dim 128 shape of the d_model 2048 point; the benchmark's shapes -
# GPT-2 (1024 positions, 12 heads of 64: the whole sequence resident, the
# static causal schedule) and OLMoE and Ouro (4096 positions, 16 heads of
# 128 at their cells' batches, 64 and 32 head-rows: four resident blocks
# of 1024 rows, each call naming its VMEM limit) and Mellum2 (8192
# positions of head size 128, a window of 1024 and none: the causal
# schedule cut to the window's band, (1024, 1024) blocks and the two
# staircases, and the same schedule whole on (1024, 512), both with
# 2 MiB key/value rows resident, the backward's rows past the default VMEM
# limit) and SDAR (a
# clean and a noised copy of 4096 positions in blocks of 4, head size 128,
# under the block-diffusion mask: the static schedule with both copies' row
# groups of 1024 a grid step); then, at a
# reduced batch, a window of one 512 sub-tile over 2047 positions at head
# size 64 (the band again, ending in a padded block), a window that no
# tile divides (the general kernels: one masked tile a loop trip), and a
# ragged short sequence on the 128-wide tiles.
FLASH_CASES = (
    ("big", BATCH, SEQ - 1, 16, 64, None),
    ("gpt2", 2, 1024, 12, 64, None),
    ("head_dim128", 8, SEQ - 1, 16, 128, None),
    ("olmoe", 4, 4096, 16, 128, None),
    ("ouro", 2, 4096, 16, 128, None),
    ("mellum_sliding", 1, 8192, 8, 128, 1024),
    ("mellum_full", 1, 8192, 8, 128, None),
    ("sdar_block", 1, 8192, 8, 128, None, (4, 4096)),
    # ling3-ft1's latent-attention layer: q.k 192 and v 128 both padded to
    # 256 lanes, which is what the kernels are compiled at, on (512, 512)
    ("ling_mla", 1, 8192, 8, 256, None),
    # dsv2lite-ft1's: the same padded width, all 16 heads of a layer
    ("dsv2_mla", 1, 8192, 16, 256, None),
    # both as the cells run them from PR 54 on: q.k at 192 and v, the output
    # and dv at 128, nothing padded (the 256-lane cases above stay: a caller
    # with one width of 256 takes the same kernels)
    ("ling_mla_192_128", 1, 8192, 8, 192, None, None, 128),
    ("dsv2_mla_192_128", 1, 8192, 16, 192, None, None, 128),
    # a block mask that does not tile (B 6 straddles every tile's edge):
    # the general kernels' sweep, which no cell runs
    ("block_general", 2, 1920, 4, 128, None, (6, 960)),
    ("windowed", 2, SEQ - 1, 4, 64, 512),
    ("general", 2, SEQ - 1, 4, 64, 500),
    ("ragged", 2, 99, 4, 64, None),
)


def child_kernels() -> None:
    devices = _child_setup()
    _say("kernels", f"on {devices[0].device_kind}")
    for case in FLASH_CASES:
        _check_flash(*case)
        # full causal, one resident block: the fused projection's entry too
        if case[5:] == (None,) and case[2] <= 2048:
            _check_flash(case[0] + "_qkv", *case[1:], fused=True)
    # q and k from their projections to the kernels' rows, at the routed
    # cells' shape: Mellum2's full layer and SDAR's two copies
    _check_heads_to_rows("mellum_full", "counted")
    _check_heads_to_rows("sdar_block", "stated")
    # ling3-ft1's other mixer: the gated delta rule in chunks, plain XLA
    _check_delta_rule("ling_kda")
    # granite4h-ft1's mixer: the state-space scan in chunks, plain XLA
    _check_ssd("granite_ssd")
    # nemotron3n-ft1's: the scan with eight groups of B and C in chunks of
    # 128, and the held share of ungated experts, both plain XLA
    _check_ssd("nemotron_ssd", S=8192, chunk=128, groups=8)
    _check_relu2_share("relu2_share")
    # the big model's largest leaf (128 grid blocks) and an odd length
    # that ends mid-block
    _check_wire_kernels("big_leaf", (1024, 4096), seed=1)
    _check_wire_kernels("odd", (70001,), seed=2)
    _say("kernels", f"link observation (not a metric): {_observe_link()}")
    _say("kernels", f"compile cache: {_cache_counts()}")


# ---------------------------------------------------------------------------
# phase: fleet — one replica group
# ---------------------------------------------------------------------------


def run_group(
    cfg: Any,
    batch_shape: Tuple[int, int],
    out_dir: str,
    group: int,
    num_groups: int,
    lighthouse_addr: str,
    sync_steps: int = SYNC_STEPS,
    plan_steps: int = PLAN_STEPS,
    heal: bool = False,
) -> List[Dict[str, Any]]:
    """One replica group's whole smoke: the README's minimal loop
    (``sync_steps`` committed steps), ``plan_steps`` PipelinedDDP q8 plan
    steps, and — with ``heal`` — the kill/restart/heal round in which the
    last group is the victim. Every check raises; returns the records it
    also appended to ``<out_dir>/group_<g>.jsonl``. Runs at any config:
    the tests call it at ``tiny_config()`` on the CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu import (
        FTTrainState,
        HostCollectives,
        Manager,
        OptimizerWrapper,
        PipelinedDDP,
    )
    from torchft_tpu.models import init_params, loss_fn
    from torchft_tpu.serving import tree_digest

    kill_marker = os.path.join(out_dir, f"killed_{group}")
    second_life = os.path.exists(kill_marker)
    victim = heal and group == num_groups - 1
    records: List[Dict[str, Any]] = []

    def record(event: str, **fields: Any) -> None:
        """One JSONL line of this group's life; the parent joins the files."""
        rec = {"group": group, "life": int(second_life), "event": event, **fields}
        with open(os.path.join(out_dir, f"group_{group}.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        records.append(rec)

    on_tpu = jax.devices()[0].platform == "tpu"

    rng = np.random.default_rng(1000 + group)
    batch = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=batch_shape, dtype=np.int32)
    )
    # same seed everywhere: the groups start as replicas
    state = FTTrainState(
        init_params(cfg, jax.random.PRNGKey(0)), optax.adamw(1e-3)
    )

    def loss_and_grads(params: Any, tokens: Any) -> Tuple[Any, Any]:
        # bf16 compute copy over the f32 master (make_train_step's
        # bf16_params discipline): the gradient tree that crosses groups
        # is bf16, the optimizer updates the f32 master
        compute = jax.tree_util.tree_map(
            lambda l: l.astype(jnp.bfloat16) if l.dtype == jnp.float32 else l,
            params,
        )
        return jax.value_and_grad(lambda p: loss_fn(cfg, p, tokens))(compute)

    grad_fn = jax.jit(loss_and_grads)
    if on_tpu:
        # the step the fleet runs must itself carry the compiled kernels:
        # flash forward + fused backward per layer
        _assert_mosaic(
            grad_fn.lower(state.params, batch), 2 * cfg.n_layers, "train step"
        )
    t0 = time.perf_counter()
    loss0, grads0 = jax.block_until_ready(grad_fn(state.params, batch))
    state.warm(grads0)  # the optimizer-update executable, compiled ahead
    compile_s = time.perf_counter() - t0
    loss0 = float(loss0)
    del grads0

    collectives = HostCollectives(timeout=timedelta(seconds=120))
    manager = Manager(
        collectives=collectives,
        load_state_dict=state.load_state_dict,
        state_dict=state.state_dict,
        min_replica_size=1,
        timeout=timedelta(seconds=120),
        quorum_timeout=timedelta(seconds=180),
        lighthouse_addr=lighthouse_addr,
        replica_id=f"smoke_{group}",
    )
    optimizer = OptimizerWrapper(manager, state)

    def sync_step() -> int:
        """One step of the README's minimal loop; returns the participant
        count. A step that fails to commit, or latches an error, raises."""
        optimizer.zero_grad()
        loss, grads = grad_fn(state.params, batch)
        avg = manager.allreduce(grads).wait()
        committed = optimizer.step(avg)
        if manager.errored() is not None:
            raise RuntimeError(
                f"step {manager.current_step()} latched an error"
            ) from manager.errored()
        if not committed:
            raise RuntimeError(f"step {manager.current_step()} did not commit")
        if not np.isfinite(float(loss)):
            raise RuntimeError(f"non-finite loss at step {manager.current_step()}")
        return manager.num_participants()

    def digest() -> str:
        return tree_digest(state.params)

    if not second_life:
        # Start line: every group has compiled and is heartbeating before
        # any asks for a quorum, so the lighthouse holds the door and the
        # first quorum has all of them.
        open(os.path.join(out_dir, f"ready_{group}"), "w").close()
        deadline = time.monotonic() + 900
        while not all(
            os.path.exists(os.path.join(out_dir, f"ready_{g}"))
            for g in range(num_groups)
        ):
            if time.monotonic() > deadline:
                raise TimeoutError("peers never reached the start line")
            time.sleep(0.05)

        # -- the README's minimal loop -------------------------------------
        for _ in range(sync_steps):
            participants = sync_step()
            if participants != num_groups:
                raise RuntimeError(
                    f"quorum of {participants}, want {num_groups}"
                )
        loss_after = float(grad_fn(state.params, batch)[0])
        if not (np.isfinite(loss_after) and loss_after < loss0):
            raise RuntimeError(
                f"loss on the fixed batch did not fall over {sync_steps} "
                f"steps: {loss0} -> {loss_after}"
            )
        record(
            "sync", step=manager.current_step(), compile_s=round(compile_s, 2),
            loss_first=round(loss0, 4), loss_after=round(loss_after, 4),
            participants=num_groups, digest=digest(),
        )

        # -- PipelinedDDP over the q8 comm plan ------------------------------
        collectives.pop_op_stats()  # drop the sync phase's entries
        ddp = PipelinedDDP(manager, state, grad_fn, compress="q8", transport="plan")
        for i in range(plan_steps):
            ddp.step(batch)
            if i > 0 and ddp.last_commit is not True:
                raise RuntimeError(f"q8 plan step {i - 1} did not commit")
        if not ddp.flush():
            raise RuntimeError("the last q8 plan step did not commit")
        plan_ops = [
            s for s in collectives.pop_op_stats() if s["op"] == "plan_allreduce"
        ]
        # device pack is the TPU default (HostCollectives auto); the CPU
        # backend host-packs. Either way it must be what the backend says.
        if len(plan_ops) != plan_steps or any(
            s["device_pack"] is not on_tpu or s["wire"] != "q8ef" for s in plan_ops
        ):
            raise RuntimeError(
                f"q8 plan steps: want {plan_steps} ops with device_pack="
                f"{on_tpu}, got {[(s['wire'], s['device_pack']) for s in plan_ops]}"
            )
        record(
            "plan_q8", step=manager.current_step(), device_pack=on_tpu,
            d2h_bytes=plan_ops[-1]["d2h_bytes"], payload_bytes=plan_ops[-1]["bytes"],
            digest=digest(),
        )

    if heal:
        # -- one heal ---------------------------------------------------------
        # The victim commits one more step and SIGKILLs itself; the
        # survivors keep committing at N-1; the launcher restarts the
        # victim on its chip; it heals from a live peer and rejoins.
        # Everyone stops one commit after the first full-strength commit —
        # the same step in every group.
        first_commit_t = None
        seen_short = second_life  # the restarted victim joins a short cohort
        while True:
            participants = sync_step()
            if first_commit_t is None:
                first_commit_t = time.time()
            if victim and not second_life:
                with open(kill_marker, "w") as f:
                    f.write(repr(time.time()))
                os.kill(os.getpid(), signal.SIGKILL)
            if participants < num_groups:
                seen_short = True
            elif seen_short:
                break
        if sync_step() != num_groups:
            raise RuntimeError("the cohort shrank again after the heal")
        fields: Dict[str, Any] = {}
        if second_life:
            stats = manager.checkpoint_transport().last_fetch_stats
            if not stats or stats.get("path") != "stream":
                raise RuntimeError(
                    f"heal did not come through the streamed path: {stats}"
                )
            with open(kill_marker) as f:
                killed_t = float(f.read())
            fields = {
                "heal_path": stats["path"],
                "kill_to_first_commit_s": round(first_commit_t - killed_t, 2),
                "restart_compile_s": round(compile_s, 2),
            }
        record("healed", step=manager.current_step(), digest=digest(), **fields)

    manager.shutdown()
    collectives.shutdown()
    return records


def child_worker() -> None:
    devices = _child_setup(expect_chips=1)
    from torchft_tpu.models import big_config

    group = int(os.environ["REPLICA_GROUP_ID"])
    num_groups = int(os.environ["NUM_REPLICA_GROUPS"])
    _say("fleet", (
        f"group {group}: pid {os.getpid()} owns {devices[0]} "
        f"(TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')})"
    ))
    records = run_group(
        dataclasses.replace(big_config(), use_flash=True),
        (BATCH, SEQ),
        OUT_DIR,
        group,
        num_groups,
        os.environ["TORCHFT_LIGHTHOUSE"],
        heal=num_groups > 1,
    )
    for r in records:
        _say("fleet", json.dumps(r))
    _say("fleet", f"group {group}: compile cache {_cache_counts()}")


# ---------------------------------------------------------------------------
# phase: mesh
# ---------------------------------------------------------------------------


def run_mesh(cfg: Any, batch_shape: Tuple[int, int]) -> str:
    """Two sharded steps over a data:2 x model:2 mesh of this process's
    four devices (``build_grad_step`` / ``build_apply_step``, flash under
    ``shard_map``); returns a one-line report."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.models import init_params, loss_fn, param_sharding_rules
    from torchft_tpu.parallel import (
        build_apply_step,
        build_grad_step,
        make_mesh,
        shard_pytree,
    )

    mesh = make_mesh({"data": 2, "model": 2}, devices=jax.devices()[:4])
    # flash under shard_map: batch over "data", heads over "model"
    cfg = dataclasses.replace(
        cfg, use_flash=True, cp_mesh=mesh, cp_head_axis="model"
    )
    rules = param_sharding_rules(cfg)
    params = shard_pytree(init_params(cfg, jax.random.PRNGKey(0)), rules, mesh)
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)
    grad_step = build_grad_step(lambda p, b: loss_fn(cfg, p, b), mesh, rules)
    apply_step = build_apply_step(tx)
    batch = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=batch_shape, dtype=np.int32
    ))
    if jax.default_backend() == "tpu":
        _assert_mosaic(
            grad_step.lower(params, batch), 2 * cfg.n_layers, "mesh step"
        )

    losses = []
    for _ in range(2):
        loss, grads = grad_step(params, batch)
        params, opt_state = apply_step(params, opt_state, grads)
        losses.append(float(loss))
    if not (np.all(np.isfinite(losses)) and losses[1] < losses[0]):
        raise RuntimeError(f"mesh: loss did not fall over two steps: {losses}")
    wqkv = params["blocks"][0]["attn"]["wqkv"]
    shard_devices = {s.device for s in wqkv.addressable_shards}
    if len(shard_devices) != 4:
        raise RuntimeError(
            f"mesh: parameter shards sit on {len(shard_devices)} device(s), want 4"
        )
    return (
        f"data:2 x model:2: losses {losses}, wqkv {tuple(wqkv.shape)} as "
        f"shards {[tuple(s.data.shape) for s in wqkv.addressable_shards]} "
        f"on devices {sorted(d.id for d in shard_devices)}"
    )


def child_mesh() -> None:
    _child_setup()
    from torchft_tpu.models import big_config

    _say("mesh", run_mesh(big_config(), (BATCH, SEQ)))
    _say("mesh", f"compile cache: {_cache_counts()}")


# ---------------------------------------------------------------------------
# the parent: never touches a JAX backend
# ---------------------------------------------------------------------------


def _run(phase: str, cmd: Sequence[str], timeout: float, capture: bool = False) -> str:
    """Runs one phase's command in its own session, on the TPU; a non-zero
    exit or a timeout raises, and nothing the command started outlives
    this call."""
    env = dict(os.environ, JAX_PLATFORMS="tpu", PYTHONUNBUFFERED="1")
    t0 = time.monotonic()
    proc = subprocess.Popen(
        list(cmd), env=env, cwd=REPO, start_new_session=True,
        stdout=subprocess.PIPE if capture else None, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout)
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"phase {phase} failed (exit {proc.returncode})")
    _say(phase, f"passed in {time.monotonic() - t0:.1f}s")
    return out or ""


def _child_cmd(phase: str) -> List[str]:
    return [sys.executable, os.path.abspath(__file__), "--phase", phase]


def phase_probe() -> Dict[str, Any]:
    try:
        out = _run("probe", _child_cmd("probe"), 300, capture=True)
    except RuntimeError:
        print(
            "chip_smoke: no TPU chip found: JAX could not initialise the "
            "tpu backend (JAX_PLATFORMS=tpu). This script only passes on "
            "the chip.",
            file=sys.stderr,
        )
        sys.exit(1)
    device = json.loads(out.strip().splitlines()[-1])
    _say("probe", json.dumps(device))
    return device


def phase_build() -> None:
    native = os.path.join(REPO, "native")
    build = os.path.join("build", "chip_smoke")  # under git-ignored native/build/
    shutil.rmtree(os.path.join(native, build), ignore_errors=True)
    lib = os.path.join(REPO, "torchft_tpu", "_libtorchft.so")
    if os.path.exists(lib):
        os.unlink(lib)
    proto = subprocess.run(
        ["make", "-C", native, "--no-print-directory", "--eval",
         "chip-smoke-proto: ; @echo $(if $(HAVE_PROTOC),HAVE_PROTOC,pb_fallback)",
         "chip-smoke-proto"],
        check=True, capture_output=True, text=True,
    ).stdout.strip()
    _say("build", f"protobuf path: {proto}")
    _run("build", [
        "make", "-C", native, f"-j{os.cpu_count() or 4}", f"BUILD={build}",
    ], 900)


def phase_fleet(num_groups: int) -> None:
    from torchft_tpu import _native  # no JAX backend: asserted at exit

    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    if num_groups == 1:
        _say("fleet", "one chip: N=1, heal skipped (no live peer to heal from)")
    # join_timeout: the first quorum waits for every heartbeating group;
    # heartbeat_timeout: how long a SIGKILLed group keeps the door held.
    lighthouse = _native.Lighthouse(
        bind="[::]:0", min_replicas=1, join_timeout_ms=60000,
        heartbeat_timeout_ms=3000,
    )
    try:
        _run("fleet", [
            sys.executable, "-m", "torchft_tpu.launcher",
            "--num-replica-groups", str(num_groups),
            "--chips-per-group", "1",
            "--lighthouse", lighthouse.address(),
            # the victim restarts once; any other exit fails the job
            "--max-restarts", "1" if num_groups > 1 else "0",
            "--", *_child_cmd("worker"),
        ], 900)
    finally:
        lighthouse.shutdown()

    records: List[Dict[str, Any]] = []
    for g in range(num_groups):
        with open(os.path.join(OUT_DIR, f"group_{g}.jsonl")) as f:
            records += [json.loads(line) for line in f]
    events = ["sync", "plan_q8"] + (["healed"] if num_groups > 1 else [])
    for event in events:
        recs = [r for r in records if r["event"] == event]
        if sorted(r["group"] for r in recs) != list(range(num_groups)):
            raise RuntimeError(f"fleet: missing {event!r} records: {recs}")
        if len({(r["step"], r["digest"]) for r in recs}) != 1:
            raise RuntimeError(
                f"fleet: parameter digests differ after {event!r}: "
                f"{[(r['group'], r['step'], r['digest']) for r in recs]}"
            )
        _say("fleet", (
            f"{event}: {num_groups} group(s) at step {recs[0]['step']}, "
            f"digest {recs[0]['digest']}"
        ))
    cold = [r["compile_s"] for r in records if r["event"] == "sync"]
    _say("fleet", f"cold compile seconds per group (not a metric): {cold}")
    if num_groups > 1:
        (healed,) = [r for r in records if r["event"] == "healed" and r["life"] == 1]
        _say("fleet", (
            f"heal (not a metric): group {healed['group']} kill -> first "
            f"commit {healed['kill_to_first_commit_s']}s via "
            f"{healed['heal_path']!r}, restarted compile "
            f"{healed['restart_compile_s']}s vs cold {cold[healed['group']]}s"
        ))


def main() -> int:
    if not os.path.isdir(os.path.join(REPO, "torchft_tpu")) or not os.path.isdir(
        os.path.join(REPO, "native")
    ):
        print(
            "chip_smoke: must run from a checkout of the repository "
            f"(no torchft_tpu/ and native/ beside {__file__})",
            file=sys.stderr,
        )
        return 2
    # a terminated parent still unwinds, so every child's session is reaped
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    t0 = time.monotonic()
    device = phase_probe()
    phase_build()
    _run("kernels", _child_cmd("kernels"), 900)
    phase_fleet(min(4, device["count"]))
    if device["count"] >= 4:
        _run("mesh", _child_cmd("mesh"), 900)
    else:
        _say("mesh", f"skipped: needs 4 chips, found {device['count']}")

    # This process started every child and must not have held a chip.
    xla_bridge = sys.modules.get("jax._src.xla_bridge")
    if xla_bridge is not None and xla_bridge._backends:
        raise RuntimeError(
            f"the parent initialised a JAX backend: {list(xla_bridge._backends)}"
        )
    _say("done", f"every phase passed in {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


_CHILDREN = {
    "probe": child_probe,
    "kernels": child_kernels,
    "worker": child_worker,
    "mesh": child_mesh,
}

if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--phase":
        _CHILDREN[sys.argv[2]]()
    elif len(sys.argv) == 1:
        sys.exit(main())
    else:
        sys.exit(f"usage: {sys.argv[0]}  (children: --phase {'|'.join(_CHILDREN)})")
