#!/usr/bin/env python3
"""What ``granite4h-ft1``'s ``correct`` tells from a sound step, read on the chip.

    python3 benchmark/controls_granite.py --seeds <n>,<n>,... [--controls a,b]
        [--attention-spread 4] [--rehearse]

``controls_ling.py``'s run over this cell and this cell's wrong terms: for
every seed the sound program and each CONTROL - one wrong term planted in
the PROGRAM - run three steps on the generator's own path and each is held
to ``reference_granite.train`` of the same seed by the harness's own
comparison, ``common.check_first_steps``, at the family's own limits; one
JSON line a seed and control, ``ok`` in it. A limit HOLDS a control when
``ok`` is false on every seed, and a sound program has to read true on
every seed. The loop, its options and ``float8`` are ``controls_ling``'s,
called and not copied; this file states the cell and its controls. It is no
part of ``benchmark/run.py``'s path and no cell's file.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import Any, Callable, Dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import controls_ling as base  # noqa: E402

CELL = "granite4h-ft1"


def controls(cfg: Any) -> Dict[str, Callable[[Any], Any]]:
    """By name, ``family -> (params, tokens) -> loss`` with one term wrong:
    the eight ISSUE 58 lists and the precision below the configuration's.
    Three are a configuration that says something else (data of
    ``OlmoeConfig`` or ``AttentionKind``: the program has no switch that
    names them); two hand the program a leaf the reference reads as seeded;
    one replaces the mixer's gated norm and one the precision of the scan's
    running sums. ``no optimizer update`` is the sound loss with its gradient
    cut: its gradient norm is the plant's own 0, so read it by its
    ``loss_rel_err`` against ``LOSS_RTOL``, which is the one limit that sees
    a state left unchanged."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import olmoe
    from torchft_tpu.ops import ssd

    def with_cfg(changed: Any) -> Callable[[Any], Any]:
        return lambda family: lambda params, tokens: family.loss(changed, params, tokens)

    def with_attention(**changed: Any) -> Callable[[Any], Any]:
        kinds = tuple(
            dataclasses.replace(k, **changed) if k.mixer is None else k for k in cfg.kinds
        )
        return with_cfg(dataclasses.replace(cfg, layer_kinds=kinds))

    def with_leaf(name: str) -> Callable[[Any], Any]:
        def zeroed(params: Any) -> Any:
            return dict(params, blocks=[
                dict(b, attn=dict(b["attn"], **{name: jnp.zeros_like(b["attn"][name])}))
                if name in b["attn"] else b for b in params["blocks"]
            ])
        return lambda family: lambda params, tokens: family.loss(cfg, zeroed(params), tokens)

    def patched(module: Any, name: str, wrong: Any) -> Callable[[Any], Any]:
        def of(family: Any) -> Any:
            def loss(params: Any, tokens: Any) -> Any:
                right = getattr(module, name)
                setattr(module, name, wrong)
                try:
                    return family.loss(cfg, params, tokens)
                finally:
                    setattr(module, name, right)
            return loss
        return of

    def norm_then_gate(c: Any, p: Any, y: Any, z: Any) -> Any:
        """``RMSNorm(y) SiLU(z)``, Mamba-2's ``norm_before_gate``, where
        ``olmoe._gated_norm`` is ``RMSNorm(y SiLU(z))``."""
        f32 = jnp.float32
        y = olmoe._rmsnorm(y.astype(f32), p["norm"], c.rms_norm_eps)
        return (y * jax.nn.silu(z.astype(f32))).astype(c.dtype)

    return {
        "sound": with_cfg(cfg),
        "float8 weights": lambda family: lambda params, tokens: family.loss(
            cfg, base.float8(params), tokens
        ),
        "residual_multiplier left at 1": with_cfg(
            dataclasses.replace(cfg, residual_multiplier=1.0)
        ),
        "head_dim ** -0.5 for attention_multiplier": with_attention(softmax_scale=None),
        "a rotary embedding applied": with_attention(rotary=True),
        "the norm before the gate": patched(olmoe, "_gated_norm", norm_then_gate),
        "D left out": with_leaf("d"),
        "dt_bias left out": with_leaf("dt_bias"),
        # the decays' running sums as a product at the DEFAULT precision: on
        # the chip one bf16 pass over the steps ``a`` and the mask
        "bf16 running sums in the scan": patched(ssd, "_EXACT", jax.lax.Precision.DEFAULT),
        "no optimizer update": lambda family: lambda params, tokens: jax.lax.stop_gradient(
            family.loss(cfg, params, tokens)
        ),
    }


if __name__ == "__main__":
    if "--attention-spread" in sys.argv:
        # the family's draw of wq and wk at another width, program and
        # reference alike: how the family's ``ATTENTION_SPREAD`` was chosen
        at = sys.argv.index("--attention-spread")
        spread = float(sys.argv[at + 1])
        del sys.argv[at:at + 2]
        from benchmark import common

        load = common.load_family

        def widened(name: str) -> Any:
            family = load(name)
            family.ATTENTION_SPREAD = spread
            return family

        common.load_family = widened
    base.CELL, base.controls, base.__doc__ = CELL, controls, __doc__
    base.main()
