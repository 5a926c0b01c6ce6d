#!/usr/bin/env python3
"""What ``ling3-ft1``'s ``correct`` tells from a sound step, read on the chip.

    python3 benchmark/controls_ling.py --seeds <n>,<n>,... [--controls a,b]
        [--router-spread 4] [--rehearse]

For every seed: the sound program and each CONTROL - one wrong term planted
in the PROGRAM - run three steps on the generator's own path
(``common.mixed_precision_grad`` and ``optax.adamw(1e-3)`` from the seed's
weights on the pool's batches 0 to 2), and each is held to
``reference_ling.train`` of the same seed by the harness's own comparison,
``common.check_first_steps``, at the family's own limits: one JSON line a
seed and control with everything that comparison returns, ``ok`` in it. A
limit HOLDS a control when ``ok`` is false on every seed, and a sound
program has to read true on every seed. The reference's three steps are
computed once a seed (its program is ``check_first_steps``'s own, so a run
of the cell has cached it) and handed to every comparison of that seed.

``--router-spread x`` draws the routers' columns ``x`` times the program's
scale in program and reference alike (``mellum_lm.ROUTER_SPREAD`` is 4),
which ISSUE 50 allowed as a departure if ``correct`` could not tell the next
rank's experts without it: this file reads whether it does (PERF.md section
6, PR 50). It is no part of ``benchmark/run.py``'s path and no cell's file.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from typing import Any, Callable, Dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CELL, STEPS = "ling3-ft1", 3


class Variant:
    """The family with some of what it states replaced: the seeded weights
    (``--router-spread``), the loss a control trains on, or the reference's
    answer for one seed, already computed."""

    def __init__(self, family: Any, **replaced: Any) -> None:
        self._family, self._replaced = family, replaced

    def __getattr__(self, name: str) -> Any:
        replaced = self.__dict__["_replaced"]
        return replaced[name] if name in replaced else getattr(self.__dict__["_family"], name)


def float8(tree: Any) -> Any:
    """Every matrix through float8 e4m3, the precision below bfloat16; the
    gradient is taken at the rounded weights and is not itself rounded."""
    import jax

    def rounded(l: Any) -> Any:
        return l + jax.lax.stop_gradient(jax.lax.reduce_precision(l, 4, 3) - l)

    return jax.tree_util.tree_map(lambda l: rounded(l) if l.ndim >= 2 else l, tree)


def controls(cfg: Any) -> Dict[str, Callable[[Any], Any]]:
    """By name, ``family -> (params, tokens) -> loss`` with one term wrong.
    ISSUE 50 lists eight; one cannot be planted: heads 8-15 for 0-7 (no term
    of the program depends on a held head's index - the rotary embedding
    turns by position, and the weights are drawn a held head at a time)."""
    import jax.numpy as jnp

    from torchft_tpu.models import olmoe

    def with_cfg(changed: Any) -> Callable[[Any], Any]:
        return lambda family: lambda params, tokens: family.loss(changed, params, tokens)

    def patched(name: str, wrong: Any) -> Callable[[Any], Any]:
        def of(family: Any) -> Any:
            def loss(params: Any, tokens: Any) -> Any:
                right = getattr(olmoe, name)
                setattr(olmoe, name, wrong(right))
                try:
                    return family.loss(cfg, params, tokens)
                finally:
                    setattr(olmoe, name, right)
            return loss
        return of

    shallow = tuple(
        dataclasses.replace(k, mixer=dataclasses.replace(k.mixer, floor=-1.0))
        if isinstance(k.mixer, olmoe.Kda) else k for k in cfg.kinds
    )
    first, held = cfg.held
    return {
        "sound": with_cfg(cfg),
        "float8 weights": lambda family: lambda params, tokens: family.loss(
            cfg, float8(params), tokens
        ),
        "the next rank's experts": with_cfg(
            dataclasses.replace(cfg, held_experts=(first + held, held))
        ),
        "the bias left out of selection": patched(
            "_sigmoid_choice",
            lambda right: lambda c, logits, bias: right(c, logits, jnp.zeros_like(bias)),
        ),
        "the decay's bound at -1": with_cfg(dataclasses.replace(cfg, layer_kinds=shallow)),
        "conv4 left out": patched(
            "causal_conv",
            lambda right: lambda x, w: x.astype(jnp.float32) * w[-1].astype(jnp.float32),
        ),
        "no causal mask in MLA": patched(
            "flash_attention_rows", lambda right: functools.partial(right, causal=False)
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", help="comma-separated names; all where left out")
    ap.add_argument("--router-spread", type=float, default=1.0)
    ap.add_argument("--rehearse", action="store_true", help="the tiny sizes, on the CPU")
    args = ap.parse_args()

    from benchmark import common
    from torchft_tpu.platform import apply_compilation_cache_env

    apply_compilation_cache_env()
    import jax
    import optax

    _, entry = common.load_cell(CELL)
    sizes = entry["sizes"]
    if args.rehearse:
        sizes = {**sizes, **sizes["rehearsal"]}
    family = common.load_family(sizes["family"])
    cfg = family.build(sizes)
    batch, seq, pool = sizes["batch"], sizes["seq"], entry["mix"]["params"]["pool"]
    spread = args.router_spread
    if spread != 1.0:
        drawn = family.init

        def init(c: Any, key: Any) -> Any:
            params = drawn(c, key)
            return dict(params, blocks=[
                dict(b, moe=dict(b["moe"], router=b["moe"]["router"] * spread))
                if "moe" in b else b for b in params["blocks"]
            ])
        family = Variant(family, init=init)

    planted = controls(cfg)
    names = args.controls.split(",") if args.controls else list(planted)
    tx = optax.adamw(1e-3)
    make = jax.jit(common._from_seed(family, cfg, batch, seq, pool))
    norm = jax.jit(common.tree_norm)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def apply(params: Any, opt: Any, grads: Any) -> Any:
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt

    steps: Dict[str, Any] = {}
    for name in names:
        t0 = time.monotonic()
        loss = planted[name](family)
        grad = common.mixed_precision_grad(
            Variant(family, loss=lambda _, p, t, loss=loss: loss(p, t)), cfg
        )
        params, tokens = jax.eval_shape(make, *common._seed_words(1, 0))
        steps[name] = jax.jit(grad).lower(
            params, jax.ShapeDtypeStruct(tokens.shape[1:], tokens.dtype)
        ).compile()
        print(f"# compiled {name!r} in {time.monotonic() - t0:.0f} s", flush=True)

    def run(lo: Any, hi: Any, g: Any) -> Any:  # check_first_steps' own program
        params, tokens = common._from_seed(family, cfg, batch, seq, pool)(lo, hi, g)
        return family.reference_train(cfg, params, tokens[:STEPS])

    t0 = time.monotonic()
    with jax.default_matmul_precision("highest"):
        reference = jax.jit(run).lower(*common._seed_words(1, 0)).compile()
    print(f"# compiled the reference in {time.monotonic() - t0:.0f} s", flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        words = common._seed_words(seed, 0)
        got: Dict[str, Any] = {}
        for name in names:
            params, tokens = make(*words)
            opt, losses, first_norm = tx.init(params), [], None
            for i in range(STEPS):
                loss, grads = steps[name](params, tokens[i])
                if i == 0:
                    first_norm = float(norm(grads))
                params, opt = apply(params, opt, grads)
                losses.append(float(loss))
            got[name] = (losses, first_norm)
            del params, opt, grads, tokens  # the reference wants the chip's memory
        answer = jax.device_get(reference(*words))
        held = Variant(family, reference_train=lambda *_: answer)
        for name, (losses, first_norm) in got.items():
            verdict = common.check_first_steps(
                held, cfg, seed, 0, batch, seq, pool, losses, first_norm
            )
            verdict.pop("seconds")
            print(json.dumps(dict(
                seed=seed, control=name, router_spread=spread, **verdict
            )), flush=True)


if __name__ == "__main__":
    main()
