"""What every traffic generator of the benchmark shares: finding files by
name, the one compile cache, the refusal to run without the chip, the
weights and token pools from the seed, the per-step log and the
profiler window.

Nothing here imports ``chip_smoke.py``; what was sound in it was copied
(the start line, the kill marker, the Mosaic check, the peak table).

Nothing here, and nothing in a generator, holds a number that is true of
one family only: how many Mosaic calls a lowered step holds, how close its
losses must come to its reference, what else a reader may want of it -
each is the family's to say (``load_family``).
"""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)


class Refused(Exception):
    """The run cannot be a measurement (no chip, wrong device, a kernel
    that did not compile): exit non-zero, print no metric."""


def load_json(*parts: str) -> Any:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_by_name(directory: str, name: str) -> Any:
    """The module ``benchmark/<directory>/<name>.py``. Generators,
    families and layer metrics are found this way, by the name a data
    file gives; the harness has no list of them."""
    path = os.path.join(BENCH, directory, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {directory} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{directory}_{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# What a family states (families/<family>.py; benchmark/README.md says what
# each is). None has a default: a family that leaves one out is an error.
FAMILY_STATES = (
    "build", "init", "loss", "reference_train", "tokens_per_step",
    "flops_per_step", "flash_calls", "lowered_mosaic_calls", "facts",
    "LOSS_RTOL", "GRAD_NORM_RTOL",
)
# What a family MAY state besides (README.md): ``routing(cfg, params, tokens)
# -> {name: array of (pool,)}``, which ``ft_sync`` reads on the state after
# the window and around a trace. A family without it runs what it ran.


def load_family(name: str) -> Any:
    """``families/<name>.py``, which must state all of ``FAMILY_STATES``."""
    family = load_by_name("families", name)
    missing = [what for what in FAMILY_STATES if not hasattr(family, what)]
    if missing:
        raise AttributeError(
            f"benchmark/families/{name}.py does not state {', '.join(missing)}"
        )
    return family


def load_cell(name: str) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """A cell is its entry in ``BENCHMARK.json``, which names its
    configuration (whose ``file`` holds the sizes) and its traffic mix
    (``traffic/<traffic>.json``: the ``generator`` that reads it and its
    ``params``). Returns the contract, the entry with ``sizes`` and
    ``mix`` added."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        contract = json.load(f)
    entry = next((w for w in contract["workloads"] if w["name"] == name), None)
    if entry is None:
        fail(f"BENCHMARK.json has no cell {name!r}", 2)
    config = next(c for c in contract["configs"] if c["name"] == entry["config"])
    with open(os.path.join(REPO, config["file"])) as f:
        sizes = json.load(f)
    return contract, dict(entry, sizes=sizes, mix=load_json("traffic", entry["traffic"] + ".json"))


class Phases:
    """Set-up split by phase: each ``mark`` closes the phase that ran
    since the last one."""

    def __init__(self, start: float) -> None:
        self._last = start
        self.seconds: Dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now


def ensure_native() -> None:
    """Builds ``_libtorchft.so`` when the checkout has none (the first
    run in a checkout only; the library is git-ignored)."""
    if not os.path.exists(os.path.join(REPO, "torchft_tpu", "_libtorchft.so")):
        subprocess.run(
            ["make", "-C", os.path.join(REPO, "native"), f"-j{os.cpu_count() or 4}"],
            check=True, stdout=subprocess.DEVNULL,
        )


def require_tpu(expect_chips: int, rehearse: bool = False) -> Dict[str, Any]:
    """The device as JAX reports it, which must be ``expect_chips`` TPU
    chips of a kind ``peaks.json`` knows. Also turns on the one compile
    cache (``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache`` in
    the checkout) before the backend comes up. A rehearsal takes the CPU
    and says so; its numbers are never printed as a result."""
    from torchft_tpu.platform import apply_compilation_cache_env

    apply_compilation_cache_env()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no accelerator: {e}") from e
    if rehearse:
        return {"platform": devices[0].platform, "kind": devices[0].device_kind,
                "count": expect_chips}
    if devices[0].platform != "tpu" or jax.default_backend() != "tpu":
        raise Refused(
            f"the benchmark measures only on a TPU; JAX initialised "
            f"{devices[0].platform!r}"
        )
    if len(devices) != expect_chips:
        raise Refused(
            f"this process needs {expect_chips} chip(s) and sees {len(devices)}"
        )
    kind = devices[0].device_kind
    if kind not in load_json("peaks.json")["devices"]:
        raise Refused(f"no peaks for device_kind {kind!r} in benchmark/peaks.json")
    return {"platform": "tpu", "kind": kind, "count": len(devices)}


def peaks(kind: str) -> Dict[str, float]:
    return load_json("peaks.json")["devices"][kind]


def require_mosaic(lowered: Any, want: int, what: str) -> None:
    """The step that is measured must carry the compiled kernels: a
    Pallas interpret fallback lowers to no Mosaic custom call. ``want``
    is the family's ``lowered_mosaic_calls(cfg)``: how many a step holds
    follows from its layers' kinds and its kernels' structure, which the
    family knows and the harness does not."""
    found = lowered.as_text().count("tpu_custom_call")
    if found != want:
        raise Refused(
            f"{what}: lowered module has {found} Mosaic custom call(s), "
            f"the family states {want} - the kernels did not compile for the chip"
        )


def memory_stats() -> Dict[str, int]:
    import jax

    return {k: int(v) for k, v in (jax.devices()[0].memory_stats() or {}).items()}


def peak_memory_bytes() -> int:
    """Bytes held on the chip while the window's steps run, read right
    after its last step: the live buffers (masters, moments, batches)
    plus what the runtime holds reserved for the step's temporaries. On
    the v5e ``bytes_in_use`` counts only the first and ``bytes_reserved``
    only the second (the compiler's ``temp_size_in_bytes`` of the step);
    they are disjoint parts of the 16 GB. The two PEAKS the runtime also
    reports do not coincide in time - their sum read 20.3 GB for
    gpt2-medium, more than the chip has - so this is the reading at one
    instant, or the live peak alone where that is larger."""
    stats = memory_stats()
    return max(
        stats.get("peak_bytes_in_use", 0),
        stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0),
    )


def _seed_words(seed: int, group: int) -> Tuple[Any, ...]:
    import numpy as np

    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32), np.uint32(group)


def _from_seed(family: Any, cfg: Any, batch: int, seq: int, pool: int) -> Callable:
    """``(seed words, group) -> (f32 masters, int32[pool, batch, seq])``,
    to be traced: the weights come from the seed alone (the same in every
    group), the pool of token batches from the seed and the group."""
    import jax
    import jax.numpy as jnp

    def make(lo: Any, hi: Any, g: Any) -> Tuple[Any, Any]:
        key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
        tokens = jax.random.randint(
            jax.random.fold_in(jax.random.fold_in(key, 1), g),
            (pool, batch, seq), 0, cfg.vocab_size, jnp.int32,
        )
        return family.init(cfg, jax.random.fold_in(key, 0)), tokens

    return make


def make_state(
    family: Any, cfg: Any, seed: int, group: int, batch: int, seq: int,
    pool: int, tx: Any,
) -> Tuple[Any, ...]:
    """The f32 master weights, this group's pool of token batches and the
    optimizer's state: made on the device in one jitted call. The seed is
    an argument of the program, not a constant in it, so every seed runs
    the one cached executable."""
    import jax

    make = _from_seed(family, cfg, batch, seq, pool)

    def state(lo: Any, hi: Any, g: Any) -> Tuple[Any, ...]:
        params, tokens = make(lo, hi, g)
        return params, [tokens[i] for i in range(pool)], tx.init(params)

    return jax.block_until_ready(jax.jit(state)(*_seed_words(seed, group)))


def mixed_precision_grad(family: Any, cfg: Any) -> Callable:
    """``(masters, tokens) -> (loss, grads)`` in a bf16 compute copy of
    the f32 masters (the discipline of ``make_train_step(bf16_params=
    True)``): the gradient tree that crosses groups is bf16, the optimizer
    updates the masters."""
    import jax
    import jax.numpy as jnp

    def loss_and_grads(masters: Any, tokens: Any) -> Any:
        compute = jax.tree_util.tree_map(
            lambda l: l.astype(jnp.bfloat16) if l.dtype == jnp.float32 else l,
            masters,
        )
        return jax.value_and_grad(lambda q: family.loss(cfg, q, tokens))(compute)

    return loss_and_grads


def all_finite(losses: List[Optional[float]]) -> bool:
    """Every loss that exists is a finite number (a failed step has none)."""
    import math

    return all(l is None or math.isfinite(l) for l in losses)


def masters_are_f32(tree: Any) -> bool:
    import jax
    import jax.numpy as jnp

    return all(
        leaf.dtype == jnp.float32
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating)
    )


def tree_norm(tree: Any) -> Any:
    import jax
    import jax.numpy as jnp

    return jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree_util.tree_leaves(tree)
    ))


def check_first_steps(
    family: Any, cfg: Any, seed: int, group: int, batch: int, seq: int,
    pool: int, losses: List[Optional[float]], grad_norm: Optional[float] = None,
) -> Dict[str, Any]:
    """Holds what the MEASURED programs produced to the plain reference.

    ``losses`` are the first losses of the run's own loop - the programs
    the window then measures, started on the seed's fresh weights and fed
    the pool's batches 0, 1, ... whole. The reference (the family's
    ``reference_train``: float32, dense attention, plain AdamW) makes the
    same weights and batches from the seed inside one jitted program and
    trains as many steps; loss k agrees only if the model, its gradient
    and k optimizer updates do. ``grad_norm``, where the generator has
    a gradient to show (``ft-sync``: the tree that crosses groups), is
    that of step 0. The tolerances are the family's (``LOSS_RTOL``,
    ``GRAD_NORM_RTOL``), set and reasoned where its reference is."""
    import jax

    t0 = time.monotonic()
    steps = len(losses)
    make = _from_seed(family, cfg, batch, seq, pool)

    def run(lo: Any, hi: Any, g: Any) -> Any:
        params, tokens = make(lo, hi, g)
        return family.reference_train(cfg, params, tokens[:steps])

    with jax.default_matmul_precision("highest"):
        ref_losses, ref_norms = jax.device_get(
            jax.jit(run)(*_seed_words(seed, group))
        )
    ref_losses = [float(x) for x in ref_losses]
    # relative, with a floor of 1 under the reference so that a loss near
    # zero (a rehearsal that memorised its pool) is not held to 1e-3 of it
    loss_err = [
        float("inf") if a is None else abs(a - b) / max(abs(b), 1.0)
        for a, b in zip(losses, ref_losses)
    ]
    ok = all(e <= family.LOSS_RTOL for e in loss_err)
    out: Dict[str, Any] = {
        "losses": losses, "reference_losses": ref_losses,
        "loss_rel_err": loss_err, "loss_rtol": family.LOSS_RTOL,
    }
    if grad_norm is not None:
        ref_norm = float(ref_norms[0])
        norm_err = abs(grad_norm - ref_norm) / max(abs(ref_norm), 1.0)
        ok = ok and norm_err <= family.GRAD_NORM_RTOL
        out.update(
            grad_norm=grad_norm, reference_grad_norm=ref_norm,
            grad_norm_rel_err=norm_err, grad_norm_rtol=family.GRAD_NORM_RTOL,
        )
    return dict(out, seconds=time.monotonic() - t0, ok=bool(ok))


class StepLog:
    """Per-step records of one group, on the host's monotonic clock
    (one clock for every process of the host).

    ``done`` is called once a step has been dispatched (and, under the
    transaction, decided). It first waits for the PREVIOUS step's loss,
    so the host stays one step ahead of the device and never further,
    and then stamps the step. Every stamp therefore lags the device by
    the same one step, and the interval between two stamps is one step
    of device time; a window between two stamps counts whole steps."""

    def __init__(self, span: Callable[[str], Any]) -> None:
        self.records: List[Dict[str, Any]] = []
        self.losses: List[Any] = []
        self._span = span
        self._previous: Any = None

    def done(
        self, loss: Any, committed: bool = True, participants: int = 1,
        **more: Any,
    ) -> Dict[str, Any]:
        if self._previous is not None:
            with self._span("bench::wait_previous_step"):
                self._previous.block_until_ready()
        record = {
            "t": time.monotonic(), "committed": committed,
            "participants": participants, **more,
        }
        self.records.append(record)
        self._previous = loss
        self.losses.append(loss)
        return record

    def drain(self) -> float:
        """Waits for the last step, so that nothing is in flight when a
        clock is read after the loop; returns that clock."""
        if self._previous is not None:
            self._previous.block_until_ready()
        return time.monotonic()

    def loss_values(self) -> List[Optional[float]]:
        """Every step's loss on the host, fetched in one batch."""
        import jax

        fetched = iter(jax.device_get([l for l in self.losses if l is not None]))
        return [None if l is None else float(next(fetched)) for l in self.losses]


def null_span(_name: str) -> Any:
    return contextlib.nullcontext()


def reduce_trace(path: str) -> Dict[str, Any]:
    """What a traced run keeps of its ``.xplane.pb``: busy and idle time
    and the breakdown (``reduce/xplane.py``), and the trace whole beside
    them (``reduce/spans.py``, a second parse of the same file): device
    seconds of every Mosaic kernel by name (``kernels_s``), by scope class
    (``scopes_s``) and by scope path (``paths_s``). ``parse_s`` is what
    the two parses cost the run."""
    from benchmark.reduce import spans, xplane

    t0 = time.monotonic()
    out = xplane.reduce_file(path)
    t1 = time.monotonic()
    whole = spans.reduce_file(path)
    out.update({k: whole[k] for k in ("kernels_s", "scopes_s", "paths_s")})
    out["parse_s"] = {"xplane": t1 - t0, "spans": time.monotonic() - t1}
    return out


class Tracer:
    """The profiler window of a traced run: ``start`` .. ``stop`` around
    a few steps, then the reduction of what it wrote. Untraced runs never
    construct one, and so never start a profiler."""

    def __init__(self, directory: str, rehearse: bool = False) -> None:
        self.directory = directory
        self.rehearse = rehearse

    @staticmethod
    def span(name: str) -> Any:
        import jax.profiler

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        import jax.profiler

        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the spans are ours, not Python frames
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def stop(self) -> None:
        import jax.profiler

        jax.profiler.stop_trace()

    def reduce(self) -> Optional[Dict[str, Any]]:
        paths = glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb"
        ))
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under {self.directory}")
        try:
            return reduce_trace(paths[0])
        except ValueError:
            if self.rehearse:  # a CPU trace has no device plane
                return None
            raise
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)


def say(text: str) -> None:
    print(text, flush=True)


def fail(text: str, code: int = 1) -> "NoReturn":  # noqa: F821
    print(f"benchmark: {text}", file=sys.stderr, flush=True)
    sys.exit(code)
