#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

prints, as the last line of its standard output, one JSON object with
the keys ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
traced ``breakdown``, and last ``compared`` (each number held to the
reference, beside its limit): the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``. It exits
non-zero, and prints no result, without the chips the cell asks for.

Everything that belongs to one configuration, traffic mix, generator,
model family or metric is a file found by its name (README.md); this
file holds no list of them.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
# the checkout's own directories, never a fixed path outside it
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="off-chip rehearsal: the configuration's tiny 'rehearsal' sizes "
        "on the CPU; prints no result line",
    )
    ap.add_argument("--worker", metavar="SCRATCH", help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def build_cell(args: argparse.Namespace) -> Dict[str, Any]:
    from benchmark import common

    contract, entry = common.load_cell(args.workload)
    sizes = entry["sizes"]
    if args.rehearse:
        sizes = {**sizes, **sizes["rehearsal"]}

    def mine(metrics: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        return [
            m for m in metrics
            if "workloads" not in m or args.workload in m["workloads"]
        ]

    out = os.path.join(BENCH, "out", args.workload)
    stem = f"{args.seed}-{args.trace}-{os.getpid()}"
    return {
        "name": args.workload,
        "generator": entry["mix"]["generator"],
        "params": entry["mix"]["params"],
        "chips": entry["chips"],
        "sizes": sizes,
        "seed": args.seed,
        "seconds": args.seconds,
        "trace": bool(args.trace),
        "rehearse": args.rehearse,
        "end_to_end": mine(contract["end_to_end"]),
        "per_layer": mine(contract["per_layer"]),
        "run_file": os.path.join(out, stem + ".json"),
        "scratch": args.worker or os.path.join(out, stem + ".d"),
        "argv": [
            "--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", str(args.trace),
            *(["--rehearse"] if args.rehearse else []),
        ],
        # a cold first run compiles; a run that hangs is cut well inside
        # the driver's own limit for it
        "deadline_s": args.seconds + 1000,
        "phases": common.Phases(T0),
        "t0": T0,
    }


def window_facts(cell: Dict[str, Any], facts: Dict[str, Any]) -> None:
    """The window's numbers (estimator.py) from group 0's first life,
    whose stamp opens it, for the readers."""
    from benchmark import estimator

    lead = next(g for g in facts["groups"] if g["group"] == 0 and g["life"] == 0)
    facts["window"] = estimator.window(
        lead["steps"][lead["open_at"]:], cell["seconds"], facts["tokens_per_step"]
    )
    facts["setup_s"] = facts["t_open"] - cell["t0"]


def read_metrics(directory: str, wanted: List[Dict[str, Any]], facts: Dict[str, Any]) -> Dict[str, Any]:
    """Each metric is read by the file of its own name; a reader that
    finds nothing to read returns None and the metric is left out. A
    quantity entered once for each end-to-end metric its cells report
    (``<quantity>.<which>``: ``mfu.routed`` beside ``mfu``) is read by the
    quantity's one file where the entry has none of its own."""
    from benchmark import common

    metrics = {}
    for m in wanted:
        name = m["name"]
        if "." in name and not os.path.isfile(
            os.path.join(common.BENCH, directory, name + ".py")
        ):
            name = name.rsplit(".", 1)[0]
        value = common.load_by_name(directory, name).read(facts)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def compared(facts: Dict[str, Any]) -> Dict[str, List[float]]:
    """Each number ``correct`` compared with the plain reference, beside
    its limit: ``{name: [number, limit]}``."""
    ref = facts.get("reference") or {}
    out = {
        f"loss{i}_rel_err": [err, ref["loss_rtol"]]
        for i, err in enumerate(ref.get("loss_rel_err", []))
    }
    if "grad_norm_rel_err" in ref:
        out["grad_norm0_rel_err"] = [ref["grad_norm_rel_err"], ref["grad_norm_rtol"]]
    return out


def plain(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:\-]", "_", name)


def main() -> int:
    args = parse()
    if not all(
        os.path.isdir(os.path.join(REPO, d)) for d in ("torchft_tpu", "native")
    ):
        print(
            "benchmark: must run from a checkout of the repository (no "
            f"torchft_tpu/ and native/ beside {BENCH})", file=sys.stderr,
        )
        return 2
    from benchmark import common

    cell = build_cell(args)
    kind = common.load_by_name("traffic", cell["generator"])
    if args.worker:
        try:
            kind.worker(cell)
        except common.Refused as e:
            common.fail(str(e), 3)
        return 0

    if not args.rehearse:
        os.environ["JAX_PLATFORMS"] = "tpu"  # a missing chip is an error
    common.ensure_native()
    cell["phases"].mark("native_build")
    shutil.rmtree(cell["scratch"], ignore_errors=True)
    os.makedirs(cell["scratch"])
    try:
        facts = kind.run(cell)
    except common.Refused as e:
        common.fail(str(e), 3)
    window_facts(cell, facts)
    facts["peaks"] = None if args.rehearse else common.peaks(facts["device"]["kind"])

    correct = all(facts["checks"].values())
    metrics = read_metrics(
        "layer_metrics" if cell["trace"] else "end_to_end",
        cell["per_layer"] if cell["trace"] else cell["end_to_end"], facts,
    )
    result: Dict[str, Any] = {
        "correct": correct, "attempted": facts["attempted"],
        "failed": facts["failed"], "metrics": metrics, "device": facts["device"],
    }
    trace = facts.get("trace")
    if cell["trace"] and trace:
        result["device"].update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {
            key: [[plain(name), s] for name, s in trace[key]]
            for key in ("device_ops", "idle_gaps")
        }
    result["compared"] = compared(facts)  # the last key of the line

    # what follows the last phase the generator marked: the reduction
    # (raw), the fleet's whole life (ft-sync's parent)
    cell["phases"].mark("rest")
    phases = dict(cell["phases"].seconds, wall_s=time.monotonic() - T0)
    with open(cell["run_file"], "w") as f:
        json.dump({
            "cell": cell["name"], "argv": cell["argv"], "result": result,
            "checks": facts["checks"], "setup_phases": phases,
            **{k: facts.get(k) for k in (
                "t_open", "setup_s", "window", "kill",
                "groups", "reference", "manager_metrics", "op_stats", "raw",
                "worker_phases", "trace", "tokens_per_step", "discarded_at_kill",
                "memory_stats", "flops_per_step", "flash", "family", "peaks",
                "routing",
            )},
        }, f)
    shutil.rmtree(cell["scratch"], ignore_errors=True)
    common.say("setup_phases " + json.dumps(
        {"parent": phases, "workers": facts.get("worker_phases")}
    ))
    common.say("checks " + json.dumps(facts["checks"]))
    common.say("window " + json.dumps(facts["window"]))
    if facts.get("routing"):  # what routing the run timed (ft_sync says where it reads)
        common.say("routing " + json.dumps(facts["routing"]))
    for name, (number, limit) in result["compared"].items():
        print(f"compared {name} {number} limit {limit}", file=sys.stderr, flush=True)
    if args.rehearse:
        common.say("REHEARSAL on the CPU, not a measurement: " + json.dumps(result))
        return 0 if correct else 1
    common.say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
