"""Lighthouse CLI: ``python -m torchft_tpu.lighthouse``.

The standalone quorum service, the role of the reference's
``torchft_lighthouse`` entrypoint (reference pyproject.toml:37-38,
src/bin/lighthouse.rs:10-23). Defaults mirror the reference CLI
(src/lighthouse.rs:66-103).

Three roles (``--role``):

- ``flat`` (default): the single-service deployment — every replica group
  heartbeats/renews into this one process.
- ``root``: identical server, but named for the hierarchical deployment —
  region lighthouses push membership digests into it and it computes the
  global quorum.
- ``region``: the middle tier. Serves the manager-facing protocol locally,
  aggregates its groups into digests pushed to ``--root``, long-polls the
  global quorum back out. See docs/OPERATIONS.md "control-plane deployment"
  for when to add a region tier.

Every role serves ``GET /status.json`` (machine-readable members, lease
deadlines, last quorum id, tier role) next to the HTML dashboard;
:func:`fetch_status` is the programmatic consumer (no scraping of the
HTML).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import threading
import urllib.request
from typing import Optional, Sequence

from . import _native

logger = logging.getLogger(__name__)


def _fetch_json(addr: str, path: str, timeout: float) -> dict:
    if not addr.startswith("http://") and not addr.startswith("https://"):
        addr = "http://" + addr
    with urllib.request.urlopen(addr + path, timeout=timeout) as r:
        return json.loads(r.read().decode("utf-8"))


def fetch_status(addr: str, timeout: float = 5.0) -> dict:
    """Fetches a lighthouse's (any role) machine-readable status view.

    ``addr`` is the service address (``http://host:port`` or ``host:port``).
    """
    return _fetch_json(addr, "/status.json", timeout)


def fetch_quorum(addr: str, timeout: float = 5.0) -> dict:
    """Fetches a REGION lighthouse's cached view of the last global quorum
    (``GET /quorum.json``): served from the region-side cache the standing
    root poll maintains, so reading it generates no root traffic — the
    read-mostly path for dashboards and fleet tooling. ``age_ms`` is the
    time since the cache was refreshed off the root (null before the first
    root quorum lands); with the root down the cache keeps serving while
    ``age_ms`` grows and ``root_connected`` goes false."""
    return _fetch_json(addr, "/quorum.json", timeout)


def main(argv: Optional[Sequence[str]] = None) -> None:
    parser = argparse.ArgumentParser(
        prog="torchft_tpu.lighthouse",
        description="Quorum service (flat, hierarchical root, or region tier) "
        "for torchft_tpu replica groups.",
    )
    parser.add_argument("--bind", default="[::]:29510")
    parser.add_argument(
        "--role",
        choices=("flat", "root", "region"),
        default="flat",
        help="flat/root: the quorum-computing service; region: aggregate "
        "local groups into digests pushed to --root",
    )
    parser.add_argument(
        "--root",
        default=os.environ.get("TORCHFT_LIGHTHOUSE_ROOT", ""),
        help="root lighthouse address (required for --role region; env "
        "TORCHFT_LIGHTHOUSE_ROOT)",
    )
    parser.add_argument(
        "--region-id",
        default="",
        help="stable region name reported in root status (default: bind addr)",
    )
    parser.add_argument(
        "--digest-interval-ms",
        type=int,
        default=int(os.environ.get("TORCHFT_DIGEST_INTERVAL_MS", "100")),
        help="cadence of periodic region->root digests (urgent pushes fire "
        "immediately; env TORCHFT_DIGEST_INTERVAL_MS)",
    )
    parser.add_argument("--min_replicas", type=int, default=1)
    parser.add_argument("--join_timeout_ms", type=int, default=60000)
    parser.add_argument("--quorum_tick_ms", type=int, default=100)
    parser.add_argument("--heartbeat_timeout_ms", type=int, default=5000)
    # ---- durable control plane (flat/root roles; see OPERATIONS.md
    # "control-plane durability & failover") ----
    parser.add_argument(
        "--wal-dir",
        default=os.environ.get("TORCHFT_LH_WAL_DIR", ""),
        help="write-ahead quorum log + snapshot directory (env "
        "TORCHFT_LH_WAL_DIR); empty = in-memory only",
    )
    parser.add_argument(
        "--snapshot-every",
        type=int,
        default=int(os.environ.get("TORCHFT_LH_SNAPSHOT_EVERY", "0")),
        help="WAL records per snapshot compaction (env "
        "TORCHFT_LH_SNAPSHOT_EVERY; 0 = default 512)",
    )
    parser.add_argument(
        "--peers",
        default=os.environ.get("TORCHFT_LH_PEERS", ""),
        help="comma-separated OTHER root endpoints of this root's "
        "failover set (env TORCHFT_LH_PEERS)",
    )
    parser.add_argument(
        "--standby",
        action="store_true",
        default=os.environ.get("TORCHFT_LH_STANDBY", "") in ("1", "on", "true"),
        help="start as a passive warm standby: tail the active peer and "
        "take over when its lease lapses (env TORCHFT_LH_STANDBY=1)",
    )
    parser.add_argument(
        "--takeover-ms",
        type=int,
        default=int(os.environ.get("TORCHFT_LH_TAKEOVER_MS", "0")),
        help="standby takeover bound: sync starvation longer than this "
        "claims a new root epoch (env TORCHFT_LH_TAKEOVER_MS; 0 = 3000)",
    )
    args = parser.parse_args(argv)

    logging.basicConfig(level=logging.INFO)
    if args.role == "region":
        if not args.root:
            parser.error("--role region requires --root (or TORCHFT_LIGHTHOUSE_ROOT)")
        server: object = _native.RegionLighthouse(
            root_addr=args.root,
            region_id=args.region_id or args.bind,
            bind=args.bind,
            digest_interval_ms=args.digest_interval_ms,
            heartbeat_timeout_ms=args.heartbeat_timeout_ms,
        )
    else:
        server = _native.Lighthouse(
            bind=args.bind,
            min_replicas=args.min_replicas,
            join_timeout_ms=args.join_timeout_ms,
            quorum_tick_ms=args.quorum_tick_ms,
            heartbeat_timeout_ms=args.heartbeat_timeout_ms,
            wal_dir=args.wal_dir,
            snapshot_every=args.snapshot_every,
            peers=args.peers,
            standby=args.standby,
            takeover_ms=args.takeover_ms,
        )
    logger.info(f"{args.role} lighthouse serving on {server.address()}")  # type: ignore[attr-defined]

    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    server.shutdown()  # type: ignore[attr-defined]


if __name__ == "__main__":
    main()
