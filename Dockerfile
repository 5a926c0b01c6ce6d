# Deploy image, the role of the reference's Dockerfile (reference
# Dockerfile:1-5, which ships a maturin builder + protoc). This image
# builds the C++ control plane, installs the package, and can run any of
# the entry points — the example trainer on CPU JAX by default:
#
#   docker build -t torchft-tpu .
#   docker run torchft-tpu                                    # demo trainer
#   docker run torchft-tpu torchft-tpu-lighthouse --bind [::]:29510
#   docker run torchft-tpu torchft-tpu-launcher --num-replica-groups 2 \
#       -- python examples/train_ddp.py
#
# For real TPU hosts, base on a TPU-enabled JAX image instead and set
# JAX_PLATFORMS=tpu (a missing chip is then an error, not a CPU fallback).
FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make protobuf-compiler libprotobuf-dev \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY . /app

RUN pip install --no-cache-dir "jax[cpu]" optax ml_dtypes \
    && pip install --no-cache-dir -e . -v

ENV JAX_PLATFORMS=cpu NUM_STEPS=30
# One-process demo: in-process lighthouse, single replica group. Multi-group
# deployments run one container per replica group pointed at a shared
# lighthouse via TORCHFT_LIGHTHOUSE (docs/OPERATIONS.md). On a TPU host a
# chip belongs to one process: either one container per group with its
# own chip, or one launcher per host with --chips-per-group 1, which
# pins group g (and every restart of it) to chip g before the group's
# first backend initialisation (more than one chip a group is refused:
# ROADMAP S2). The launcher itself never touches the JAX backend.
# `python chip_smoke.py` proves the layout; speeds are in PERF.md.
CMD ["torchft-tpu-launcher", "--num-replica-groups", "1", \
     "python", "examples/train_ddp.py"]
