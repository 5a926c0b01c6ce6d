import os
import subprocess
import sys

# JAX on a virtual 8-device CPU mesh: multi-chip sharding paths are tested
# without TPU hardware (the driver's dryrun uses the same trick). Must be set
# before the first `import jax` anywhere in the test session.
# Force the CPU even on a machine with a chip: the unit suite needs 8
# virtual devices, and one process per chip means a test session must not
# hold one. chip_smoke.py is what runs on the hardware.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

_LIB = os.path.join(REPO_ROOT, "torchft_tpu", "_libtorchft.so")
if not os.path.exists(_LIB):
    subprocess.run(["make", "-C", os.path.join(REPO_ROOT, "native")], check=True)

def pytest_configure(config):
    # tier-1 filters with -m 'not slow'; register the marker so it is a
    # contract, not a typo-prone string.
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos/fleet schedules excluded from tier-1",
    )
