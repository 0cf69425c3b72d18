"""The rehearsals run on the CPU backend with four virtual devices. This
is the tests' own steering: nothing under ``benchmark/`` reads it, and
``run.py`` started without it on a machine with no TPU exits non-zero."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for path in (os.path.dirname(BENCH), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
