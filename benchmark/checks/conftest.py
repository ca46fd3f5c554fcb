"""The checks run on the CPU, on one device as the cells have one chip,
and print no metric.  Run them with

    python -m pytest benchmark/checks -q

They are no part of the repo's tier-1 tests."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# a rehearsal leaves no compiled code behind: the caches a run places are
# for the chip (and XLA:CPU cannot always load what it cached)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
os.environ["MXTPU_PROGRAM_CACHE"] = ""
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=1")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), HERE]
