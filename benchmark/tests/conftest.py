"""The benchmark's own tests run by hand, here on the CPU:

    python -m pytest benchmark/tests -q -p no:cacheprovider

Four virtual CPU devices, so that the mesh cell's faults can be driven too.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
).strip()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # benchmark/
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))  # the checkout
