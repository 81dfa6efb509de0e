"""The benchmark's own tests run on the CPU at tiny sizes:
``python3 -m pytest benchmark/tests -q`` from the root of the checkout.
Four virtual devices for the mesh path; both variables are read when JAX
is first imported, so they are set here and nowhere in the benchmark."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
