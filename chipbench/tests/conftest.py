"""Four CPU host devices for the benchmark's tests, set before JAX is
imported, so a four-chip cell runs its replicas each on a device of its
own at smoke size."""
import os

os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""), "--xla_force_host_platform_device_count=4"]).strip()
