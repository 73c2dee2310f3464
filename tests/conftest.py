import os

# Tests run on the single real CPU device (the 512-device override belongs to
# dryrun.py ONLY). Some CI shells inherit XLA_FLAGS; strip the device-count
# flag defensively.
flags = os.environ.get("XLA_FLAGS", "")
os.environ["XLA_FLAGS"] = " ".join(
    f for f in flags.split() if "force_host_platform_device_count" not in f)

# The suite runs on the CPU: kernels in interpret mode where a test asks for
# them, the XLA reference otherwise. Worker and forced-device subprocesses
# inherit this environment, so they stay on the CPU too.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
