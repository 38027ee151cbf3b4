import os

# Tests run on host CPU devices — the dry-run (and only the dry-run)
# forces 512 devices via its own XLA_FLAGS before jax init.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Force an 8-device CPU ring for the whole suite (must land before the
# first jax backend init) so the period-program executor and every
# shard_map path are tested on a real multi-device mesh without TPUs
# (launch.mesh.make_test_mesh picks these up).
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count=8 {_flags}".strip())

from hypothesis import settings  # noqa: E402

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")
