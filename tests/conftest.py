import os

# Keep CPU smoke tests single-device: the dry-run (and only the dry-run)
# forces 512 host devices in its own process.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
from hypothesis import settings

jax.config.update("jax_enable_x64", False)

# No on-disk example database: no run replays failures saved in an earlier
# run's .hypothesis/; counterexamples worth keeping are pinned with @example.
settings.register_profile("repro", database=None)
settings.load_profile("repro")
