import os

# Tests must see exactly ONE device (the dry-run sets its own flag in a
# separate process).  Sharding tests spawn subprocesses with their own
# XLA_FLAGS.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
