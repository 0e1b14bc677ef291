import os

import pytest

# Tests must see exactly ONE device (the dry-run sets its own flag in a
# separate process).  Sharding tests spawn subprocesses with their own
# XLA_FLAGS.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture
def round_needs(monkeypatch):
    """Spy on the compacted detector batch (``exsample.detect_needed``, as
    the resident, slot and mesh rounds call it): every call records on the
    host how many of its lanes the round needed.  Returns the list the
    counts land in, complete once ``jax.effects_barrier()`` returns.  A
    program compiled before the spy went in does not call it, so run the
    spied search with a detector no earlier test traced."""
    import jax.numpy as jnp

    from repro.core import exsample, executor

    counts = []
    real = exsample.detect_needed

    def spy(detector, keys, frames, need):
        jax.debug.callback(lambda n: counts.append(int(n)), jnp.sum(need))
        return real(detector, keys, frames, need)

    monkeypatch.setattr(exsample, "detect_needed", spy)
    monkeypatch.setattr(executor, "detect_needed", spy)
    return counts
