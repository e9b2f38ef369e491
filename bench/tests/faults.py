"""Faults planted under the timed path, for the test that sees ``correct``
come out false: each wraps one method of the runner the harness built."""

from __future__ import annotations

import numpy as np


def unchanged(runner):
    """A chunk that returns its state unchanged (only the counter moves)."""
    runner.run_chunk = lambda state, hold=None: state._replace(
        chunk=state.chunk + 1)


def half(runner):
    """Half of the batch left out of every chunk."""
    inner = runner.run_chunk

    def run_chunk(state, hold=None):
        n = runner.cfg.n_instances
        h = np.zeros(n, bool)
        h[n // 2:] = True
        return inner(state, hold=h if hold is None else (h | hold))

    runner.run_chunk = run_chunk


def altered(runner):
    """An answer altered where it is produced: every moving vehicle leaves
    the chunk 1 cm/s faster than it computed."""
    import jax.numpy as jnp

    inner = runner.run_chunk

    def run_chunk(state, hold=None):
        out = inner(state, hold)
        sim = out.sim
        return out._replace(sim=sim._replace(
            vel=jnp.where(sim.active, sim.vel + 0.01, sim.vel)))

    runner.run_chunk = run_chunk


FAULTS = {"unchanged": unchanged, "half": half, "altered": altered}
