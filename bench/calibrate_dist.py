#!/usr/bin/env python3
"""``calibrate.py`` for the row-sharded cells, with one more fault: the
distributed tier's halo exchange sending zeros (``no_halo``).

    python3 bench/calibrate_dist.py --workload jacobi2d5pt.dist4 \
        --seconds 5 --fault no_halo --fault-seeds 301,302

Everything else, the seeds, the control and ``dropped_step``, is
``calibrate.py``'s.
"""
from __future__ import annotations

import contextlib
import sys

import calibrate


def _no_halo(dispatch):
    """Each exchange of boundary rows between chips hands every shard zeros
    in its neighbours' place; the shard's own rows are stepped as before."""
    import jax.numpy as jnp
    from repro.exec import adapters

    def zero_halo(x, radius, axis, **kw):
        top, bot = exchange(x, radius, axis, **kw)
        return jnp.zeros_like(top), jnp.zeros_like(bot)

    exchange = adapters.halo_exchange

    def broken(*args):
        with _swapped(adapters, "halo_exchange", zero_halo):
            return dispatch(*args)
    return broken


@contextlib.contextmanager
def _swapped(module, name, value):
    real = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, real)


calibrate.FAULTS["no_halo"] = _no_halo

if __name__ == "__main__":
    sys.exit(calibrate.main())
