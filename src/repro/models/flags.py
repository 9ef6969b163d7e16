"""Global model-construction flags.

``FULL_UNROLL``: XLA's ``cost_analysis()`` counts a while-loop body ONCE,
ignoring the trip count, so costs read from scan-structured HLO undercount
FLOPs/bytes by ~L.  A program whose cost is read is therefore built with
every scan fully unrolled (``lax.scan(..., unroll=length)`` eliminates the
loop): ``tests/test_tpu_compile.py`` does so for the SSD scan's bytes and
``chipbench/tests/test_flops.py`` for the forward's matrix products.
Training/serving keep the rolled form (small HLO, fast compiles).

Use the ``scan`` wrapper below at every scan site so one flag flips all of
them consistently.
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax

FULL_UNROLL = False


@contextlib.contextmanager
def full_unroll(enabled: bool = True):
    global FULL_UNROLL
    prev = FULL_UNROLL
    FULL_UNROLL = enabled
    try:
        yield
    finally:
        FULL_UNROLL = prev


def scan(body, init, xs, length: int | None = None, unroll: int = 1):
    """lax.scan honoring FULL_UNROLL (cost-accounting mode);
    ``unroll`` is the iterations per trip of the rolled form."""
    if length is None:
        length = jax.tree.leaves(xs)[0].shape[0]
    return jax.lax.scan(body, init, xs, length=length, unroll=length if FULL_UNROLL else unroll)
