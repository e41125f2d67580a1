"""Device-completion barrier for timing and stage attribution.

JAX dispatch is asynchronous: a jitted call returns once the work is
enqueued, so a host clock stopped right after it measures the enqueue.
:func:`host_sync` waits for completion by reading back ONE element per
array (a jitted slice, so the transfer is 4 bytes, not the array): the
value cannot reach the host before the program that produced it has
finished. On an attached TPU this costs one small device→host copy and
waits for the same thing ``block_until_ready`` does; keeping the
readback form means a timing never depends on how a runtime resolves
buffer futures.
"""

from __future__ import annotations

import numpy as np

__all__ = ["host_sync"]


def host_sync(tree) -> None:
    """Block until every array in ``tree`` has actually finished computing.

    Accepts a single array or any pytree of arrays; non-array leaves are
    ignored. Safe on numpy inputs (no-op reads).
    """
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "ravel"):
            np.asarray(leaf.ravel()[:1])
