"""Reference oracle for the fork slot map (``engine.plan_fork_map``).

The straightforward source-major formulation: rank the requesters with a
stable argsort and its inverse, hand the r-th admitted one the r-th free
lane of its block, then invert that source→slot map into the
destination-major ``(src2, is_copy)`` form ``expand_forks`` gathers with.
Boring on purpose, and never deployed: ``plan_fork_map`` is diffed
against it (tests/test_fork_map.py) as the interpreter is against
``pyevm_ref``.

``dense=True`` inverts with a ``[G, B, B]`` one-hot compare where the
default scatters. Same answers; it is the O(P²) term of the lane-scaling
cliff, kept so tests/test_scaling.py can show that
``tools/scaling_report.py``'s cost counter still sees such a term.
"""

import jax.numpy as jnp

I32 = jnp.int32


def fork_map_ref(req2, free2, key, fork_policy="fifo", dense=False):
    """``(src2 [G, B], is_copy [P], slot [P])`` as ``plan_fork_map``
    returns them, except that an admitted source's ``slot`` is the
    global lane it forks into (``plan_fork_map`` only says ``!= P``)."""
    G, B = req2.shape
    P = G * B
    loc = jnp.broadcast_to(jnp.arange(B, dtype=I32)[None, :], (G, B))
    gidx = jnp.broadcast_to(jnp.arange(G, dtype=I32)[:, None], (G, B))
    n_free = jnp.sum(free2.astype(I32), axis=1, keepdims=True)
    if fork_policy == "fifo":
        rank = jnp.cumsum(req2.astype(I32), axis=1) - req2.astype(I32)
    else:
        key = jnp.where(req2, key, 1 << 20)  # non-requesters sort last
        order = jnp.argsort(key, axis=1, stable=True).astype(I32)
        rank = jnp.argsort(order, axis=1).astype(I32)  # its inverse
    # beam admits at most B//4 forks a block a superstep
    n_adm = (jnp.minimum(n_free, max(1, B // 4))
             if fork_policy == "beam" else n_free)
    free_ids = jnp.sort(jnp.where(free2, loc, B), axis=1)
    slot2 = jnp.where(
        req2 & (rank < n_adm),
        jnp.take_along_axis(free_ids, jnp.clip(rank, 0, B - 1), axis=1),
        B,
    )  # local free-lane index per forking lane; B = not admitted
    if dense:
        # destination j is a copy iff some source i chose it (distinct
        # ranks choose distinct free lanes), and its source is that i
        eq = slot2[:, :, None] == jnp.arange(B, dtype=I32)[None, None, :]
        is_copy2 = jnp.any(eq, axis=1)
        src2 = jnp.where(is_copy2, jnp.argmax(eq, axis=1).astype(I32), loc)
    else:
        src2 = loc.at[gidx, slot2].set(loc, mode="drop")
        is_copy2 = jnp.zeros((G, B), dtype=bool).at[gidx, slot2].set(
            True, mode="drop")
    slot = jnp.where(slot2 < B, slot2 + gidx * B, P).reshape(P)
    return src2, is_copy2.reshape(P), slot
