"""Handing a step's host inputs to the device.

A transfer costs the host a fraction of a millisecond whatever its size
(43 leaves of a step, a megabyte together, took 8.5 ms on a v5e host), so
a step's inputs cross as ONE int32 buffer, with any leaf of another dtype
beside it, and one small program, outside the step's, cuts the buffer into
the leaves the step takes: the same leaves, dtypes and shapes as a
transfer a leaf, so the same step program.  The handover ends when that
program's outputs are ready, so the step is never launched on inputs
that are still being produced."""

from __future__ import annotations

import functools
import math

import jax
import numpy as np


def to_device(host, finish=None, packed=True):
    """``host``: a pytree of numpy arrays and scalars.  Returns (the tree
    on the device, buffers sent, KiB sent).  ``finish``, a module-level
    function of the tree, is applied inside the cutting program.
    ``packed=False`` sends a buffer a leaf and runs no program (a step
    whose inputs have a shape its rung has not compiled a cut for)."""
    leaves, treedef = jax.tree_util.tree_flatten(host)
    if packed:
        inbuf = tuple(x.dtype == np.int32 for x in leaves)
        sent = (np.concatenate([x.reshape(-1)
                                for x, p in zip(leaves, inbuf) if p]),
                *(x for x, p in zip(leaves, inbuf) if not p))
        buf, *rest = jax.device_put(sent)
        tree = cut(buf, tuple(rest), finish=finish, layout=(
            treedef, tuple((x.shape, p) for x, p in zip(leaves, inbuf))))
        # ready before the step is dispatched: a launch whose inputs the
        # cutting program was still producing took the v5e's runtime 12 ms
        # a step longer (granite cell, PERF.md section 6, PR 31); the
        # program takes 0.2 ms and the step could not start before it
        # anyway (the wait still costs the chat cell ~2%).  One leaf is
        # waited for: a program's outputs are ready together
        jax.block_until_ready(jax.tree_util.tree_leaves(tree)[0])
    else:
        sent = leaves
        tree = treedef.unflatten(jax.device_put(leaves))
        if finish is not None:
            tree = finish(tree)
    return tree, len(sent), sum(x.nbytes for x in sent) / 1024


@functools.partial(jax.jit, static_argnames=("layout", "finish"))
def cut(buf, rest, *, layout, finish):
    treedef, specs = layout
    rest, leaves, at = iter(rest), [], 0
    for shape, inbuf in specs:
        if inbuf:
            n = math.prod(shape)
            leaves.append(buf[at:at + n].reshape(shape))
            at += n
        else:
            leaves.append(next(rest))
    tree = treedef.unflatten(leaves)
    return tree if finish is None else finish(tree)
