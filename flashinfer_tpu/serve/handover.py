"""Handing a step's host inputs to the device.

A transfer costs the host a fraction of a millisecond whatever its size
(43 leaves of a step, a megabyte together, took 8.5 ms on a v5e host), so
a step's inputs cross as ONE int32 buffer, any leaf of another dtype
beside it, and one small program, outside the step's, cuts the buffer into
the leaves the step takes: the same leaves, dtypes and shapes as a transfer
a leaf, so the same step program.  It ends when that program's outputs are."""

from __future__ import annotations

import functools
import math

import jax
import numpy as np


def to_device(host, finish=None, packed=True, span=None):
    """``host``: a pytree of numpy arrays.  Returns (the tree on the
    device, buffers sent, KiB sent).  ``finish``, a module-level function
    of the tree, is applied inside the cutting program.  ``packed=False``
    sends a buffer a leaf and runs no program (a step whose inputs have a
    shape its rung has not compiled a cut for).  ``span(name)`` opens a
    child span of the caller's (``pack``, ``put``, ``cut``), None nothing."""
    from flashinfer_tpu.obs import child_span

    with child_span(span, "pack"):
        leaves, treedef = jax.tree_util.tree_flatten(host)
        inbuf = tuple(packed and x.dtype == np.int32 for x in leaves)
        sent = [x for x, p in zip(leaves, inbuf) if not p]
        if packed:
            sent.insert(0, np.concatenate(
                [x.reshape(-1) for x, p in zip(leaves, inbuf) if p]))
    with child_span(span, "put"):
        on_device = jax.device_put(sent)
    if packed:
        with child_span(span, "cut"):
            tree = cut(on_device[0], tuple(on_device[1:]), finish=finish,
                       layout=(treedef, tuple((x.shape, p) for x, p
                                              in zip(leaves, inbuf))))
            # a launch whose inputs were still being produced took 12 ms
            # longer (PERF.md section 6, PR 31); outputs are ready together
            jax.block_until_ready(jax.tree_util.tree_leaves(tree)[0])
    else:
        tree = treedef.unflatten(on_device)
        tree = tree if finish is None else finish(tree)
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
