"""Writing one step's K/V rows into the paged pool, in place.

The pool is ``[pages, Hkv, page_size, D]`` and a step's rows go to
``(token_page, :, token_slot, :)``.  The plain scatter
``pool.at[token_page, :, token_slot, :].set(rows)`` indexes two axes that are
not adjacent, so on the chip the compiler re-lays the WHOLE pool out around it
(``{3,2,1,0}`` to ``{3,1,2,0}`` and back): two pool-sized copies a pool a layer,
whatever the step holds (PERF.md section 6, PR 29).  Here the write goes
through the pages the step touches instead: gather those pages (a few MB),
scatter the rows into that staging block (the awkward two-axis scatter, now on
megabytes), and put the pages back with a scatter on the leading axis alone,
whose windows are whole ``[Hkv, page_size, D]`` pages in the pool's own layout.
The donated pool is updated in place and the cost follows the rows written.

It moves bytes: the rows arrive in the pool's dtype (int8 pages are quantized
by the caller), and the pool afterwards holds, bit for bit, what the plain
scatter leaves.

A pool without a head axis (``[pages, page_size, W]``: a latent row, an
indexer's key, one row a token for all heads) goes the same way through
:func:`append_pages`: the same touched pages, whole ``[page_size, W]`` pages
put back.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def page_cap(rung: int, page_size: int, max_batch: int) -> int:
    """The most pages one step of ``rung`` rows can touch: a request's ``n``
    consecutive rows lie on at most ``(n - 1) // page_size + 2`` pages, there
    are at most ``max_batch`` requests and one run of pad rows (scratch page
    0), and never more pages than rows."""
    return min(rung, rung // page_size + 2 * max_batch + 1)


# an unused entry of the page list: past any pool, so its read is clipped and
# its write dropped
_NO_PAGE = jnp.iinfo(jnp.int32).max


class TouchedPages(NamedTuple):
    """``page_ids[cap]``: the pages a step writes, in row order, unused
    entries out of range; ``rank[T]``: each row's entry in it."""

    page_ids: jax.Array
    rank: jax.Array


def touched_pages(token_page, *, cap: int) -> TouchedPages:
    """The run-length of ``token_page``.  Rows of one page must be ADJACENT:
    the engine's flat axis holds each scheduled request's rows once and
    contiguously, no two requests write one page, and the pad rows (page 0)
    trail.  A page met in two separate runs would be put back twice, each
    copy without the other's rows.  Runs past ``cap`` are dropped, so the
    caller's ``cap`` must hold for its schedules (:func:`page_cap`)."""
    prev = jnp.concatenate(
        [jnp.full((1,), -1, token_page.dtype), token_page[:-1]])
    rank = jnp.cumsum((token_page != prev).astype(jnp.int32)) - 1
    page_ids = jnp.full((cap,), _NO_PAGE, jnp.int32).at[rank].set(
        token_page, mode="drop")
    return TouchedPages(page_ids, rank)


def append_pages(pool, rows, touched: TouchedPages, token_slot):
    """One pool's rows through the touched pages (module doc): ``pool``
    ``[pages, Hkv, page_size, D]`` with ``rows`` ``[T, Hkv, D]``, or
    ``[pages, page_size, W]`` with ``rows`` ``[T, W]``."""
    page_ids, rank = touched
    staged = jnp.take(pool, page_ids, axis=0, mode="clip")
    if pool.ndim == 4:
        staged = staged.at[rank, :, token_slot, :].set(rows, mode="drop")
    else:
        staged = staged.at[rank, token_slot, :].set(rows, mode="drop")
    return pool.at[page_ids].set(staged, mode="drop")


def append_rows(kc, vc, k_w, v_w, touched: TouchedPages, token_slot):
    """``kc.at[token_page, :, token_slot, :].set(k_w)`` and the same for V,
    through the touched pages (module doc).  ``k_w`` / ``v_w`` are
    ``[T, Hkv, D]`` in the pools' dtypes (or ``[T, W]`` for pools without a
    head axis)."""
    return (append_pages(kc, k_w, touched, token_slot),
            append_pages(vc, v_w, touched, token_slot))
