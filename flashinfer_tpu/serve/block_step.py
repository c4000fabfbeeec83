"""The engine's step for a model that brings its own block.

``ServingEngine`` serves two kinds of model.  One without ``engine_layer``
is the Llama-style dense block, whose step is written inline in
``engine.py`` ``_build_step`` and is NOT touched by this file.  One with
``engine_layer`` gets the step built here, chosen once in
``ServingEngine.__init__`` and imported only then: the engine still owns the
tokens, the schedule, the caches, the K/V write and the paged attention, the
head's row gather and the sampling; the model supplies the embedding, each
layer and the head.

Why a second body, and not a branch in the first: on the chip a rung's first
step took 0.55 s longer, on a warm compile cache and with a byte-identical
lowered program, once the Llama ``_body`` held this branch, and 1.6 s longer
with the Llama layer in a function of its own (PERF.md section 6, PR 28;
mechanism not found).  So the reference tier's windows and the sampling
epilogue below are copies of ``_build_step``'s (a fix to one belongs in the
other); the K/V write is the one both call (``serve/kv_write.py``), here
without int8 pages, which no such model runs.

Such a model object supplies, beside the sizes every model has:

- ``layer_kinds()``: ``"attention"``, ``"window"``, ``"recurrent"`` or
  ``"latent"`` per layer; an attention layer gets K/V pages; a window layer
  (its queries see the last ``attention_window`` positions, the query's own
  among them) gets K/V pages in a SECOND pool sized by the window
  (``serve/window_pool.py``): a request holds there only the pages from
  position ``kv_len - attention_window`` on, the pages behind are released
  after each step and their rows are gone by design; a recurrent one the
  arrays of
  ``new_recurrent_state(slots)`` (``[slots, ...]``, one slot a batch slot),
  a latent one the arrays of ``new_latent_pages(num_pages, page_size)``
  (``[pages, page_size, W]``, no head axis: one row a token for all heads,
  as many arrays as the layer caches things a token, all on the request's
  one page list), each as ``cache`` below and donated through the step;
  with recurrent or latent layers also ``scan_chunk``, the rows of one work
  unit of ``serve/recurrent.py``'s plan.  A latent layer attends by itself
  (``ctx.page_table``, ``ctx.append``): the engine plans and launches no
  GQA work unit for a model without an attention layer;
- ``attention_rope`` (False: no rotation; or ``{kind: bool}``, rotation
  being a property of the layer kind where window layers rotate and full
  layers do not) and ``attention_scale``;
- ``engine_embed(params, tokens)``, ``engine_layer(li, x, layer, cache,
  ctx) -> (x, cache)``, ``engine_head(params, x_last) -> float32 logits``;
- ``engine_facts(aux, tokens)``: the step's facts from what its layers put
  into ``ctx.aux`` (small arrays, fetched with the tokens).
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from flashinfer_tpu.serve.kv_write import (append_pages, append_rows,
                                           page_cap, touched_pages)

_NEG_INF = -1e30


@dataclasses.dataclass
class StepContext:
    """What the step hands the model's layer, once per trace.

    ``attend(q, k, v, cache, kind="attention") -> (out, cache)`` writes the
    K/V rows into the pages and attends over them (the engine's tier, the
    model's rotation and scale); ``kind="window"`` picks the window layers'
    pool, page table, plan and rotation, and the window rule; ``plan`` is
    ``serve/recurrent.py``'s plan of the step:
    ``n_tokens`` and, with recurrent or latent layers, each slot's segment
    and the work units of the longer segments; ``aux`` takes what the layers
    want fetched.  For a latent layer: ``page_table`` ``[max_batch, pages a
    request]`` (row = batch slot), ``page_size``, and ``append(pool, rows)
    -> pool``, which writes the step's rows ``[T, W]`` into a paged array
    ``[pages, page_size, W]`` through ``serve/kv_write.py``."""

    positions: jax.Array
    tok_req: jax.Array
    attend: object
    plan: dict
    aux: dict
    page_table: object = None
    page_size: int = 0
    append: object = None


def _sample(logits, sampling, base_key, sample_seeds):
    """The engine's sampling epilogue (``_build_step``'s): per-lane
    deterministic, the key from (request arrival id, token index) alone."""
    with jax.named_scope("sample"):
        t = jnp.maximum(
            jnp.asarray(sampling.temperature, jnp.float32), 1e-6)
        probs = jax.nn.softmax((logits / t).astype(jnp.float32), -1)
        if sampling.top_k:
            from flashinfer_tpu import sampling as S

            probs = S.top_k_renorm_probs(probs, sampling.top_k)
        if sampling.top_p < 1.0:
            from flashinfer_tpu import sampling as S

            probs = S.top_p_renorm_probs(probs, sampling.top_p)
        keys = jax.vmap(
            lambda s: jax.random.fold_in(base_key, s))(sample_seeds)
        tokens = jax.vmap(
            lambda p, kk: jax.random.categorical(
                kk, jnp.log(jnp.maximum(p, 1e-30))))(probs, keys)
    return tokens.astype(jnp.int32)


def build(eng):
    """The jitted, donated step of ``eng`` for ``eng.cfg``'s own block:
    ``_build_step._body``'s arguments and ``mplan`` -> ``(tokens, caches,
    aux)``."""
    cfg, mcfg = eng.config, eng.cfg
    if eng._int8_kv:
        raise NotImplementedError(
            "int8 K/V pages are not supported for a model that brings its "
            "own block (serve/block_step.py)")
    K = eng._ppr * cfg.page_size   # per-request KV window rows
    sm_scale = float(getattr(mcfg, "attention_scale", None)
                     or 1.0 / float(mcfg.head_dim) ** 0.5)
    rope = getattr(mcfg, "attention_rope", True)
    use_rope = rope if isinstance(rope, dict) else \
        dict.fromkeys(("attention", "window"), bool(rope))
    # a model with window layers: their scopes are told apart
    # (``attn.core`` > ``attn.window`` / ``attn.full``), and their side of
    # the schedule rides the model's plan (``mplan["window"]``)
    has_window = eng._window
    window_left = mcfg.attention_window - 1 if has_window else -1
    Kw = eng._wpages.cap * cfg.page_size if has_window else 0
    wgeom = eng._wgeom
    full_scope = (lambda: jax.named_scope("attn.full")) if has_window \
        else contextlib.nullcontext
    sampling = cfg.sampling
    base_key = jax.random.PRNGKey(cfg.seed)
    kernel_backend, geom = eng._kernel_backend, eng._geom
    return_logits = cfg.return_logits
    Hq, Hkv, D = mcfg.num_qo_heads, mcfg.num_kv_heads, mcfg.head_dim

    def _window(c, table, rows=K):
        # [pages, Hkv, PS, D] -> [rows, K, Hkv, D]: the row offset of KV
        # position j is always j (for a window layer: j less the position of
        # the first row its request still holds)
        w = c[table]
        return jnp.swapaxes(w, 2, 3).reshape(w.shape[0], rows, Hkv, D)

    def _attend(q, kw, vw, lo, hi):
        # the reference tier's per-token windowed attention: valid rows j in
        # [lo, hi] per token, masked lanes contribute exact zeros
        T = q.shape[0]
        qg = q.reshape(T, Hkv, Hq // Hkv, D).astype(jnp.float32)
        s = jnp.einsum("tngd,tknd->tngk", qg,
                       kw.astype(jnp.float32)) * sm_scale
        j = jnp.arange(kw.shape[1])
        vm = ((j[None, :] >= lo[:, None])
              & (j[None, :] <= hi[:, None]))[:, None, None, :]
        s = jnp.where(vm, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(vm, jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1)
        out = jnp.einsum(
            "tngk,tknd->tngd", p / jnp.where(l > 0, l, 1.0)[..., None],
            vw.astype(jnp.float32))
        lse = jnp.where(l > 0, m[..., 0] + jnp.log(l), _NEG_INF)
        return out.reshape(T, Hq, D), lse.reshape(T, Hq)

    def _body(params, flat_tokens, positions, tok_req, token_page,
              token_slot, page_table, grp_pages, tok_grp, split,
              last_rows, sample_seeds, kplans, caches, mplan):
        eng._traces += 1
        with jax.named_scope("attn.kv_write"):
            touched = touched_pages(
                token_page, cap=page_cap(token_page.shape[0], cfg.page_size,
                                         cfg.max_batch))

        if has_window:
            win = mplan["window"]
            with jax.named_scope("attn.kv_write"):
                wtouched = touched_pages(
                    win["token_page"],
                    cap=page_cap(token_page.shape[0], cfg.page_size,
                                 cfg.max_batch))

        def _rotated(q, k, kind):
            if not use_rope[kind]:
                return q, k
            from flashinfer_tpu.rope import apply_rope_pos_ids

            return apply_rope_pos_ids(q, k, positions,
                                      rope_theta=mcfg.rope_theta)

        def _attend_window(q, k, v, cache):
            """A window layer: its own pool, page table and plan; positions
            counted from the first row the request still holds."""
            kc, vc = cache
            with jax.named_scope("attn.kv_write"):
                q, k = _rotated(q, k, "window")
                kc, vc = append_rows(kc, vc, k.astype(kc.dtype),
                                     v.astype(vc.dtype), wtouched, token_slot)
            with jax.named_scope("attn.core"), jax.named_scope("attn.window"):
                if kernel_backend:
                    from flashinfer_tpu.serve.engine_kernels import (
                        engine_window_attention)

                    # graft-lint: ok env read once a trace, pinned per rung
                    o = engine_window_attention(
                        q, kc, vc, win["plans"], geom=wgeom,
                        sm_scale=sm_scale, window_left=window_left)
                else:
                    hi = positions - win["first_tok"][tok_req]
                    o, _ = _attend(
                        q, _window(kc, win["page_table"], Kw)[tok_req],
                        _window(vc, win["page_table"], Kw)[tok_req],
                        jnp.maximum(hi - window_left, 0), hi)
            return o.astype(mcfg.dtype), (kc, vc)

        def _attend_pages(q, k, v, cache, kind="attention"):
            if kind == "window":
                # graft-lint: ok env read once a trace, pinned per rung
                return _attend_window(q, k, v, cache)
            kc, vc = cache
            with jax.named_scope("attn.kv_write"):
                q, k = _rotated(q, k, "attention")
                # pad lanes go to the scratch page (pool page 0)
                kc, vc = append_rows(kc, vc, k.astype(kc.dtype),
                                     v.astype(vc.dtype), touched, token_slot)
            with jax.named_scope("attn.core"), full_scope():
                if kernel_backend:
                    from flashinfer_tpu.serve.engine_kernels import (
                        engine_kernel_attention)

                    # graft-lint: ok env read once a trace, pinned per rung
                    o = engine_kernel_attention(
                        q, kc, vc, kplans, geom=geom, sm_scale=sm_scale)
                else:
                    from flashinfer_tpu.cascade import (
                        compose_cascade_levels)

                    # level 1: the request's own window, rows [split, pos];
                    # level 0: the shared prefix run, rows
                    # [0, min(split, pos + 1))
                    o1, lse1 = _attend(
                        q, _window(kc, page_table)[tok_req],
                        _window(vc, page_table)[tok_req], split, positions)
                    o0, lse0 = _attend(
                        q, _window(kc, grp_pages)[tok_grp],
                        _window(vc, grp_pages)[tok_grp],
                        jnp.zeros_like(split),
                        jnp.minimum(split - 1, positions))
                    o, _ = compose_cascade_levels([(o0, lse0), (o1, lse1)])
            return o.astype(mcfg.dtype), (kc, vc)

        def _append(pool, rows):
            return append_pages(pool, rows.astype(pool.dtype), touched,
                                token_slot)

        aux = {}
        ctx = StepContext(positions=positions, tok_req=tok_req,
                          attend=_attend_pages, plan=mplan, aux=aux,
                          page_table=page_table, page_size=cfg.page_size,
                          append=_append)
        with jax.named_scope("embed"):
            x = mcfg.engine_embed(params, flat_tokens)
        new_caches = []
        for li, layer in enumerate(params["layers"]):
            x, cache = mcfg.engine_layer(li, x, layer, caches[li], ctx)
            new_caches.append(cache)
        with jax.named_scope("head"):
            logits = mcfg.engine_head(params, x[last_rows])
        if return_logits:
            aux["logits"] = logits
        return (_sample(logits, sampling, base_key, sample_seeds),
                new_caches, aux)

    return jax.jit(_body, donate_argnums=(13,) if cfg.donate else ())
