"""The plain reference of the ``minicpm_sala`` block (MiniCPM-SALA: lightning
linear-attention layers beside InfLLM-v2 block-sparse GQA layers, a SwiGLU
MLP in every layer, muP-scaled ends and residuals), in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  It is
given token ids and the benchmark's own seeded weights one layer at a time
(``minicpm_sala.layer_params``), and returns what the block defines: the K, V
and compressed-key rows of a sparse layer, the matrix state of a lightning
layer after a stated number of tokens, and the logits.

The equations (``n`` an RMSNorm with its own weight, eps 1e-6; no biases; ``r
= scale_depth / sqrt(L_pub)`` with ``L_pub`` the PUBLISHED depth):

* ends: ``h = scale_emb E[tok]``; ``logits = W_head (n(h_L) / (hidden /
  dim_model_base))``;
* layer: ``h <- h + r mixer(n(h))``; ``h <- h + r W_down(silu(W_gate u) * W_up
  u)``, ``u = n(h)``;
* lightning (``H`` heads of ``d``): ``[q | k | v | g] = x W_qkvg``; per-head
  RMSNorm of ``q`` and of ``k`` (a learned ``[d]`` weight each); both rotated
  (rotate-half pairs ``(i, i + d/2)``, frequencies ``theta^(-2i/d)``) at the
  token's position; ``S_t = lambda_h S_{t-1} + k_t^T v_t`` (``[d, d]``,
  ``S_{-1} = 0``); ``o_t = (q_t / sqrt(d)) S_t``; ``o = n_hidden(concat_h
  o_t) * sigmoid(g)``; out ``o W_o``.  ``lambda_h = exp(-s_h f_l)``, ``s_h =
  2^(-8 (h + 1) / H)``, ``f_l = 1 - l / (L_pub - 1) + 1e-5``, ``l`` the
  layer's PUBLISHED index.  The recurrence is computed in chunks of 256
  tokens AS ALGEBRA (``o = (q a) S + ((q k^T) * D) v``, ``D_ij = a_i / a_j``
  for ``j <= i`` with ``a`` the running decay, every exponent at most 0),
  which is the recurrence to float32 rounding (a test holds it to the
  token-by-token form); tokens past the sequence's stated end neither decay
  the state nor add to it, so the carried state is the state at that end;
* sparse (``Hq`` query heads, ``Hkv`` K/V heads of ``d``): ``[q | k | v | g] =
  x W_qkvg``; per-head RMSNorm of ``q`` and ``k``; NO rotation.  Compressed
  key ``kc_c = mean(k_j, stride c <= j < stride c + kernel_size)``, defined
  once position ``stride c + kernel_size - 1`` exists.  For the query at
  position ``t`` and K/V head ``g``: over the ``c`` with ``stride c +
  kernel_size - 1 <= t``, ``p_{h,c} = softmax_c(q_{t,h} . kc_c / sqrt(d))`` a
  query head, ``s_c`` its sum over the group's heads; block score ``B_b =
  max(s_c)`` over the valid ``c`` in ``{per b - 1, ..., per b + per - 1}``
  (``per = block_size / stride``) for blocks ``b <= floor(t / block_size)``;
  ``B_b = +inf`` for ``b < init_blocks`` and for the ``window_size /
  block_size`` newest blocks; the selection is the ``topk`` highest ``B_b``,
  ties to the lower ``b``, all blocks while there are at most ``topk``.
  Attention: softmax over the positions ``j <= t`` inside the selected
  blocks, scale ``1 / sqrt(d)``; ``o * sigmoid(g)``; out ``o W_o``.  Computed
  ``QUERY_BLOCK`` query rows at a time: the full compressed-score matrix of
  those rows, their block mask, and each row's selected blocks gathered by
  the mask (with the selection off: a masked softmax over every position),
  so that a 64k-token sequence fits; a layer's projections and MLP
  ``ROW_BLOCK`` rows at a time.

A layer at a time over every sequence (a layer's weights are made once a
pass), one sequence at a time inside it, no cache, no batching.  ``precision="int8"`` is for
the control only: every linear has its weight rounded to 8-bit codes per
output channel and its input per row (not the states, scores and softmaxes,
which the configuration states in float32).  ``precision="float32-dense"`` is
the other control (``--control``): the sparse layers attend every causal
position (selection off).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LINEAR = ("qkvg", "o_proj", "gate_up", "down")
ROW_BLOCK = 2048      # rows of a layer computed at once (a sequence is a
#                       multiple of it, or shorter): the one compiled shape
QUERY_BLOCK = 128     # query rows of a sparse layer attended at once
DENSE_QUERY_BLOCK = 64   # the same with the selection off (every position)
SCAN_BLOCK = 256      # tokens of one step of the lightning recurrence


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def int8_round(w, axis):
    """Symmetric 8-bit codes, one scale per slice along ``axis``, back in
    float32 (``-2``: per output channel of an ``[in, out]`` weight; ``-1``:
    per row of an activation)."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _mm(x, w, precision):
    return (int8_round(x, -1) if precision == "int8" else x) @ w


def _f32(layer, precision):
    out = {k: v.astype(jnp.float32) for k, v in layer.items()}
    if precision == "int8":
        for name in LINEAR:
            out[name] = int8_round(out[name], -2)
    elif precision not in ("float32", "float32-dense"):
        raise ValueError(f"unknown precision {precision!r}")
    return out


def _mlp(x, w, eps, res, precision):
    u = rmsnorm(x, w["post_norm"], eps)
    gate, up = jnp.split(_mm(u, w["gate_up"], precision), 2, -1)
    return x + res * _mm(jax.nn.silu(gate) * up, w["down"], precision)


def rotate_half(x, pos, theta):
    """``x`` ``[T, H, d]`` rotated at ``pos``: pairs ``(i, i + d/2)``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def log_decay(heads, published_index, published_layers):
    """``log lambda_h`` ``[heads]`` of the layer at ``published_index``."""
    slope = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                    / heads)
    return -slope * (1.0 - published_index / (published_layers - 1) + 1e-5)


def linear_attention(q, k, v, live, log_lam, s):
    """``o_t = q_t S_t``, ``S_t = lambda S_{t-1} + k_t^T v_t`` over ``[T, H,
    d]`` inputs from the state ``s``, in steps of :data:`SCAN_BLOCK` tokens
    (or the largest power of two that divides ``T``); tokens that are not ``live`` leave the state alone.  Returns
    ``(o [T, H, d], the state after them [H, d, d])``."""
    t = q.shape[0]
    c = math.gcd(SCAN_BLOCK, t)
    k = jnp.where(live[:, None, None], k, 0.0)
    g = jnp.where(live[:, None], log_lam[None], 0.0)        # [T, H]
    blocks = lambda a: a.reshape(t // c, c, *a.shape[1:])
    tri = jnp.tril(jnp.ones((c, c), bool))

    def step(s, inp):
        qb, kb, vb, gb = inp
        a = jnp.cumsum(gb, 0)                               # [c, H], <= 0
        inter = jnp.einsum("ihd,hde->ihe", qb * jnp.exp(a)[..., None], s)
        decay = jnp.exp(jnp.where(tri[:, :, None],
                                  a[:, None] - a[None, :], -jnp.inf))
        scores = jnp.einsum("ihd,jhd->ijh", qb, kb) * decay
        intra = jnp.einsum("ijh,jhe->ihe", scores, vb)
        s = jnp.exp(a[-1])[:, None, None] * s + jnp.einsum(
            "jhd,jhe->hde", kb * jnp.exp(a[-1][None] - a)[..., None], vb)
        return s, inter + intra

    s, o = jax.lax.scan(step, s, (blocks(q), blocks(k), blocks(v), blocks(g)))
    return o.reshape(q.shape), s


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "theta", "published_layers", "eps", "res",
    "precision"))
def lightning_rows(xb, r0, n, s, layer, published_index, *, heads, head_dim,
                   theta, published_layers, eps, res, precision="float32"):
    """One lightning layer over a block of one sequence's rows, ``xb`` ``[R,
    hidden]`` float32 at positions ``r0 ..``, from the state ``s`` after the
    rows before them; ``n``: how many leading tokens are the sequence.
    Returns ``(xb_out, the state after these rows [heads, d, d])``.  The
    shapes are a block's whatever the sequence's length, and ``r0``, ``n``
    and the layer's published index are traced: one compile serves every
    block of every sequence and layer."""
    with jax.default_matmul_precision("highest"):
        w = _f32(layer, precision)
        rb = xb.shape[0]
        pos = r0 + jnp.arange(rb)
        h = rmsnorm(xb, w["input_norm"], eps)
        q, k, v, g = jnp.split(_mm(h, w["qkvg"], precision), 4, -1)
        shape = (rb, heads, head_dim)
        q = rotate_half(rmsnorm(q.reshape(shape), w["q_norm"], eps), pos,
                        theta)
        k = rotate_half(rmsnorm(k.reshape(shape), w["k_norm"], eps), pos,
                        theta)
        o, s = linear_attention(
            q / head_dim ** 0.5, k, v.reshape(shape), pos < n,
            log_decay(heads, published_index, published_layers), s)
        o = rmsnorm(o.reshape(rb, heads * head_dim), w["o_norm"], eps) \
            * jax.nn.sigmoid(g)
        xb = xb + res * _mm(o, w["o_proj"], precision)
        return _mlp(xb, w, eps, res, precision), s


def block_selection(q, kc, pos, *, stride, kernel_size, block_size, topk,
                    init_blocks, local_blocks, blocks):
    """bool ``[Q, Hkv, blocks]``: the blocks each query row (``q`` ``[Q, Hq,
    d]`` at positions ``pos``) selects a K/V head, from the sequence's
    compressed keys ``kc`` ``[C, Hkv, d]``."""
    n_q, hq, d = q.shape
    n_c, hkv, _ = kc.shape
    per = block_size // stride
    s = jnp.einsum("qngd,cnd->qngc", q.reshape(n_q, hkv, hq // hkv, d), kc) \
        / d ** 0.5
    valid = (jnp.arange(n_c) * stride + kernel_size - 1)[None] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(valid[:, None, None], s, -jnp.inf), -1)
    p = jnp.where(valid[:, None, None], p, 0.0)     # a row with no valid c
    sc = jnp.where(valid[:, None], jnp.sum(p, 2), -jnp.inf)   # [Q, Hkv, C]
    b = jnp.arange(blocks)
    # block b is touched by c = per b - 1 .. per b + per - 1
    cs = b[:, None] * per + jnp.arange(-1, per)[None]          # [blocks, 5]
    inside = (cs >= 0) & (cs < n_c)
    score = jnp.max(jnp.where(inside, sc[..., jnp.clip(cs, 0, n_c - 1)],
                              -jnp.inf), -1)                   # [Q, Hkv, B]
    own = (pos // block_size)[:, None]
    forced = (b[None] < init_blocks) | (b[None] > own - local_blocks)
    score = jnp.where(forced[:, None], jnp.inf, score)
    score = jnp.where((b[None] <= own)[:, None], score, -jnp.inf)
    # the topk highest, ties to the lower block: a stable sort, descending
    order = jnp.argsort(-score, -1, stable=True)
    rank = jnp.argsort(order, -1)
    return (rank < topk) & (score > -jnp.inf)


def _split_qkvg(w, heads, kv_heads, d):
    return jnp.split(
        w["qkvg"],
        [heads * d, (heads + kv_heads) * d, (heads + 2 * kv_heads) * d], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "precision"))
def sparse_keys_values(xb, layer, *, heads, kv_heads, head_dim, eps,
                       precision="float32"):
    """The keys (normed) and values ``[R, Hkv, d]`` of a block of rows."""
    with jax.default_matmul_precision("highest"):
        w = _f32(layer, precision)
        _, w_k, w_v, _ = _split_qkvg(w, heads, kv_heads, head_dim)
        h = rmsnorm(xb, w["input_norm"], eps)
        k = _mm(h, w_k, precision).reshape(-1, kv_heads, head_dim)
        return (rmsnorm(k, w["k_norm"], eps),
                _mm(h, w_v, precision).reshape(-1, kv_heads, head_dim))


@functools.partial(jax.jit, static_argnames=("stride", "block_size"))
def compress_and_block(k, v, *, stride, block_size):
    """``k``, ``v`` ``[T, Hkv, d]`` -> (compressed keys ``[T / stride - 1,
    Hkv, d]``: the mean of two consecutive pages of keys; keys and values as
    a head's blocks ``[Hkv, T / block_size, block_size, d]``)."""
    t, kv_heads, d = k.shape
    pages = k.reshape(t // stride, stride, kv_heads, d)
    kc = jnp.mean(jnp.concatenate([pages[:-1], pages[1:]], 1), 1)
    blocks = lambda a: jnp.transpose(
        a.reshape(t // block_size, block_size, kv_heads, d), (2, 0, 1, 3))
    return kc, blocks(k), blocks(v)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "stride", "kernel_size", "block_size",
    "topk", "init_blocks", "local_blocks", "eps", "res", "precision"))
def sparse_rows(xb, r0, layer, kc, k_blocks, v_blocks, *, heads, kv_heads,
                head_dim, stride, kernel_size, block_size, topk, init_blocks,
                local_blocks, eps, res, precision="float32"):
    """One sparse layer over a block of one sequence's rows, ``xb`` ``[R,
    hidden]`` float32 at positions ``r0 ..``, against the sequence's
    compressed keys and its keys and values by block
    (:func:`compress_and_block`; rows past the sequence's end are never
    valid for a query before it).  Returns ``(xb_out, (query, position)
    pairs attended a K/V head)``."""
    with jax.default_matmul_precision("highest"):
        w = _f32(layer, precision)
        rb, d, g_sz = xb.shape[0], head_dim, heads // kv_heads
        blocks = k_blocks.shape[1]
        w_q, _, _, w_g = _split_qkvg(w, heads, kv_heads, d)
        dense = precision == "float32-dense"
        chosen = min(topk, blocks)

        def attend_all(inp):
            # the selection OFF: every causal position, a plain masked softmax
            qs, pos = inp            # [qb, heads, d], [qb]
            qb = qs.shape[0]
            ks, vs = (a.reshape(kv_heads, -1, d) for a in (k_blocks, v_blocks))
            ok = jnp.arange(ks.shape[1])[None] <= pos[:, None]     # [qb, T]
            s = jnp.einsum("qngd,njd->qngj",
                           qs.reshape(qb, kv_heads, g_sz, d), ks) / d ** 0.5
            p = jax.nn.softmax(jnp.where(ok[:, None, None], s, -jnp.inf), -1)
            o = jnp.einsum("qngj,njd->qngd", p, vs)
            return o.reshape(qb, heads * d), jnp.sum(ok)

        def attend(inp):
            qs, pos = inp            # [qb, heads, d], [qb]
            qb = qs.shape[0]
            sel = block_selection(
                qs, kc, pos, stride=stride, kernel_size=kernel_size,
                block_size=block_size, topk=topk, init_blocks=init_blocks,
                local_blocks=local_blocks, blocks=blocks)
            # each row's selected blocks, ascending, and which are real
            ids = jnp.argsort(~sel, -1, stable=True)[..., :chosen]
            real = jnp.take_along_axis(sel, ids, -1)        # [qb, Hkv, chosen]
            at = ids[..., None] * block_size + jnp.arange(block_size)
            ok = (real[..., None] & (at <= pos[:, None, None, None])).reshape(
                qb, kv_heads, -1)
            ids = jnp.transpose(ids, (1, 0, 2))             # [Hkv, qb, chosen]
            ks, vs = (jax.vmap(lambda a, i: a[i])(a, ids).reshape(
                kv_heads, qb, -1, d) for a in (k_blocks, v_blocks))
            s = jnp.einsum("qngd,nqjd->qngj",
                           qs.reshape(qb, kv_heads, g_sz, d), ks) / d ** 0.5
            p = jax.nn.softmax(jnp.where(ok[:, :, None], s, -jnp.inf), -1)
            o = jnp.einsum("qngj,nqjd->qngd", p, vs)
            return o.reshape(qb, heads * d), jnp.sum(ok[:, 0])

        h = rmsnorm(xb, w["input_norm"], eps)
        q = rmsnorm(_mm(h, w_q, precision).reshape(rb, heads, d),
                    w["q_norm"], eps)
        qb = math.gcd(DENSE_QUERY_BLOCK if dense else QUERY_BLOCK, rb)
        o, pairs = jax.lax.map(
            attend_all if dense else attend,
            (q.reshape(rb // qb, qb, heads, d),
                     (r0 + jnp.arange(rb)).reshape(rb // qb, qb)))
        o = o.reshape(rb, heads * d) * jax.nn.sigmoid(_mm(h, w_g, precision))
        xb = xb + res * _mm(o, w["o_proj"], precision)
        return _mlp(xb, w, eps, res, precision), jnp.sum(pairs)


@functools.partial(jax.jit, static_argnames=("eps", "shrink", "precision"))
def head_forward(x, final_norm, lm_head, *, eps, shrink, precision="float32"):
    with jax.default_matmul_precision("highest"):
        w = lm_head.astype(jnp.float32)
        if precision == "int8":
            w = int8_round(w, -2)
        return _mm(rmsnorm(x, final_norm.astype(jnp.float32), eps) / shrink,
                   w, precision)


def _bucket(n, floor=64):
    b = floor
    while b < n:
        b *= 2
    return b


def forward(seqs, lengths, m, ends, layer_of, *, mixers, published,
            rows_cap=None, on_rows=None, precision="float32",
            logit_rows=None, on_pairs=None):
    """Run the layers ``mixers`` (``"minicpm4"`` | ``"lightning-attn"``,
    whose published indices are ``published``) over each token sequence of
    ``seqs`` (each a multiple of a block long, and of :data:`ROW_BLOCK`
    where longer); ``lengths[s]`` is how many of its tokens are real (a
    lightning layer's state and the compressed keys are taken there).

    ``m`` is the model dict (published key names), ``ends`` the embedding,
    head and final norm, ``layer_of(i)`` layer i's weights, made on demand
    (once a pass: every sequence goes through layer i before layer i + 1 is
    made, so one layer exists at a time and the sequences' rows are what is
    held, float32).  A sequence goes through a layer ``ROW_BLOCK`` rows at a
    time, and a sparse layer's keys and values are padded to ``rows_cap``
    rows (default: the longest sequence), so every compiled shape is a
    block's or the cap's: nothing compiles a sequence length.  ``on_rows(s,
    i, rows)`` is handed ``{"k", "v": [T, Hkv x d], "kc": [T, Hkv x d]}`` of
    a sparse layer (``kc`` row ``c`` is the compressed key ``c``, zero where
    it is not defined within ``lengths[s]`` tokens) or ``{"state": [1, H x d
    x d]}`` of a lightning layer.  ``on_pairs(s, i, n)``: the (query,
    position) pairs sequence s attended in sparse layer i.  Returns
    ``logits[s]``, float32 ``[len(logit_rows[s]), vocab]``."""
    sp = m["sparse_config"]
    eps = float(m["rms_norm_eps"])
    res = float(m["scale_depth"]) / float(
        m["num_hidden_layers_published"]) ** 0.5
    embed = ends["embed"]
    shrink = float(m["hidden_size"]) / float(m["dim_model_base"])
    cap = max([rows_cap or 0] + [len(s) for s in seqs])
    kv_heads, d = m["num_key_value_heads"], m["head_dim"]
    sparse_kw = dict(heads=m["num_attention_heads"], kv_heads=kv_heads,
                     head_dim=d, eps=eps, precision=precision)
    # cut on the host: a slice of a device array compiles a program a start
    starts = [range(0, len(seq), min(ROW_BLOCK, len(seq))) for seq in seqs]
    acts = [[float(m["scale_emb"]) * embed[jnp.asarray(
        seq[r0:r0 + at.step], jnp.int32)].astype(jnp.float32) for r0 in at]
        for seq, at in zip(seqs, starts)]
    for li, (mixer, pub) in enumerate(zip(mixers, published)):
        layer = layer_of(li)
        for si, xs in enumerate(acts):
            t = len(seqs[si])
            if mixer == "minicpm4":
                kv = [sparse_keys_values(xb, layer, **sparse_kw)
                      for xb in xs]
                k, v = (jnp.concatenate([b[i] for b in kv]) for i in (0, 1))
                grow = ((0, cap - t), (0, 0), (0, 0))
                kc, k_blocks, v_blocks = compress_and_block(
                    jnp.pad(k, grow), jnp.pad(v, grow),
                    stride=sp["kernel_stride"], block_size=sp["block_size"])
                out = [sparse_rows(
                    xb, jnp.int32(r0), layer, kc, k_blocks, v_blocks,
                    stride=sp["kernel_stride"],
                    kernel_size=sp["kernel_size"],
                    block_size=sp["block_size"], topk=sp["topk"],
                    init_blocks=sp["init_blocks"],
                    local_blocks=sp["window_size"] // sp["block_size"],
                    res=res, **sparse_kw) for xb, r0 in zip(xs, starts[si])]
                xs[:] = [o[0] for o in out]
                done = max((lengths[si] - sp["kernel_size"])
                           // sp["kernel_stride"] + 1, 0)
                kc = jnp.pad(kc, ((0, max(t - kc.shape[0], 0)), (0, 0),
                                  (0, 0)))[:t]
                kc = jnp.where((jnp.arange(t) < done)[:, None, None], kc, 0.0)
                rows = {"k": k.reshape(t, -1), "v": v.reshape(t, -1),
                        "kc": kc.reshape(t, -1)}
                if on_pairs is not None:
                    on_pairs(si, li, sum(o[1] for o in out))
                del kv, k, v, kc, k_blocks, v_blocks, out
            else:
                s_n = jnp.zeros((m["lightning_nh"],) + (
                    m["lightning_head_dim"],) * 2, jnp.float32)
                for i, r0 in enumerate(starts[si]):
                    xs[i], s_n = lightning_rows(
                        xs[i], jnp.int32(r0), jnp.int32(lengths[si]), s_n,
                        layer, jnp.float32(pub), heads=m["lightning_nh"],
                        head_dim=m["lightning_head_dim"],
                        theta=float(m["rope_theta"]),
                        published_layers=int(
                            m["num_hidden_layers_published"]),
                        eps=eps, res=res, precision=precision)
                rows = {"state": s_n.reshape(1, -1)}
            if on_rows is not None:
                on_rows(si, li, rows)
            del rows
        del layer
    if logit_rows is None:
        return None
    logits = []
    for xs, want in zip(acts, logit_rows):
        # the rows asked, padded to a few sizes (one compile each)
        want = list(want)
        at = jnp.asarray(want + [0] * (_bucket(len(want)) - len(want)),
                         jnp.int32)
        logits.append(head_forward(
            jnp.concatenate(xs)[at], ends["final_norm"], ends["lm_head"],
            eps=eps, shrink=shrink, precision=precision)[:len(want)])
    return logits
