"""Flash attention as a Pallas TPU kernel.

The hot op of the transformer family (BERT zoo, ring/Ulysses sequence
parallelism): fused QK^T → online-softmax → PV with O(S) memory instead of
materializing the (S, S) score matrix in HBM. Reference framework analog:
the fused attention the reference lacked (its transformer era predated it);
TPU design per /opt/skills/guides/pallas_guide.md — a q tile stays resident
in VMEM, spans of k/v sub-tiles stream through the grid's inner dimension
(the span schedule: `_span_schedule`, `_walk`), the MXU sees (rows, d) x
(d, sub-tile) matmuls — (rows, d) x (d, half a sub-tile) inside a sub-tile
the mask cuts, which is walked in quarters so that its dead quarter is
never computed — and the online-softmax running max / sum live in
VMEM scratch across the inner grid steps.  The op reads its tiles off the
shapes (`_choose_tile`) and builds the plan of a signature once (`_plan`).

`flash_attention` is differentiable via custom_vjp with a block-streamed
Pallas backward (FlashAttention-2): the forward saves only (out, lse);
backward recomputes P tiles per block from (q, k, lse), so training is
O(S) memory end to end, and delta = rowsum(dO*O) supplies the softmax
correction.  No per-row statistic is a (..., S, 1) column in HBM, where
the tiling of the last two dimensions pads it 128-fold: the forward turns
a q tile's finished lse into rows of a lane width once (`_store_lse`) and
stores those (`_Plan.stat_shape`: dense at a tile of 1024), the backward
turns them back into the column its bodies broadcast once a q tile
(`_load_column`, into scratch), and delta is made inside the backward from
the dO and out blocks of the rows it holds, so it never exists in HBM at
all.  The
backward is ONE kernel (`_bwd_kernel`) wherever a head's
keys, values and their float32 gradients fit the core's fast memory: it
walks the forward's schedule, computes P, dP and dS once a sub-tile and
feeds dQ (summed over the spans of a resident q tile), dK and dV (summed
in VMEM scratch of the whole sequence over every q tile and every query
head of the group) from them — five products a sub-tile.  Where they do
not fit (`_Plan.fused`: a rule on the sequence length, the two widths and
the operands' type) it is the two kernels it replaces, dQ over streaming
K/V spans and dK/dV over streaming Q spans, which each recompute the
scores: seven products.

Off a TPU the jnp reference implementation runs instead (tests run the
kernel in interpret mode for numerics); on a TPU the kernel runs and a
compile failure raises.
"""
from __future__ import annotations

import collections
import functools
import math
import warnings

import jax
import jax.numpy as jnp

from .registry import register_op

__all__ = ["flash_attention", "attention_reference", "SAVED_BY_NAME",
           "saved_lse", "stored_lse"]

# names (jax.ad_checkpoint.checkpoint_name) of the forward kernel's output
# and logsumexp (in its stored form: `saved_lse` undoes it) among the
# residuals of `flash_attention`'s backward
SAVED_BY_NAME = ("flash_attention_out", "flash_attention_lse")


def _pallas_call(kernel, *, name, vmem_limit=None, **kwargs):
    """`pl.pallas_call` under a stable `name` (what a device trace and
    the HLO show instead of `jvp__.N`), whose kernel body and BlockSpec
    index maps trace with x64 off.  The package turns `jax_enable_x64`
    on for user arrays (the 64-bit dtype contract); under it a Python
    `0` in an index map is an i64, which Mosaic refuses to legalize.
    Kernel operands are bf16/f32/int32, so nothing 64-bit crosses this
    boundary.  ``vmem_limit``: the bytes of fast memory the kernel may
    take where its plan needs more than the compiler's 16 MiB default."""
    import jax.experimental.pallas as pl

    if vmem_limit is not None:
        from jax.experimental.pallas import tpu as pltpu

        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=int(vmem_limit))

    def call(*operands):
        with jax.enable_x64(False):
            return pl.pallas_call(kernel, name=name, **kwargs)(*operands)

    return call


_H1 = 0x9E3779B1
_H2 = 0x85EBCA6B
_H3 = 0xC2B2AE35


def _dropout_keep(seed, bh, q_pos, k_pos, dropout_p):
    """Deterministic per-element keep mask: murmur3-finalizer counter
    hash of (seed, batch·head, global q position, global k position).

    Pure uint32 jnp arithmetic, so the SAME mask materializes inside
    Pallas kernel tiles (fwd and every bwd kernel), in interpret mode,
    and on the full matrix of the jnp reference path — dropout is
    exactly reproducible across all of them."""
    h = (q_pos.astype(jnp.uint32) * jnp.uint32(_H1)
         + k_pos.astype(jnp.uint32) * jnp.uint32(_H2)
         + jnp.asarray(seed).astype(jnp.uint32)
         + jnp.asarray(bh).astype(jnp.uint32) * jnp.uint32(_H3))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_H2)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_H3)
    h = h ^ (h >> 16)
    thresh = jnp.uint32(max(int((1.0 - dropout_p) * 4294967296.0) - 1, 0))
    return h <= thresh


_BIG = 2 ** 30


def _mask_codes(causal, block_diffusion, s_len, valid_len=None, window=None):
    """The mask as static codes per position, or None when nothing is
    masked: ``(qc, kc)``, int32 arrays of shape (s_len, 2) and
    (2, s_len), with

        keep[i, j] = (kc[0, j] == qc[i, 0]) | (kc[1, j] <= qc[i, 1])

    and, under a ``window``, a third query code, (s_len, 3), the
    threshold term's lower bound:

        keep[i, j] = (kc[1, j] <= qc[i, 1]) & (kc[1, j] > qc[i, 2])

    One rule for every mask the kernels know, evaluated from a (block_q,
    2 or 3) column and a (2, block_k) row per tile:

    * padding (``valid_len``): a padded key's codes match no query;
    * ``causal``: the threshold term alone, position against position;
    * ``window=W``, a causal band: query i keeps key j iff 0 <= i - j < W —
      the threshold term between two bounds, j <= i and j > i - W.  A
      band is causal, so ``causal`` beside it changes nothing;
    * ``block_diffusion=(B, L)``, the vectorised training mask of block
      diffusion (Arriola et al., arXiv:2503.09573) over the 2L positions
      [noisy ; clean] with block b(i) = (i mod L) // B: noisy->noisy iff
      same block (the equality term), noisy->clean iff b(j) < b(i),
      clean->clean iff b(j) <= b(i) (the threshold term), clean->noisy
      never.  Every query keeps its own block, so no row is empty.
    """
    import numpy as onp

    pos = onp.arange(s_len)
    valid = pos < (s_len if valid_len is None else valid_len)
    never_q, never_k = onp.full(s_len, -1), onp.full(s_len, -2)
    if window is not None:
        if block_diffusion is not None:
            raise ValueError("window and block_diffusion exclude each other")
        if int(window) < 1:
            raise ValueError(f"window={window}: a band keeps at least the "
                             "query's own position")
        k_thr = onp.where(valid, pos, _BIG)
        return (onp.stack([never_q, pos, pos - int(window)], 1
                          ).astype(onp.int32),
                onp.stack([never_k, k_thr], 0).astype(onp.int32))
    if block_diffusion is not None:
        if causal:
            raise ValueError("causal and block_diffusion exclude each other")
        blen, half = (int(x) for x in block_diffusion)
        if (s_len if valid_len is None else valid_len) != 2 * half:
            raise ValueError(
                f"block_diffusion=(block {blen}, half {half}) needs a "
                f"sequence of {2 * half} positions [noisy ; clean]")
        noisy = pos < half
        blk = onp.where(noisy, pos, pos - half) // blen
        q_eq = onp.where(noisy, blk, -1)
        q_thr = onp.where(noisy, blk - 1, blk)
        k_eq = onp.where(noisy & valid, blk, -2)
        k_thr = onp.where(~noisy & valid, blk, _BIG)
    elif causal:
        q_eq, q_thr, k_eq = never_q, pos, never_k
        k_thr = onp.where(valid, pos, _BIG)
    elif valid_len is not None:
        q_eq, q_thr, k_eq = never_q, onp.zeros(s_len, int), never_k
        k_thr = onp.where(valid, 0, _BIG)
    else:
        return None
    return (onp.stack([q_eq, q_thr], 1).astype(onp.int32),
            onp.stack([k_eq, k_thr], 0).astype(onp.int32))


def _keep(qc, kc, use_eq=True):
    """The rule of ``_mask_codes`` on (n, 2 or 3) query and (2, m) key
    codes (jnp or numpy): an (n, m) boolean.  A third query code is a
    band's lower bound (static: the codes' shape)."""
    keep = kc[1:2, :] <= qc[:, 1:2]
    if qc.shape[-1] == 3:
        keep = keep & (kc[1:2, :] > qc[:, 2:3])
    if use_eq:
        keep = keep | (kc[0:1, :] == qc[:, 0:1])
    return keep


# a sub-tile's class in the span schedule: what the mask keeps of its pairs
_DEAD, _FREE, _MASKED = 0, 1, 2   # nothing (skipped), all (no mask), some
# sub-tiles a schedule word has class bits for: 8 bits, two a sub-tile.  Which
# quarters of a masked sub-tile are live does not fit beside them: a bit a
# quarter rides in a second int32 a step (`_quarter_words`)
_MAX_SPAN = 4


def _classes(codes, s_len, unit_q, unit_k):
    """The class of every (unit_q queries, unit_k keys) sub-tile of a
    mask as an (s_len // unit_q, s_len // unit_k) array of ``_DEAD`` /
    ``_FREE`` / ``_MASKED``, from the codes' minima and maxima over each
    unit: interval arithmetic on 2 * s_len numbers, never a dense (S, S)
    mask.  ``_FREE`` promises that every pair is kept and ``_DEAD`` that
    none is; the masks ``_mask_codes`` builds (code values that rise with
    the position) are classified exactly wherever a unit does not
    straddle the noisy / clean boundary; a band's sub-tile is dead above
    the diagonal and below the band, free where its last key is at most
    its first query and its first key inside its last query's window."""
    import numpy as onp

    nq, nk = s_len // unit_q, s_len // unit_k
    if codes is None:
        return onp.full((nq, nk), _FREE, onp.int8)
    qc = codes[0].astype(onp.int64).reshape(nq, unit_q, -1)
    kc = codes[1].astype(onp.int64).reshape(2, nk, unit_k)
    q_eq, q_thr, k_eq, k_thr = qc[:, :, 0], qc[:, :, 1], kc[0], kc[1]

    def held(eq):           # lowest and highest code a unit holds (< 0: none)
        return (onp.where(eq >= 0, eq, _BIG).min(1),
                onp.where(eq >= 0, eq, -_BIG).max(1))

    (q_lo, q_hi), (k_lo, k_hi) = held(q_eq), held(k_eq)
    some_eq = (k_lo[None] <= q_hi[:, None]) & (k_hi[None] >= q_lo[:, None])
    # every pair by the equality term: one code on each side, the same
    one_q = (q_eq.min(1) == q_hi) & (q_hi >= 0)
    one_k = (k_eq.min(1) == k_hi) & (k_hi >= 0)
    all_eq = one_q[:, None] & one_k[None] & (q_hi[:, None] == k_hi[None])
    some_thr = k_thr.min(1)[None] <= q_thr.max(1)[:, None]
    all_thr = k_thr.max(1)[None] <= q_thr.min(1)[:, None]
    if qc.shape[-1] == 3:       # a band: the threshold's lower bound
        q_low = qc[:, :, 2]
        k_real = onp.where(k_thr < _BIG, k_thr, -_BIG).max(1)   # no padding
        some_thr &= k_real[None] > q_low.min(1)[:, None]
        all_thr &= k_thr.min(1)[None] > q_low.max(1)[:, None]
    return onp.where(all_thr | all_eq, _FREE,
                     onp.where(some_thr | some_eq, _MASKED, _DEAD)
                     ).astype(onp.int8)


def _span_schedule(classes, span, by_key=False):
    """The grid steps of a kernel as one int32 each.  ``classes`` holds a
    row per resident tile (q tiles for the forward and dQ, k tiles for
    dK/dV: pass the transposed array and ``by_key``) and a column per
    streamed sub-tile; a step is a resident tile and a block of ``span``
    consecutive sub-tiles of which at least one is live, in tile-major
    order.  Bits 20.. hold the q index, bits 10..19 the k index (the
    tile's, or the block's in units of ``span`` sub-tiles), bits 2..9
    the classes of the block's sub-tiles, two bits each, bit 1 is set on
    the first step of its tile and bit 0 on the last.  A block the mask
    empties is not in the list, so the grid never visits it; a tile the
    mask empties keeps one sub-tile as ``_MASKED`` (which adds nothing:
    zeros, or no live quarter at all), so every output block is written.
    What a ``_MASKED`` sub-tile holds at the size of a body — which of
    its quarters are dead — is not in these words but in a second one a
    step (`_quarter_words`); the steps are the same with or without it."""
    import numpy as onp

    n_tiles, n_sub = classes.shape
    if not 1 <= span <= _MAX_SPAN or n_sub % span:
        raise ValueError(f"a span of {span} over {n_sub} sub-tiles")
    n_blocks = n_sub // span
    nq, nk = (n_blocks, n_tiles) if by_key else (n_tiles, n_blocks)
    if nq >= 1 << 11 or nk >= 1 << 10:
        raise ValueError(f"too many tiles for the schedule: {nq} x {nk}")
    classes = classes.astype(onp.int32)
    classes[~classes.any(1), 0] = _MASKED
    blocks = classes.reshape(n_tiles, n_blocks, span)
    tile, block = onp.nonzero(blocks.any(2))
    edge = onp.concatenate([[True], tile[1:] != tile[:-1], [True]])
    bits = sum(blocks[tile, block, j] << (2 + 2 * j) for j in range(span))
    qi, kj = (block, tile) if by_key else (tile, block)
    return ((qi << 20) | (kj << 10) | bits | (edge[:-1] << 1)
            | edge[1:]).astype(onp.int32)


def _word_classes(words, span):
    """(steps, span) classes of a schedule's sub-tiles, from its bits."""
    import numpy as onp

    return onp.stack([(words >> (2 + 2 * j)) & 3 for j in range(span)], 1)


def _quarter_words(words, span, classes, n_chunks, n_keys, by_key):
    """One int32 a grid step beside its schedule word: which quarters of
    the step's ``_MASKED`` sub-tiles hold a kept pair.  ``classes`` is the
    mask classified a second time (`_classes`), at a chunk of query rows by
    a body's keys; a sub-tile holds ``n_chunks`` x ``n_keys`` such
    quarters, and quarter (chunk c, keys h) of sub-tile j has bit
    (j * n_chunks + c) * n_keys + h, so the quarters of one chunk of rows
    are ``n_keys`` neighbouring bits.  Sub-tiles of another class leave
    their bits 0: nothing reads them."""
    import numpy as onp

    qi, kj = (x.astype(onp.int64) for x in _tiles_of(words))
    j, c, h = (onp.arange(n).reshape(shape) for n, shape in (
        (span, (1, -1, 1, 1)), (n_chunks, (1, 1, -1, 1)),
        (n_keys, (1, 1, 1, -1))))
    qi, kj = qi.reshape(-1, 1, 1, 1), kj.reshape(-1, 1, 1, 1)
    if by_key:      # a k tile and a span of q sub-tiles
        rows, cols = (qi * span + j) * n_chunks + c, kj * n_keys + h
    else:           # a q tile and a span of k sub-tiles
        rows, cols = qi * n_chunks + c, (kj * span + j) * n_keys + h
    masked = (_word_classes(words, span) == _MASKED)[:, :, None, None]
    live = (masked & (classes[rows, cols] != _DEAD)).astype(onp.int64)
    shift = (j * n_chunks + c) * n_keys + h
    return (live << shift).sum((1, 2, 3)).astype(onp.uint32).view(onp.int32)


def _head_div(bh, group):
    """Row of the key-value head that query-head row ``bh`` reads."""
    return bh if group == 1 else jax.lax.div(bh, jnp.int32(group))


def _tiles_of(e):
    """(q index, k index) of one word of the schedule."""
    return e >> 20, (e >> 10) & 0x3FF


def _aligned(row, unit):
    """``row``, a multiple of ``unit``: the compiler is told so where it
    is a loop's and not a constant."""
    import jax.experimental.pallas as pl

    return row if isinstance(row, int) else pl.multiple_of(row, unit)


def _first(i, unit):
    """The first row of unit ``i`` of ``unit`` rows, aligned."""
    return _aligned(i * unit, unit)


def _each(n, fn):
    """``fn(i)`` for i under ``n``: a rolled loop, or the one call."""
    if n == 1:
        fn(0)
    else:
        jax.lax.fori_loop(0, n, lambda i, c: (fn(i), c)[1], 0)


def _walk(word, quarters, side, body):
    """``body(j, r0, k0, keys, masked)`` on what a grid step's span keeps
    of the mask, in order: ``side.chunk`` query rows from row ``r0`` of
    sub-tile ``j``'s rows against ``keys`` of its keys from key ``k0``.
    Rolled loops: an iteration of the outer one reads its sub-tile's class
    off the schedule word and takes the mask-free or the masked body on
    all the sub-tile's keys, chunk of rows by chunk.  Where the plan cuts
    masked sub-tiles in quarters (``side.keys`` under a sub-tile's keys),
    a chunk of rows of a masked sub-tile reads off ``quarters``
    (`_quarter_words`) which of its quarters are live: all of them, and it
    takes the masked body on all the keys as before; else the masked body
    on each live quarter alone — a dead quarter is never computed.  (A
    body costs the kernels about a microsecond beside its products, so a
    row of live quarters is one body, not two.)  A kernel traces a body
    once for each size and class its plan uses, whatever the tile and the
    span, and the device code of a body is a chunk's."""
    import jax.experimental.pallas as pl

    span, chunk, keys = side.span, side.chunk, side.keys
    rows, cols = _extent(side)
    n_chunks, n_keys = rows // chunk, cols // keys

    def whole(j, masked):
        _each(n_chunks, lambda c: body(j, _first(c, chunk), 0, cols, masked))

    def quartered(j):
        full = (1 << n_keys) - 1

        def row(c):
            r0 = _first(c, chunk)
            live = (quarters >> ((j * n_chunks + c) * n_keys)) & full
            if side.whole_rows:
                pl.when(live == full)(
                    functools.partial(body, j, r0, 0, cols, True))
            _each(n_keys, lambda h: pl.when(
                (live != full) & (((live >> h) & 1) != 0))(
                    functools.partial(body, j, r0, _first(h, keys), keys,
                                      True)))

        _each(n_chunks, row)

    def run(c, j):
        if c == _MASKED and n_keys > 1:
            quartered(j)
        else:
            whole(j, c == _MASKED)

    def visit(j):
        cls = (word >> (2 + 2 * j)) & 3
        for c in side.classes:
            pl.when(cls == c)(functools.partial(run, c, j))

    if span > 1 or len(side.classes) > 1:
        _each(span, visit)
    else:       # one sub-tile a step, one class: every listed step is live
        run(side.classes[0], 0)


def attention_reference(q, k, v, causal=False, scale=None,
                        dropout_p=0.0, dropout_seed=None,
                        block_diffusion=None, window=None):
    """Plain jnp attention (the numeric oracle + off-TPU fallback).
    q: (B, H, S, D); k: (B, Hkv, S, D); v: (B, Hkv, S, Dv) with H a
    multiple of Hkv (query head h reads key-value head h // (H // Hkv));
    the result is (B, H, S, Dv).  ``block_diffusion`` and ``window`` as in
    `flash_attention`.  dropout uses the same counter-hash mask as
    the Pallas kernel, applied to the normalized probabilities
    (numerator only, inverted scaling) — bit-identical semantics to the
    kernel."""
    b, h, s, d = q.shape
    group = h // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k.astype(q.dtype)) * scale
    codes = _mask_codes(causal, block_diffusion, s, window=window)
    if codes is not None:
        scores = jnp.where(_keep(*codes), scores, -jnp.inf)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    if dropout_p > 0.0:
        bh = jnp.arange(b * h, dtype=jnp.int32).reshape(b, h, 1, 1)
        q_pos = jnp.arange(s, dtype=jnp.int32).reshape(1, 1, s, 1)
        k_pos = jnp.arange(s, dtype=jnp.int32).reshape(1, 1, 1, s)
        keep = _dropout_keep(dropout_seed, bh, q_pos, k_pos, dropout_p)
        p = jnp.where(keep, p, 0.0) / (1.0 - dropout_p)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


def _scores(q, k, qc, kc, scale, masked, use_eq):
    """One sub-tile of QK^T * scale; with ``masked`` the mask's dead pairs
    at -inf, from the (rows, 2) and (2, columns) codes."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if masked:
        s = jnp.where(_keep(qc, kc, use_eq), s, -jnp.inf)
    return s


def _pair_keep(seed_ref, bh, q_start, k_start, shape, dropout_p):
    """The dropout keep mask of the sub-tile whose first pair is global
    position (q_start, k_start): the same in every kernel."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return _dropout_keep(seed_ref[0], bh, q_pos, k_pos, dropout_p)


def _store_lse(lse_ref, m_ref, l_ref):
    """logsumexp per row of a finished q tile, m + log l (-inf for a row
    the mask empties), from the running max and sum.  They are columns
    while the tile runs — a (tile, 1) block is 128-fold padding in HBM and
    as much in vector registers — so they are turned into rows of a lane
    width first and the arithmetic runs on the dense form."""
    shape = lse_ref.shape[-2:]
    m, l = m_ref[:].reshape(shape), l_ref[:].reshape(shape)
    lse_ref[0, 0] = jnp.where(jnp.isfinite(m), m + jnp.log(
        jnp.maximum(l, 1e-30)), -jnp.inf)


def _load_column(stat_ref, j, col_ref, r0=0, unroll=False):
    """`_store_lse`'s turn undone: the stored rows of q unit ``j`` of a
    block into rows ``r0``.. of a (rows, 1) column scratch, which is what
    a body broadcasts along the keys.  A transpose a row of a lane width.
    ``unroll``: the rows as static slices (a tile of 1024 has eight), as
    the fused backward asks — a rolled pass with dynamic one-row loads and
    stores costs it four times as much a q tile (PERF.md section 6,
    PR 53).  The dQ and dK/dV kernels, which no cell runs and whose
    bodies are held to a size (ISSUE 40), roll the loop."""
    import jax.experimental.pallas as pl

    n_rows, lanes = stat_ref.shape[-2:]

    def row(r):
        col_ref[pl.ds(_aligned(r0 + r * lanes, lanes), lanes)] = stat_ref[
            0, j, pl.ds(r, 1), :].T

    if unroll:
        for r in range(n_rows):
            row(r)
    else:
        _each(n_rows, row)


def _delta(do_ref, out_ref, j):
    """delta = rowsum(dO . O) in float32, the softmax-grad correction
    (with dropout it still equals rowsum(P-hat . dP-hat) since O = P_d V),
    of the rows of q unit ``j`` of a dO and an ``out`` block: a lane
    reduction, so it is born the column a body broadcasts."""
    return jnp.sum(do_ref[0, j].astype(jnp.float32)
                   * out_ref[0, j].astype(jnp.float32),
                   axis=-1, keepdims=True)


def _tile_columns(lse_ref, do_ref, out_ref, lse_col, delta_col, unroll=False):
    """The two columns a backward's bodies broadcast, for the resident q
    tile: lse from its stored rows, delta from the tile's dO and ``out``
    blocks."""
    _load_column(lse_ref, 0, lse_col, unroll=unroll)
    delta_col[:] = _delta(do_ref, out_ref, 0)


def _fwd_kernel(seed_ref, quart_ref, sched_ref, q_ref, k_ref, v_ref, qc_ref,
                kc_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                scale, side, use_eq, dropout_p=0.0):
    import jax.experimental.pallas as pl

    tile, sub, span, chunk = side.tile, side.sub, side.span, side.chunk
    # hoisted: program_id inside pl.when bodies breaks interpret mode
    bh_idx = pl.program_id(0)
    word = sched_ref[pl.program_id(1)]
    quarters = quart_ref[pl.program_id(1)]
    q_idx, k_blk = _tiles_of(word)

    @pl.when((word & 2) != 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def sub_tile(j, r0, k0, keys, masked):
        rows, cols = pl.ds(r0, chunk), pl.ds(k0, keys)
        v = v_ref[0, j, cols]
        s = _scores(q_ref[0, 0, rows], k_ref[0, j, cols], qc_ref[0, rows],
                    kc_ref[j, :, cols], scale, masked, use_eq)  # (chunk, keys)
        m_prev = m_ref[rows]                           # (chunk, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row with nothing live so far (a sub-tile the mask half
        # empties) has m_new = -inf: exp(-inf - 0) = 0 is what it adds
        m_safe = m_new
        if masked:
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(m_prev - m_safe)
        l_ref[rows] = alpha * l_ref[rows] + jnp.sum(p, axis=-1,
                                                    keepdims=True)
        # dropout masks the numerator only (the softmax denominator l
        # stays un-dropped): out = sum M.p.v / (l.(1-p)) as FlashAttention
        if dropout_p > 0.0:
            keep = _pair_keep(seed_ref, bh_idx, q_idx * tile + r0,
                              (k_blk * span + j) * sub + k0, p.shape,
                              dropout_p)
            p = jnp.where(keep, p, 0.0)
        acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[rows] = m_new

    _walk(word, quarters, side, sub_tile)

    @pl.when((word & 1) != 0)
    def _finish():
        denom = jnp.maximum(l_ref[:], 1e-30)
        # lse records the TRUE softmax normalizer (backward recomputes
        # p-hat from it); only the output division carries the inverted
        # dropout scale
        o_denom = denom * (1.0 - dropout_p) if dropout_p > 0.0 else denom
        o_ref[0, 0] = (acc_ref[:] / o_denom).astype(o_ref.dtype)
        _store_lse(lse_ref, m_ref, l_ref)


def _seed_arr(dropout_seed):
    if dropout_seed is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(dropout_seed, jnp.int32).reshape((1,))


def _scratch(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


# what the tiles the op chooses may plan to use of a core's fast memory:
# the compiler gives a kernel 16 MiB of it unless told otherwise
_VMEM_BUDGET = 12 * 2 ** 20
_LANES = 128
_MAX_TILE = 1024
_SPAN = 2
_ROWS = 512     # query rows a body works on at a time: its device code


def _chunk_of(rows):
    """Query rows of one pass of a body over ``rows`` of them."""
    return _ROWS if rows % _ROWS == 0 else rows


def _working_set(tile, sub, span, dk, dv, itemsize, resident=0):
    """Bytes of fast memory a grid step plans for: three float32
    temporaries of a chunk of rows by a tile of columns (scores,
    probabilities, their gradient: the mask-free sub-tile's body, the
    largest — a quarter of a masked sub-tile takes half the columns),
    the resident rows and the streamed
    span of both widths, double-buffered, and the float32 accumulators.
    With ``resident`` key positions held for a whole head (the fused
    backward, which streams no span): K, V, dK and dV of all of them,
    double-buffered, and the float32 accumulators of both gradients;
    the dQ block beside q and the ``out`` block beside dO; and the
    lane-padded columns of the resident rows — the query codes'
    double-buffered block and the two scratch columns lse and delta are
    turned into (their stored rows are a few KB) — which at a tile of 1024
    rows are 2 MiB that the 12 MiB plans leave to the compiler's slack.
    A width under a lane (64-wide heads) fills the lane in fast memory
    and is planned as one."""
    dk, dv = max(dk, _LANES), max(dv, _LANES)
    total = (3 * 4 * max(_chunk_of(tile), _chunk_of(sub)) * max(tile, sub)
             + 2 * itemsize * (tile + span * sub) * (dk + dv)
             + 4 * tile * (dk + dv))
    if resident:
        total += (resident * (dk + dv) * (4 * itemsize + 4)
                  + 2 * itemsize * tile * (dk + dv)
                  + (2 + 2) * 4 * tile * _LANES)
    return total


# the fused backward holds a head's keys, values and their gradients in
# fast memory where that working set is at most this share of the core's;
# the kernel is then given the working set and a third more as its limit
_FUSED_VMEM_SHARE = 0.6
_V5E_VMEM = 128 * 2 ** 20


def _vmem_capacity():
    """Bytes of fast memory of the core the kernels run on: read from the
    chip where there is one, the v5e's where the kernel is lowered for a
    described chip or interpreted."""
    if jax.devices()[0].platform != "tpu":
        return _V5E_VMEM
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.get_tpu_info().vmem_capacity_bytes


def _span_for(s_len, tile, sub, dk, dv, itemsize):
    """Sub-tiles a grid step streams: as many as divide the sequence and
    fit the budget, ``_SPAN`` at most."""
    for span in range(_SPAN, 1, -1):
        if (s_len // sub) % span == 0 and _working_set(
                tile, sub, span, dk, dv, itemsize) <= _VMEM_BUDGET:
            return span
    return 1


def _choose_tile(s_pad, dk, dv, itemsize):
    """The q and k tile for a padded length when the caller names none:
    the sequence itself where it is one tile, else the largest multiple
    of the lane width up to ``_MAX_TILE`` that divides it and whose
    working set fits the budget."""
    for tile in ([s_pad] if s_pad <= _MAX_TILE else []) + list(
            range(_MAX_TILE, _LANES, -_LANES)):
        if s_pad % tile == 0 and _working_set(
                tile, tile, 1, dk, dv, itemsize) <= _VMEM_BUDGET:
            return tile
    return min(s_pad, _LANES)


# one kernel's half of a plan: the resident tile's rows, a streamed
# sub-tile's, the sub-tiles a grid step streams, the schedule, the classes
# its steps use, the query rows of one pass of a body, whether the resident
# tile is the keys'; then the keys of a quarter of a masked sub-tile (all
# the sub-tile's where the walk does not cut it), which quarters are live,
# one word a step (`_quarter_words`), and whether some chunk of rows of a
# masked sub-tile has every quarter live
_Side = collections.namedtuple(
    "_Side",
    "tile sub span words classes chunk by_key keys quarters whole_rows")


def _extent(side):
    """(query rows, keys) of one sub-tile of a side's schedule."""
    return (side.sub, side.tile) if side.by_key else (side.tile, side.sub)


def _quarters_of(side):
    """(steps, span, chunks, key pieces) booleans: a side's live quarters,
    from `_quarter_words`' bits."""
    import numpy as onp

    rows, cols = _extent(side)
    shape = side.span, rows // side.chunk, cols // side.keys
    bits = side.quarters.view(onp.uint32)[:, None] >> onp.arange(
        math.prod(shape)) & 1
    return bits.reshape((-1,) + shape).astype(bool)


def _maskfree_share(side):
    """Of the sub-tiles a side's schedule visits, the share that takes
    the mask-free body."""
    classes = _word_classes(side.words, side.span)
    return float((classes == _FREE).sum()) / (classes != _DEAD).sum()


def _pairs_visited(side):
    """Query-key pairs of one head that a side's kernels compute: the
    sub-tiles its schedule visits (every class but dead), less the dead
    quarters of the masked ones where the walk cuts them."""
    classes = _word_classes(side.words, side.span)
    if side.keys == _extent(side)[1]:
        return int((classes != _DEAD).sum()) * side.tile * side.sub
    return (int((classes == _FREE).sum()) * side.tile * side.sub
            + int(_quarters_of(side).sum()) * side.chunk * side.keys)


def _pairs_kept(codes):
    """Query-key pairs of one head the mask keeps, from the codes alone:
    per query the keys between its thresholds (a sorted search) and the
    keys of its equality code — the two terms never keep the same pair
    under the masks ``_mask_codes`` builds."""
    import numpy as onp

    qc, kc = (c.astype(onp.int64) for c in codes)
    k_thr = onp.sort(kc[1])
    kept = onp.searchsorted(k_thr, qc[:, 1], "right")
    if qc.shape[-1] == 3:
        kept = kept - onp.searchsorted(k_thr, qc[:, 2], "right")
    total = int(kept.sum())
    q_eq, k_eq = qc[:, 0][qc[:, 0] >= 0], kc[0][kc[0] >= 0]
    if q_eq.size and k_eq.size:
        held = onp.bincount(k_eq, minlength=int(q_eq.max()) + 1)
        total += int(held[q_eq].sum())
    return total


def _stat_shape(heads, s_len, block_q):
    """The form a per-row statistic of ``heads`` heads has in HBM: (heads,
    q tiles, rows, lanes), a q tile's rows as rows of a lane width, or as
    one row where the tile is no multiple of one (toy tiles)."""
    block_q = min(block_q, s_len)
    lanes = _LANES if block_q % _LANES == 0 else block_q
    return heads, s_len // block_q, block_q // lanes, lanes


class _Plan:
    """What the kernels of one signature share, built once (`_plan`):
    tile sizes, the mask's codes, the span schedules with their classes
    and the backward's memory plan.  The forward, dQ and the fused
    backward hold a q tile and walk spans of k sub-tiles (``rows``);
    dK/dV holds a k tile and streams spans of q sub-tiles (``cols``).
    A side whose masked sub-tiles hold whole quarters of `_ROWS` query
    rows by `_ROWS` keys (`_side`: a sub-tile of a multiple of 2 x
    `_ROWS` keys, rows in chunks of `_ROWS`), one of them dead, also
    carries the mask classified at that size, one word a grid step
    (``quarters``: a bit a live quarter): its kernels never compute a
    masked sub-tile's dead quarters (`_walk`; ``keys`` is a quarter's).
    ``block_q`` is the q tile and the q sub-tile, ``block_k`` the k tile
    and the k sub-tile.  ``stat_shape`` is the form the per-row statistic
    lse has in HBM (`_stat_shape`): (heads,
    q tiles, rows, lanes) with a tile's rows as rows of a lane width, or
    as one row where the tile is no multiple of one (toy tiles) — a q
    tile's block is its last two dimensions whole, so every tile size
    takes it, and at a tile of 1024 it is one dense (8, 128) float32
    tile where a (tile, 1) column is padded 128-fold.  ``vmem_limit`` is
    the fast memory the fused backward is given, or None where a head's
    keys, values and their gradients do not fit it (``fused`` says which):
    the backward is then the dQ and the dK/dV kernel, and only then is
    ``cols`` built."""

    def __init__(self, q_shape, k_shape, v_shape, itemsize, causal,
                 block_q, block_k, valid_len, block_diffusion, window=None):
        b, h, s_len, dk = q_shape
        self.group = h // k_shape[1]
        if h != self.group * k_shape[1]:
            raise ValueError(f"{h} query heads over {k_shape[1]} key-value "
                             "heads: not a multiple")
        self.bh, self.bkv, self.s_len = b * h, b * k_shape[1], s_len
        # queries and keys share one width, values and the output another
        self.dk, self.dv = dk, v_shape[-1]
        block_q, block_k = min(block_q, s_len), min(block_k, s_len)
        codes = _mask_codes(causal, block_diffusion, s_len, valid_len,
                            window)
        # the kind of mask, the label of the gauge pair `_plan` sets
        if window is not None:
            self.mask = "window"
        elif block_diffusion is not None:
            self.mask = "block_diffusion"
        elif causal:
            self.mask = "causal"
        else:
            self.mask = "none" if valid_len is None else "padding"
        self.use_eq = block_diffusion is not None
        self.codes = codes if codes is not None else _mask_codes(
            False, None, s_len, s_len)     # nothing reads them: all kept
        self.stat_shape = _stat_shape(self.bh, s_len, block_q)
        self.rows = self._side(codes, block_q, block_k, itemsize)
        need = _working_set(block_q, block_k, 0, self.dk, self.dv, itemsize,
                            resident=s_len)
        self.vmem_limit = need + need // 3 if (
            need <= _FUSED_VMEM_SHARE * _vmem_capacity()) else None
        self.cols = None if self.fused else self._side(
            codes, block_k, block_q, itemsize, by_key=True)

    @property
    def fused(self):
        return self.vmem_limit is not None

    @property
    def stat_bytes_per_row(self):
        """HBM bytes a query row's statistics occupy between the kernels:
        lse's stored form under the (8, 128) tiling of its last two
        dimensions (delta is made in the kernels and is never there)."""
        rows, lanes = self.stat_shape[2:]
        padded = 4 * (-(-rows // 8) * 8) * (-(-lanes // _LANES) * _LANES)
        return padded / (rows * lanes)

    def _side(self, codes, tile, sub, itemsize, by_key=False):
        import numpy as onp

        def used(classes):
            return tuple(int(c) for c in onp.unique(classes) if c != _DEAD)

        span = _span_for(self.s_len, tile, sub, self.dk, self.dv, itemsize)
        rows, cols = (sub, tile) if by_key else (tile, sub)
        classes = _classes(codes, self.s_len, rows, cols)
        words = _span_schedule(classes.T if by_key else classes, span,
                               by_key)
        chunk = _chunk_of(rows)
        side = _Side(tile, sub, span, words, used(_word_classes(words, span)),
                     chunk, by_key, cols, onp.zeros_like(words), False)
        # a masked sub-tile in quarters of a chunk by `_ROWS` keys, where
        # it holds whole ones, a step's fit a word and one of them is dead
        n_chunks, n_keys = rows // chunk, cols // _ROWS
        if (_MASKED in side.classes and chunk == _ROWS
                and cols % (2 * _ROWS) == 0
                and span * n_chunks * n_keys <= 32):
            cut = side._replace(keys=_ROWS, quarters=_quarter_words(
                words, span, _classes(codes, self.s_len, _ROWS, _ROWS),
                n_chunks, n_keys, by_key))
            live = _quarters_of(cut)[_word_classes(words, span) == _MASKED]
            if not live.all():
                side = cut._replace(whole_rows=bool(live.all(-1).any()))
        return side

    def units(self, x, unit, kv=False):
        """(heads, units, rows of a unit, width): a tensor over the
        sequence cut into tiles or sub-tiles (no copy)."""
        return x.reshape(self.bkv if kv else self.bh, self.s_len // unit,
                         unit, -1)

    def code_units(self, unit_q, unit_k):
        """The codes cut the same way: (units, rows, 2), (units, 2, rows)."""
        qc, kc = self.codes
        return (qc.reshape(-1, unit_q, qc.shape[-1]),
                kc.reshape(2, -1, unit_k).transpose(1, 0, 2))

    def specs(self, side, head_of, step_axis=1):
        """BlockSpecs for a grid whose axis ``step_axis`` walks ``side``'s
        schedule: (q-indexed tensor of a width, k-indexed tensor of a
        width, q codes, k codes, q-indexed statistic).  The resident
        side's block is one tile, the streamed side's a span of sub-tiles;
        ``head_of(*grid indices)`` gives (query head row, key-value head
        row).  An index map gets the grid indices, then the three
        prefetched scalar operands (dropout seed, the quarters' words,
        the schedule).  The statistic (lse) has ``stat_shape``, its
        block the step's q units with their rows whole; the kernels turn
        a column into rows and back (`_store_lse`, `_load_column`),
        because doing it in XLA is a relayout of a lane-padded array that
        costs megabytes of code a copy."""
        import jax.experimental.pallas as pl

        tile, sub, span = side[:3]
        q_rows, k_rows = ((span, sub), (1, tile)) if side.by_key else (
            (1, tile), (span, sub))

        def q_of(a):
            return _tiles_of(a[-1][a[step_axis]])[0]

        def k_of(a):
            return _tiles_of(a[-1][a[step_axis]])[1]

        def q_block(width):
            return pl.BlockSpec(
                (1,) + q_rows + (width,),
                lambda *a: (head_of(*a[:-3])[0], q_of(a), 0, 0))

        def k_block(width):
            return pl.BlockSpec(
                (1,) + k_rows + (width,),
                lambda *a: (head_of(*a[:-3])[1], k_of(a), 0, 0))

        return (q_block, k_block,
                pl.BlockSpec(q_rows + (self.codes[0].shape[-1],),
                             lambda *a: (q_of(a), 0, 0)),
                pl.BlockSpec((k_rows[0], 2, k_rows[1]),
                             lambda *a: (k_of(a), 0, 0)),
                pl.BlockSpec(
                    (1, q_rows[0]) + self.stat_shape[2:],
                    lambda *a: (head_of(*a[:-3])[0], q_of(a), 0, 0)))

    def head_block(self, sub, width):
        """BlockSpec of a key-value head's WHOLE sequence in sub-tiles of
        ``sub`` rows, for a grid whose axis 0 walks the key-value heads:
        fetched (or written back) once a head."""
        import jax.experimental.pallas as pl

        return pl.BlockSpec((1, self.s_len // sub, sub, width),
                            lambda b, *_: (b, 0, 0, 0))

    def call(self, kernel, name, side, grid, in_specs, out_specs, out_shape,
             scratch, interpret, vmem_limit=None, **static):
        from jax.experimental.pallas import tpu as pltpu

        return _pallas_call(
            functools.partial(kernel, side=side, use_eq=self.use_eq,
                              **static),
            name=name, vmem_limit=vmem_limit,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape, interpret=interpret)


@functools.lru_cache(maxsize=256)
def _plan(q_shape, k_shape, v_shape, dtype, causal, block_q, block_k,
          valid_len, block_diffusion, window=None):
    """The plan of one signature, built once: N layers, the kernels of a
    layer and remat's replays share one host computation.  Sets the gauges
    ``attention_maskfree_share{kernel}`` from the schedules' class bits,
    ``attention_fused_backward_share`` from the memory plan,
    ``attention_stat_bytes_per_row`` from the statistics' stored form and
    the pair ``attention_pairs_visited{mask}`` /
    ``attention_pairs_kept{mask}`` from the forward's schedule and the
    codes."""
    from ..telemetry import instruments as _telemetry

    plan = _Plan(q_shape, k_shape, v_shape, jnp.dtype(dtype).itemsize,
                 causal, block_q, block_k, valid_len, block_diffusion,
                 window)
    rows = _maskfree_share(plan.rows)
    _telemetry.set_attention_maskfree_share(
        {"flash_attention_fwd": rows, "flash_attention_bwd": rows}
        if plan.fused else
        {"flash_attention_fwd": rows, "flash_attention_bwd_dq": rows,
         "flash_attention_bwd_dkv": _maskfree_share(plan.cols)})
    _telemetry.record_attention_backward_plan(plan.fused)
    _telemetry.attention_stat_bytes_per_row.set(plan.stat_bytes_per_row)
    _telemetry.set_attention_pairs(plan.mask, _pairs_visited(plan.rows),
                                   _pairs_kept(plan.codes))
    return plan


def _plan_of(q, k, v, causal, block_q, block_k, valid_len, block_diffusion,
             window=None):
    return _plan(q.shape, k.shape, v.shape, jnp.dtype(q.dtype).name,
                 bool(causal), int(block_q), int(block_k), valid_len,
                 block_diffusion, window)


@functools.lru_cache(maxsize=256)
def _shared(fn, plan, *static):
    """``fn(plan, *static, *operands)`` as one jitted function a
    signature: the layers of a model trace and lower one kernel, not one
    each."""
    def call(*operands):
        return fn(plan, *static, *operands)

    call.__name__ = fn.__name__.strip("_")
    return jax.jit(call)


def _flash_fwd_call(plan, scale, dropout_p, interpret, seed, q, k, v):
    tile, sub, words = plan.rows.tile, plan.rows.sub, plan.rows.words
    group, dk, dv = plan.group, plan.dk, plan.dv
    q_block, k_block, q_code, k_code, stat = plan.specs(
        plan.rows, lambda b, t: (b, _head_div(b, group)))
    out, lse = plan.call(
        _fwd_kernel, "flash_attention_fwd", plan.rows,
        grid=(plan.bh, len(words)),
        in_specs=[q_block(dk), k_block(dk), k_block(dv), q_code, k_code],
        out_specs=[q_block(dv), stat],
        out_shape=[
            jax.ShapeDtypeStruct((plan.bh, plan.s_len // tile, tile, dv),
                                 q.dtype),
            jax.ShapeDtypeStruct(plan.stat_shape, jnp.float32),
        ],
        scratch=[
            _scratch((tile, 1)),   # running max m
            _scratch((tile, 1)),   # running sum l
            _scratch((tile, dv)),  # output accumulator
        ],
        interpret=interpret, scale=scale, dropout_p=dropout_p,
    )(seed, jnp.asarray(plan.rows.quarters), jnp.asarray(words),
      plan.units(q, tile), plan.units(k, sub, True), plan.units(v, sub, True),
      *plan.code_units(tile, sub))
    return out, lse


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               valid_len=None, dropout_p=0.0, dropout_seed=None,
               block_diffusion=None, window=None):
    """(out, lse): lse as the kernel stores it (`_Plan.stat_shape`; the
    backward reads that form, `saved_lse` gives it by position)."""
    plan = _plan_of(q, k, v, causal, block_q, block_k, valid_len,
                    block_diffusion, window)
    out, lse = _shared(_flash_fwd_call, plan, float(scale), dropout_p,
                       interpret)(_seed_arr(dropout_seed), q, k, v)
    return out.reshape(q.shape[:-1] + (plan.dv,)), lse


def saved_lse(lse, q_shape):
    """The logsumexp the forward saves for the backward, by position:
    (B, H, S) float32 for queries of ``q_shape`` (B, H, S, D).  The stored
    form holds a head's rows in order, so this is its elements regrouped
    (tests and the ring's merge read it; the backward does not)."""
    return lse.reshape(q_shape[:3])


def stored_lse(lse, block_q):
    """`saved_lse` undone: a (B, H, S) logsumexp by position in the form
    `_flash_bwd` takes, for q tiles of ``block_q`` rows (the ring, whose
    lse is the hops' merged one)."""
    b, h, s_len = lse.shape
    return lse.reshape(_stat_shape(b * h, s_len, block_q))


def _recompute_p(q, k, lse_col, qc, kc, scale, masked, use_eq):
    """exp(QK^T * scale - lse) for one (q rows, k rows) sub-tile.
    lse_col: (rows, 1) column (see _finish in _fwd_kernel).  A row of a
    mask-free sub-tile keeps every key of it, so its lse is finite."""
    p = jnp.exp(_scores(q, k, qc, kc, scale, masked, use_eq) - lse_col)
    return jnp.where(jnp.isfinite(lse_col), p, 0.0) if masked else p


def _p_ds(q, k, v, do, lse_col, delta_col, qc, kc, scale, masked, use_eq,
          dropout_p, keep):
    """What the backward makes of one (q rows, k rows) sub-tile before
    its products: ``(p, drop, ds)`` — the probabilities recomputed from
    the saved logsumexp, dropout as a function of a tile (the identity
    without it: P_d = drop(P) is what the forward multiplied V by), and
    dS = P . (drop(dO V^T) - delta) without its factor ``scale``, which
    the kernels apply to the finished sums.  ``keep(shape)`` is the
    sub-tile's keep mask (`_pair_keep`), asked for under dropout only;
    delta already equals rowsum(P_d . dP_d) because delta = rowsum(dO . O).
    """
    p = _recompute_p(q, k, lse_col, qc, kc, scale, masked, use_eq)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (q rows, k rows)

    def drop(x):
        return x

    if dropout_p > 0.0:
        kept = keep(p.shape)

        def drop(x):
            return jnp.where(kept, x, 0.0) / (1.0 - dropout_p)

    return p, drop, p * (drop(dp) - delta_col)


def _t_dot(a, b):
    """a^T b in float32: (rows, m), (rows, n) -> (m, n)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _bwd_dq_kernel(seed_ref, quart_ref, sched_ref, q_ref, k_ref, v_ref,
                   do_ref, out_ref, lse_ref, qc_ref, kc_ref, dq_ref, dq_acc,
                   lse_col, delta_col, *, scale, side, use_eq,
                   dropout_p=0.0):
    """dQ over streaming K/V spans; the q tile's two columns as in the
    fused kernel."""
    import jax.experimental.pallas as pl

    tile, sub, span, chunk = side.tile, side.sub, side.span, side.chunk
    bh_idx = pl.program_id(0)
    word = sched_ref[pl.program_id(1)]
    quarters = quart_ref[pl.program_id(1)]
    q_idx, k_blk = _tiles_of(word)

    @pl.when((word & 2) != 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        _tile_columns(lse_ref, do_ref, out_ref, lse_col, delta_col)

    def sub_tile(j, r0, k0, keys, masked):
        rows, cols = pl.ds(r0, chunk), pl.ds(k0, keys)
        k = k_ref[0, j, cols]
        _, _, ds = _p_ds(
            q_ref[0, 0, rows], k, v_ref[0, j, cols], do_ref[0, 0, rows],
            lse_col[rows], delta_col[rows], qc_ref[0, rows],
            kc_ref[j, :, cols], scale, masked, use_eq, dropout_p,
            lambda shape: _pair_keep(
                seed_ref, bh_idx, q_idx * tile + r0,
                (k_blk * span + j) * sub + k0, shape, dropout_p))
        dq_acc[rows] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _walk(word, quarters, side, sub_tile)

    @pl.when((word & 1) != 0)
    def _finish():
        dq_ref[0, 0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, quart_ref, sched_ref, q_ref, k_ref, v_ref,
                    do_ref, out_ref, lse_ref, qc_ref, kc_ref, dk_ref,
                    dv_ref, dk_acc, dv_acc, lse_col, delta_col, *, scale,
                    side, use_eq, group, dropout_p=0.0):
    import jax.experimental.pallas as pl

    tile, sub, span, chunk = side.tile, side.sub, side.span, side.chunk

    # grid: (key-value head, step of the k-major schedule, query head of
    # the group): the spans of q sub-tiles of one k tile and, inside
    # each, the query heads that share this key-value head all add into
    # one dK and dV
    g_idx = pl.program_id(2)
    bh_idx = pl.program_id(0) * group + g_idx
    word = sched_ref[pl.program_id(1)]
    quarters = quart_ref[pl.program_id(1)]
    q_blk, k_idx = _tiles_of(word)

    @pl.when(((word & 2) != 0) & (g_idx == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # every step streams another span of q sub-tiles (or another query
    # head's): their lse from its stored rows and their delta from the dO
    # and ``out`` it streams beside q, as one column each over the span
    def columns(j):
        _load_column(lse_ref, j, lse_col, j * sub)
        delta_col[pl.ds(_first(j, sub), sub)] = _delta(do_ref, out_ref, j)

    _each(span, columns)

    def sub_tile(j, r0, k0, keys, masked):
        rows, cols = pl.ds(r0, chunk), pl.ds(k0, keys)
        span_rows = pl.ds(pl.multiple_of(j * sub + r0, chunk), chunk)
        q, do = q_ref[0, j, rows], do_ref[0, j, rows]
        p, drop, ds = _p_ds(
            q, k_ref[0, 0, cols], v_ref[0, 0, cols], do,
            lse_col[span_rows], delta_col[span_rows], qc_ref[j, rows],
            kc_ref[0, :, cols], scale, masked, use_eq, dropout_p,
            lambda shape: _pair_keep(
                seed_ref, bh_idx, (q_blk * span + j) * sub + r0,
                k_idx * tile + k0, shape, dropout_p))    # (chunk, keys)
        dv_acc[cols] += _t_dot(drop(p).astype(do.dtype), do)
        dk_acc[cols] += _t_dot(ds.astype(q.dtype), q)

    _walk(word, quarters, side, sub_tile)

    @pl.when(((word & 1) != 0) & (g_idx == group - 1))
    def _finish():
        dk_ref[0, 0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_kernel(seed_ref, quart_ref, sched_ref, q_ref, k_ref, v_ref, do_ref,
                out_ref, lse_ref, qc_ref, kc_ref, dq_ref, dk_ref, dv_ref,
                dq_acc, dk_acc, dv_acc, lse_col, delta_col, *, scale, side,
                use_eq, group, dropout_p=0.0):
    """dQ, dK and dV from one computation of P, dP and dS a sub-tile.

    Grid: (key-value head, query head of its group, step of the q-major
    schedule).  A q tile is resident and dQ sums over its spans as in
    `_bwd_dq_kernel`; K and V are blocks of the WHOLE sequence of the
    key-value head (fetched once a head, cut into sub-tiles on their
    leading axis), and dK and dV sum over every query tile and every
    query head of the group in float32 scratch of the whole sequence,
    zeroed at the head's first step and stored at its last.  The two
    columns the bodies broadcast are made once a q tile, at its first
    step, in scratch: lse from its stored rows, delta from the tile's dO
    and ``out`` blocks."""
    import jax.experimental.pallas as pl

    tile, sub, span, chunk = side.tile, side.sub, side.span, side.chunk
    g_idx, step = pl.program_id(1), pl.program_id(2)
    bh_idx = pl.program_id(0) * group + g_idx
    word, quarters = sched_ref[step], quart_ref[step]
    q_idx, k_blk = _tiles_of(word)
    n_sub = dk_acc.shape[0]

    def each_sub_tile(fn):
        jax.lax.fori_loop(0, n_sub, lambda i, c: (fn(i), c)[1], 0)

    @pl.when((g_idx == 0) & (step == 0))
    def _init_kv():
        @each_sub_tile
        def _(i):
            dk_acc[i] = jnp.zeros(dk_acc.shape[1:], dk_acc.dtype)
            dv_acc[i] = jnp.zeros(dv_acc.shape[1:], dv_acc.dtype)

    @pl.when((word & 2) != 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        _tile_columns(lse_ref, do_ref, out_ref, lse_col, delta_col,
                      unroll=True)

    def sub_tile(j, r0, k0, keys, masked):
        rows, cols = pl.ds(r0, chunk), pl.ds(k0, keys)
        kj = k_blk * span + j                    # the sub-tile's own index
        q, do, k = q_ref[0, 0, rows], do_ref[0, 0, rows], k_ref[0, kj, cols]
        p, drop, ds = _p_ds(
            q, k, v_ref[0, kj, cols], do, lse_col[rows],
            delta_col[rows], qc_ref[0, rows], kc_ref[j, :, cols], scale,
            masked, use_eq, dropout_p,
            lambda shape: _pair_keep(seed_ref, bh_idx, q_idx * tile + r0,
                                     kj * sub + k0, shape, dropout_p))
        ds = ds.astype(k.dtype)                  # (chunk, keys), once
        dq_acc[rows] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[kj, cols] += _t_dot(ds, q)
        dv_acc[kj, cols] += _t_dot(drop(p).astype(do.dtype), do)

    _walk(word, quarters, side, sub_tile)

    @pl.when((word & 1) != 0)
    def _finish():
        dq_ref[0, 0] = (dq_acc[:] * scale).astype(dq_ref.dtype)

    @pl.when((g_idx == group - 1) & (step == sched_ref.shape[0] - 1))
    def _finish_kv():
        @each_sub_tile
        def _(i):
            dk_ref[0, i] = (dk_acc[i] * scale).astype(dk_ref.dtype)
            dv_ref[0, i] = dv_acc[i].astype(dv_ref.dtype)


def _flash_bwd_call(plan, scale, dropout_p, interpret, seed, q, k, v, out,
                    lse, g):
    """The backward's kernels on the forward's residuals; ``lse`` in its
    stored form.  Nothing but the kernels: delta = rowsum(dO . O) is made
    inside them, from the ``out`` block a q tile holds beside its dO."""
    group, dk_, dv_ = plan.group, plan.dk, plan.dv
    tile, sub, words = plan.rows.tile, plan.rows.sub, plan.rows.words
    dq_shape = jax.ShapeDtypeStruct(
        (plan.bh, plan.s_len // tile, tile, dk_), q.dtype)
    k_units, v_units = plan.units(k, sub, True), plan.units(v, sub, True)
    operands = (seed, jnp.asarray(plan.rows.quarters), jnp.asarray(words),
                plan.units(q, tile), k_units, v_units, plan.units(g, tile),
                plan.units(out, tile), lse, *plan.code_units(tile, sub))
    columns = [_scratch((tile, 1)), _scratch((tile, 1))]    # lse, delta
    if plan.fused:
        q_block, _, q_code, k_code, stat = plan.specs(
            plan.rows, lambda b, j, t: (b * group + j, b), step_axis=2)
        k_head, v_head = plan.head_block(sub, dk_), plan.head_block(sub, dv_)
        return plan.call(
            _bwd_kernel, "flash_attention_bwd", plan.rows,
            grid=(plan.bkv, group, len(words)),
            in_specs=[q_block(dk_), k_head, v_head, q_block(dv_),
                      q_block(dv_), stat, q_code, k_code],
            out_specs=[q_block(dk_), k_head, v_head],
            out_shape=[dq_shape,
                       jax.ShapeDtypeStruct(k_units.shape, k.dtype),
                       jax.ShapeDtypeStruct(v_units.shape, v.dtype)],
            scratch=[_scratch((tile, dk_)), _scratch(k_units.shape[1:]),
                     _scratch(v_units.shape[1:])] + columns,
            interpret=interpret, vmem_limit=plan.vmem_limit, scale=scale,
            dropout_p=dropout_p, group=group)(*operands)

    q_block, k_block, q_code, k_code, stat = plan.specs(
        plan.rows, lambda b, t: (b, _head_div(b, group)))
    dq = plan.call(
        _bwd_dq_kernel, "flash_attention_bwd_dq", plan.rows,
        grid=(plan.bh, len(words)),
        in_specs=[q_block(dk_), k_block(dk_), k_block(dv_), q_block(dv_),
                  q_block(dv_), stat, q_code, k_code],
        out_specs=q_block(dk_), out_shape=dq_shape,
        scratch=[_scratch((tile, dk_))] + columns,
        interpret=interpret, scale=scale, dropout_p=dropout_p,
    )(*operands)

    tile, sub, words = plan.cols.tile, plan.cols.sub, plan.cols.words
    span = plan.cols.span
    q_block, k_block, q_code, k_code, stat = plan.specs(
        plan.cols, lambda b, t, j: (b * group + j, b))
    dk, dv = plan.call(
        _bwd_dkv_kernel, "flash_attention_bwd_dkv", plan.cols,
        grid=(plan.bkv, len(words), group),
        in_specs=[q_block(dk_), k_block(dk_), k_block(dv_), q_block(dv_),
                  q_block(dv_), stat, q_code, k_code],
        out_specs=[k_block(dk_), k_block(dv_)],
        out_shape=[
            jax.ShapeDtypeStruct((plan.bkv, plan.s_len // tile, tile, dk_),
                                 k.dtype),
            jax.ShapeDtypeStruct((plan.bkv, plan.s_len // tile, tile, dv_),
                                 v.dtype),
        ],
        scratch=[_scratch((tile, dk_)), _scratch((tile, dv_)),
                 _scratch((span * sub, 1)), _scratch((span * sub, 1))],
        interpret=interpret, scale=scale, dropout_p=dropout_p, group=group,
    )(seed, jnp.asarray(plan.cols.quarters), jnp.asarray(words),
      plan.units(q, sub), plan.units(k, tile, True),
      plan.units(v, tile, True), plan.units(g, sub), plan.units(out, sub),
      lse, *plan.code_units(sub, tile))
    return dq, dk, dv


def _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k,
               interpret, valid_len=None, dropout_p=0.0,
               dropout_seed=None, block_diffusion=None, window=None):
    """Block-streamed FlashAttention-2 backward: O(S) memory, no (S, S)
    residual: P sub-tiles are recomputed from (q, k, lse) (and the
    dropout keep mask from its counter hash).  ``lse`` is as `_flash_fwd`
    stores it (`stored_lse` gives one by position that form)."""
    plan = _plan_of(q, k, v, causal, block_q, block_k, valid_len,
                    block_diffusion, window)
    dq, dk, dv = _shared(_flash_bwd_call, plan, float(scale), dropout_p,
                         interpret)(
        _seed_arr(dropout_seed), q, k, v, out, lse, g)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, seed, causal, scale, block_q, block_k, interpret,
           dropout_p=0.0, valid_len=None, block_diffusion=None, window=None):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        interpret, valid_len, dropout_p, seed,
                        block_diffusion, window)
    return out


def _flash_vjp_fwd(q, k, v, seed, causal, scale, block_q, block_k,
                   interpret, dropout_p=0.0, valid_len=None,
                   block_diffusion=None, window=None):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                          interpret, valid_len, dropout_p, seed,
                          block_diffusion, window)
    # named, so that a checkpoint policy can keep the kernel's two results
    # (SAVED_BY_NAME) and not run the forward kernel a second time
    out = checkpoint_name(out, SAVED_BY_NAME[0])
    lse = checkpoint_name(lse, SAVED_BY_NAME[1])
    return out, (q, k, v, seed, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, dropout_p,
                   valid_len, block_diffusion, window, res, g):
    import numpy as _onp

    q, k, v, seed, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q,
                            block_k, interpret, valid_len, dropout_p,
                            seed, block_diffusion, window)
    # integer seed takes a float0 cotangent
    return dq, dk, dv, _onp.zeros(seed.shape, jax.dtypes.float0)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@register_op("flash_attention")
def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, dropout_p=0.0,
                    dropout_seed=None, block_diffusion=None, window=None):
    """Fused multi-head attention: softmax(QK^T * scale + mask) V.

    q: (B, H, S, D); k: (B, Hkv, S, D); v: (B, Hkv, S, Dv), H a multiple
    of Hkv (grouped queries: query head h reads key-value head
    h // (H // Hkv), and dK and dV sum over the group).  Queries
    and keys share the width D, values and the result (B, H, S, Dv) the
    width Dv, and the two may differ (latent attention: 192 and 128):
    every tile, accumulator and gradient has the width of its tensor, so
    nothing is padded to the wider one.  The default scale is
    1 / sqrt(D).  Runs the Pallas kernel on TPU (or anywhere with
    interpret=True); off a TPU the jnp reference runs instead.

    **Tiles.**  A caller that names no ``block_q`` / ``block_k`` gets the
    tile the op reads off what it sees (`_choose_tile`): the sequence is
    padded to whole sublanes (up to 128 positions) or whole lane widths;
    up to 1024 positions it is one tile, above that the tile is the
    largest multiple of 128 up to 1024 that divides it and whose working
    set at the operands' widths and type fits the fast-memory budget (the
    head group does not enter: the key-value tile dK/dV holds is shared
    by its query heads).  A named block is honoured: ``block_q`` is the
    q tile (and dK/dV's q sub-tile), ``block_k`` the k sub-tile (and
    dK/dV's k tile); the sequence is padded to a multiple of ``block_q``
    and the kernel masks the padded keys (static ``valid_len``).

    What the kernel cannot tile takes the reference path LOUDLY, with the
    counter ``attention_kernel_fallback_total{reason}`` and a
    RuntimeWarning, wherever the kernel was asked for (on a TPU, or with
    interpret=True): ``reason="width"`` where D or Dv is not a multiple
    of 8 (a tile's rows would not be whole sublanes), ``reason="tile"``
    where ``block_q`` / ``block_k`` do not divide the padded length or
    are not multiples of 8.

    **The span schedule.**  The mask is static: nothing, ``causal``,
    ``block_diffusion=(block length B, half length L)`` for a sequence of
    S = 2L positions [noisy ; clean], or ``window=W``, a causal band —
    query i keeps key j iff 0 <= i - j < W (a band is causal whatever
    ``causal`` says; with ``block_diffusion`` it raises) — (`_mask_codes`
    has the rule).  A
    grid step of the forward and dQ kernels holds one q tile and streams
    a span of consecutive k sub-tiles (the fused backward walks the same
    steps over keys it holds; dK/dV: a k tile and a span of q sub-tiles);
    the host lists the steps once (`_span_schedule`) and
    gives each sub-tile a class from the codes' minima and maxima:
    dead (skipped; a span of dead sub-tiles is not in the grid at all —
    above the diagonal and, under a window, below the band),
    mask-free (every pair kept: no codes read, no compare, no select) or
    masked.  The kernel walks the span with one rolled loop that takes
    the mask-free or the masked body by the class, so each body is traced
    once a kernel whatever the span.  A masked sub-tile of 1024 keys is
    half dead or worse under every mask here, so it is walked in quarters
    of 512 query rows by 512 keys, classified the same way a second time
    (one more int32 a step, a bit a live quarter): a chunk of 512 rows
    whose quarters are all live takes the masked body on all the keys, as
    a sub-tile that is not cut does; a chunk with a dead quarter takes the
    masked body on its live quarter alone — the diagonal's upper right
    quarter, a band's lower left, all but the diagonal quarters of block
    diffusion's noisy blocks are never computed.  Sub-tiles under 1024
    keys (one-tile sequences, small named blocks) are computed whole.  An
    unmasked call is the case where every sub-tile is mask-free, padding
    one more masked class at the edge.  The gauge
    ``attention_maskfree_share{kernel}`` is the share of the schedule's
    visited sub-tiles that are whole; the pair
    ``attention_pairs_visited{mask}`` / ``attention_pairs_kept{mask}``
    gives, for the latest plan of each kind of mask, the pairs of one head
    that the forward computes (whole sub-tiles, and masked ones less their
    dead quarters) and the pairs the mask keeps.

    **The backward's memory plan.**  One fused kernel,
    ``flash_attention_bwd``, where the working set of a key-value head
    held whole — K, V, dK and dV blocks of the entire sequence,
    double-buffered, their float32 accumulators, the resident q tile's
    side (q, dO, ``out``, dQ, the two statistics' columns) and the
    temporaries of a chunk (`_working_set(resident=S)`: 44 MB
    at 8192 positions of 192 / 128 bf16) — is at most 0.6 of the core's
    fast memory (`pltpu.get_tpu_info()` on a TPU, the v5e's 128 MiB where
    the kernel is lowered for a described chip or interpreted); the kernel
    is given that working set and a third more as ``vmem_limit_bytes``.
    It walks the forward's schedule on the grid (key-value head, query
    head of the group, step), fetches K and V once a key-value head,
    computes P, dP and dS once a sub-tile and makes dQ, dK and dV from
    them.  Beyond that (192 / 128 bf16: past 16k positions) the backward
    is ``flash_attention_bwd_dq`` and ``flash_attention_bwd_dkv``, which
    stream what the other holds and each recompute the scores.  Both
    give the same float32 terms; dK and dV sum them in another order.
    The gauge ``attention_fused_backward_share`` is the share of planned
    signatures that fused.

    **The row statistics.**  The forward saves lse for the backward as
    rows of a lane width (`_Plan.stat_shape`: at a tile of 1024 one dense
    (8, 128) float32 tile a q tile, where a (heads, S, 1) column is
    128-fold padding in HBM), turned from the running column once a q
    tile; the backward turns them back into a column in scratch once a q
    tile and makes delta = rowsum(dO . O) there too, from the tile's dO
    and ``out`` blocks, so no XLA pass runs between the kernels.  (The
    two-kernel plan: dQ does the same, and dK/dV makes delta for the span
    of q sub-tiles it streams, from their dO and ``out``.)  The gauge
    ``attention_stat_bytes_per_row`` is what a row's statistics occupy in
    HBM: 4 at a tile of 1024.

    **Before the first step** the plan of a signature (codes, schedules,
    classes, the memory plan, the gauges) is built once on the host in
    about a millisecond (`_plan`), and the layers of a model that share
    the signature share one traced and lowered copy of each kernel
    (`_shared`).

    dropout_p > 0 with an int32 `dropout_seed` applies attention-prob
    dropout inside the kernel (numerator-masked, inverted scaling; the
    counter-hash mask regenerates identically in the backward kernels
    and the reference path; see _dropout_keep).
    """
    d = q.shape[-1]
    if k.shape[-1] != d or v.shape[:-1] != k.shape[:-1]:
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape}: q and k share their "
            "last dimension, k and v every other")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    dropout_p = float(dropout_p)
    if block_diffusion is not None:
        block_diffusion = tuple(int(x) for x in block_diffusion)
    if window is not None:
        if block_diffusion is not None:
            raise ValueError("window and block_diffusion exclude each other")
        window = int(window)

    def _fallback(qq, kk, vv, reason=None):
        if reason is not None:
            _record_fallback(reason, q.shape, v.shape[-1], block_q, block_k)
        return attention_reference(qq, kk, vv, causal=causal, scale=scale,
                                   dropout_p=dropout_p,
                                   dropout_seed=dropout_seed,
                                   block_diffusion=block_diffusion,
                                   window=window)

    if interpret is None:
        interpret = False
        if jax.devices()[0].platform != "tpu":
            return _fallback(q, k, v)
    if d % 8 or v.shape[-1] % 8:
        return _fallback(q, k, v, "width")
    s_len = q.shape[2]
    if block_q is None and block_k is None:
        # whole sublanes up to one lane width, whole lane widths above
        s_pad = _tile_pad_len(s_len, _LANES)
        bq = bk = _choose_tile(s_pad, d, v.shape[-1],
                               jnp.dtype(q.dtype).itemsize)
    else:
        block_q = block_q or block_k
        block_k = block_k or block_q
        s_pad = _tile_pad_len(s_len, block_q)
        bq, bk = min(block_q, s_pad), min(block_k, s_pad)
        if s_pad % bq or s_pad % bk or bq % 8 or bk % 8:
            return _fallback(q, k, v, "tile")
    seed = _seed_arr(dropout_seed)
    if s_pad == s_len:
        return _flash(q, k, v, seed, causal, scale, bq, bk, interpret,
                      dropout_p, None, block_diffusion, window)
    pad = [(0, 0), (0, 0), (0, s_pad - s_len), (0, 0)]
    out = _flash(jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                 seed, causal, scale, bq, bk, interpret, dropout_p, s_len,
                 block_diffusion, window)
    return out[:, :, :s_len]


def _record_fallback(reason, q_shape, v_width, block_q, block_k):
    """The kernel was asked for and the plain reference runs: counted,
    and said."""
    from ..telemetry import instruments as _telemetry

    _telemetry.record_attention_fallback(reason)
    warnings.warn(
        f"flash_attention: q {tuple(q_shape)}, value width {v_width}, "
        f"blocks ({block_q}, {block_k}) cannot be tiled ({reason}); the "
        "plain reference, which materialises the scores, runs instead",
        RuntimeWarning, stacklevel=3)


def _tile_pad_len(s_len, block):
    """Smallest padded length that tiles: multiple of 8 below one block,
    multiple of the block size above."""
    if s_len >= block:
        return -(-s_len // block) * block
    return -(-s_len // 8) * 8
