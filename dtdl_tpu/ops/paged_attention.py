"""Pallas paged-attention decode kernel (kernel round 2, ISSUE 16).

The serving engine's production memory layout is the block-paged KV
arena (``[n_pages, H, page_size, D]`` pools addressed through per-slot
page tables — dtdl_tpu/models/transformer.py:_paged_attend_slots).  The
round-6 attend gathers the ENTIRE logical view first::

    pages = jnp.take(pool, table, axis=0)        # [B, n_ptab, H, pg, D]
    gat   = pages.transpose(...).reshape(B, H, n_ptab * pg, D)

which materializes ``B * n_ptab * page_size`` K/V rows in scratch HBM
every decode step even though a slot at position ``pos`` only occupies
``ceil((pos+1)/page_size)`` pages (PR 6's known-remaining; its cost is
not measured on the chip).  This kernel walks the page
table INSIDE the attention loop instead:

* grid ``(B, H, n_ptab)`` with the page step innermost (sequential);
  batch and head are embarrassingly parallel;
* the table / positions / active mask ride in **scalar prefetch**
  (``pltpu.PrefetchScalarGridSpec``): the K/V BlockSpec index maps read
  ``table[b, j]`` to aim each DMA straight at the *physical* page, so
  tiles stream ``[1, 1, page_size, D]`` chunks from the pooled arena —
  no gathered copy exists at any point;
* pages past a slot's high-water mark (``j > (pos + S - 1) // page``)
  clamp their index map to the last live page — consecutive identical
  block indices elide the DMA (the _kmaps trick in ops/attention.py) —
  and the guarded kernel body skips them entirely, so a 100-token slot
  in a 32K arena reads 1 page, not ``n_ptab``;
* int8/fp8 arenas fuse dequant into the tile loads exactly as the
  gather path does: the per-(page, head, offset) key scales ride a
  sibling ``[1, H, page_size]`` tile (all heads of the page — Mosaic
  wants a block's last two dims whole or (8, 128)-aligned, and a
  single head's ``[1, page_size]`` row is neither; the kernel picks
  its head's row) and multiply the f32 logits BEFORE masking, the
  value scales fold into the softmax weights (quant/core.py:kv_quantize
  layout, PR 7);
* online softmax in VMEM scratch (m, l, acc — same recurrence as
  ops/attention.py:_fwd_kernel) finalizes once per (b, h).

Bytes argument (SCALING.md "Kernel round 2"): per decode step the gather
path moves ``2 * B * n_ptab * page * H * D`` payload bytes pool->scratch
PLUS the same again scratch->compute; this kernel moves
``2 * B * ceil((pos+1)/page) * page * H * D`` pool->VMEM once.  For the
production long-context shape (n_ptab >> live pages) that is the whole
tax.  Inactive rows read only the reserved garbage page 0 (elided after
the first tile) and write zeros.

Token-identity contract: for every ACTIVE row the masked-logit set,
scale application order, and f32 accumulation dtype match
``_paged_attend_slots`` op-for-op (per-tile max/sum ordering differs —
an online softmax — so outputs agree to bf16 rounding; greedy tokens
are identical, pinned by tests/test_paged_kernel.py under the standing
RecompileSentinel zero-new-programs contract).  Inactive rows return
zeros (the engine discards them; the gather path returns garbage there).

On CPU the kernel runs under the Pallas interpreter (correct but slow —
tests only); ``paged_kernel_enabled`` routes 'auto' to the gather path
there so a CPU engine never eats interpreter overhead by accident.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from dtdl_tpu.ops.attention import (_pallas_kwargs, _sds, _use_interpret,
                                    _vma_of, _vmem)

NEG_INF = -1e30   # matches the gather path's mask fill, NOT -inf


def paged_kernel_enabled(flag) -> bool:
    """Resolve the engine's ``paged_kernel=`` knob to a bool.

    ``True``/``False`` are explicit (True on CPU runs the interpreter —
    tests and debugging); ``'auto'`` enables the kernel exactly where it
    compiles through Mosaic (TPU) and takes the gather path where it
    would be interpreted (CPU); any other platform raises.
    """
    if isinstance(flag, bool):
        return flag
    if flag == "auto":
        return not _use_interpret()
    raise ValueError(
        f"paged_kernel must be True, False or 'auto', got {flag!r}")


def _kernel(tab_ref, pos_ref, act_ref, *refs, scale, page, s_new, quant,
            dtype):
    """Grid (B, H, n_ptab); j = page step, sequential innermost."""
    if quant:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    b, hh, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)

    def head_row(scale_ref):
        """This head's [1, page] f32 row of a [1, H, page] scale tile."""
        tile = scale_ref[0].astype(jnp.float32)               # [H, pg]
        rows = lax.broadcasted_iota(jnp.int32, tile.shape, 0)
        return jnp.sum(jnp.where(rows == hh, tile, 0.0), axis=0,
                       keepdims=True)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # high-water page of this row; tiles past it hold no visible keys
    last = jnp.maximum((pos_ref[b] + s_new - 1) // page, 0)
    guard = (act_ref[b] > 0) & (j <= last)

    @pl.when(guard)
    def _compute():
        q = q_ref[0, 0]                            # [S, D] native dtype
        k = k_ref[0, 0]                            # [pg, D] pool dtype
        if quant:
            k = k.astype(dtype)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [S, pg] f32
        if quant:
            # key scale multiplies the logits BEFORE the causal scale
            # and mask — the gather path's exact op order
            s = s * head_row(ks_ref)
        cols = j * page + lax.broadcasted_iota(
            jnp.int32, (s_new, page), 1)
        qpos = pos_ref[b] + lax.broadcasted_iota(
            jnp.int32, (s_new, page), 0)
        s = jnp.where(cols <= qpos, s * scale, NEG_INF)
        # every active row keeps column 0 of tile j=0, so a fully
        # NEG_INF first tile (the exp(0)=1 hazard) cannot occur
        m_prev = m_scr[:]                          # [S, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # [S, pg] f32
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0]                            # [pg, D]
        if quant:
            # value scale folds into the softmax weights (as gather)
            w = (p * head_row(vs_ref)).astype(dtype)
            v = v.astype(dtype)
        else:
            w = p.astype(v.dtype)
        pv = lax.dot_general(
            w, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)       # inactive rows -> 0
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def paged_attention(q, pages_k, pages_v, page_table, pos, active, *,
                    scale, key_scale=None, value_scale=None):
    """Attend ``q`` [B, H, S, D] (already roped) against a paged arena.

    ``pages_k``/``pages_v``: ``[n_pages, H, page_size, D]`` pools (bf16,
    int8 or fp8 — pass both ``key_scale``/``value_scale``
    ``[n_pages, H, page_size]`` siblings for quantized pools).
    ``page_table`` [B, n_ptab] int32 maps logical to physical pages
    (garbage page 0 for unmapped), ``pos`` [B] the clamped per-row
    positions (``pos_safe``), ``active`` [B] bool.  Returns
    ``[B, H, S, D]`` in q's dtype; inactive rows are zeros.
    """
    from jax.experimental.pallas import tpu as pltpu

    b, h, s_new, d = q.shape
    n_pages, hp, page, dp = pages_k.shape
    assert (hp, dp) == (h, d), (pages_k.shape, q.shape)
    n_ptab = page_table.shape[1]
    quant = key_scale is not None
    if quant != (value_scale is not None):
        raise ValueError("key_scale and value_scale must be passed "
                         "together")

    # block-index maps: scalar-prefetch refs arrive as trailing args.
    # Pages past the high-water mark clamp to it and inactive rows pin
    # to the garbage page — consecutive identical indices elide the DMA.
    def _phys(jj, tab, p_, act, bi):
        last = jnp.maximum((p_[bi] + s_new - 1) // page, 0)
        jc = jnp.minimum(jj, last)
        return jnp.where(act[bi] > 0, tab[bi, jc], 0)

    def q_map(bi, hh, j, tab, p_, act):
        return (bi, hh, 0, 0)

    def kv_map(bi, hh, j, tab, p_, act):
        return (_phys(j, tab, p_, act, bi), hh, 0, 0)

    def scale_map(bi, hh, j, tab, p_, act):
        return (_phys(j, tab, p_, act, bi), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, s_new, d), q_map),
        pl.BlockSpec((1, 1, page, d), kv_map),
        pl.BlockSpec((1, 1, page, d), kv_map),
    ]
    operands = [q, pages_k, pages_v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, h, page), scale_map),
            pl.BlockSpec((1, h, page), scale_map),
        ]
        operands += [key_scale, value_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, h, n_ptab),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, s_new, d), q_map),
        scratch_shapes=[
            _vmem((s_new, 1), jnp.float32),
            _vmem((s_new, 1), jnp.float32),
            _vmem((s_new, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, scale=scale, page=page, s_new=s_new, quant=quant,
        dtype=q.dtype)
    return pl.pallas_call(
        kernel,
        name="paged_attn",
        grid_spec=grid_spec,
        out_shape=_sds((b, h, s_new, d), q.dtype,
                       _vma_of(q, pages_k, pages_v)),
        interpret=_use_interpret(),
        **_pallas_kwargs(),
    )(jnp.asarray(page_table, jnp.int32),
      jnp.asarray(pos, jnp.int32),
      jnp.asarray(active, jnp.int32),
      *operands)
