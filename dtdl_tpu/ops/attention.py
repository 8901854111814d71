"""Fused multi-head attention — Pallas TPU flash-attention kernels.

The reference has no attention at all (SURVEY §5.7: CNN/MLP only, reference
pytorch/model.py:53-118, chainer/train_mnist_multi.py:15-28); long-context
sequence models are a first-class capability of *this* framework, so the hot
op gets a real TPU kernel rather than a dense softmax(QK^T)V.

Design (the standard TPU flash decomposition):

* forward — grid ``(batch*heads, q_blocks, k_blocks)``; the k dimension is the
  innermost (sequential) grid axis, so VMEM scratch carries the online-softmax
  state (running max ``m``, normalizer ``l``, accumulator ``acc``) across k
  steps.  O(S) memory instead of O(S²); the S×S score matrix never exists.
* backward — two kernels with the same tiling: one accumulates ``dq`` over k
  blocks, one accumulates ``dk``/``dv`` over q blocks, both recomputing the
  probability tile from the saved logsumexp (no S×S residual is stored).
* causal masking skips whole tiles above the diagonal via ``pl.when``, and
  (round 13) the k/v **index maps clamp** masked iterations to the last
  useful block — consecutive grid steps that map to the same block elide
  their DMA, so skipped tiles cost neither MXU time *nor* HBM bandwidth.
* **fused rope** (round 13): ``flash_attention(..., rope=(cos, sin))`` folds
  the rotary embedding into the Q/K tile loads.  The unfused path
  (``ops/rope.py:apply_rope`` before the kernel) reads and writes both
  [B, H, S, D] tensors through HBM once per layer per direction just to
  rotate them; fused, the per-position (cos, sin) rows ride the existing
  HBM→VMEM tile transfer (tables are [S, D] — ~1/(2·B·H) of the tensor
  traffic) and the rotation is VPU work between the DMA and the matmul.
  The backward kernels re-rotate the saved UNROTATED q/k tiles on load
  (recompute, like the probability tiles) and apply the inverse rotation
  to the accumulated dq/dk at finalize — rope is per-row orthogonal, so
  its VJP is the same rotation with the angle negated.
* grid ``dimension_semantics`` mark the two outer axes ``parallel`` and the
  sequential (scratch-carrying) axis ``arbitrary``, so Mosaic's pipeliner
  double-buffers the next iteration's K/V tiles against the current tile's
  matmuls instead of stalling the MXU at the top of each k step.
* block shapes come from a small **static autotune table** keyed on
  (head_dim, seq bucket, causal) — see :data:`_BLOCK_TABLE` — taken from
  a v5e block sweep whose record is gone (:func:`_build_block_table` says
  what is known of it today).  Explicit ``block_q``/``block_k`` args
  still override (the tests' fixed geometries).

On the CPU platform (the 8-virtual-device test mesh, SURVEY §4) the same
kernels run under the Pallas interpreter, so every test exercises the exact
kernel code path the TPU compiles; any third platform is refused
(:func:`_use_interpret`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtdl_tpu.ops.rope import rope_rows as _rope_rows

NEG_INF = -1e30

# Names (``jax.ad_checkpoint.checkpoint_name``) on what the backward kernels
# read of the forward pass.  Identity outside a ``jax.checkpoint`` policy;
# under one that saves ``FLASH_OUT`` the second ``flash_fwd`` call of a
# rematerialized block is dead code (models/remat_plan.py chooses).
FLASH_OUT = "flash_out"     # o and its log-sum-exp
FLASH_QKV = "flash_qkv"     # q, k, v as the kernels take them (unrotated)


def _use_interpret() -> bool:
    """Whether the Pallas kernels run under the interpreter: yes on the
    CPU platform (tests), no on TPU (Mosaic).  Any other platform is an
    error — these are TPU kernels, and a quiet interpreter fallback
    would report interpreter timings under the device's name."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"dtdl_tpu Pallas kernels run on 'tpu' (Mosaic) or 'cpu' (the "
        f"interpreter, for tests); the default JAX platform is "
        f"{platform!r}")


def _pallas_kwargs():
    """Shared pallas_call extras: the pipelining hint (outer grid axes
    parallel, the sequential scratch-carrying axis arbitrary).  All the
    kernels use 3D grids with the inner axis sequential, so one spelling
    serves them all."""
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


def _vma_of(*arrays):
    """Union of manual (shard_map) varying axes across inputs.

    Pallas out_shapes must declare how outputs vary when the kernel runs
    inside shard_map (e.g. under the DataParallel strategy); outside
    shard_map this is empty and the plain ShapeDtypeStruct path is used.
    """
    vma = set()
    for a in arrays:
        vma |= set(getattr(jax.typeof(a), "vma", ()) or ())
    return tuple(sorted(vma))


def _sds(shape, dtype, vma):
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _zero_pad_rows(x, block_start, valid_total):
    """Zero rows past the logical array end in a ragged tail tile.

    Pallas pads out-of-bounds tile regions (NaN under the interpreter,
    unspecified on hardware); masked-to-zero probabilities times padded
    NaN/garbage still poison matmul accumulations, so padded rows are
    explicitly zeroed before any dot.
    """
    rows = block_start + lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < valid_total, x, 0.0)


# ---------------------------------------------------------------------------
# fused rope: rotation helpers + per-position table rows
# ---------------------------------------------------------------------------

def _rot_half(x):
    """[x1, x2] -> [-x2, x1] on the last (head_dim) axis."""
    d2 = x.shape[-1] // 2
    return jnp.concatenate([-x[..., d2:], x[..., :d2]], axis=-1)


def _rotate(x, c, s):
    """Apply rope to a [rows, d] tile: f32 compute, cast back to x.dtype —
    operation-for-operation the same arithmetic as ops/rope.py:apply_rope
    (x1·c − x2·s ‖ x1·s + x2·c), so fused output bits match unfused."""
    xf = x.astype(jnp.float32)
    return (xf * c + _rot_half(xf) * s).astype(x.dtype)


def _unrotate_f32(g, c, s):
    """Transpose (= inverse: rope is orthogonal per row) rotation of an
    f32 gradient tile — rope with the angle negated."""
    return g * c - _rot_half(g) * s




def mha_reference(q, k, v, *, causal: bool = True, scale: float | None = None,
                  window: int | None = None):
    """Dense reference attention (numerics oracle for the kernels).

    q,k,v: [batch, heads, seq, head_dim]  (k/v seq may differ from q's).
    ``window``: with ``causal``, query ``i`` (bottom-aligned) sees the
    ``window`` keys that end with its own, ``i - window < j <= i``.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        logits = jnp.where(mask, logits, NEG_INF)
    elif window is not None:
        raise ValueError("a window is a band under the causal diagonal")
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# block autotune table
# ---------------------------------------------------------------------------

# seq is bucketed to the next power of two in this range; larger sequences
# use the 32768 entry (same tiling — block shape is seq-independent past
# the knee, only the grid grows).  The sub-128 buckets cover the
# page-granular tile shapes of the paged-attention decode kernel (kernel
# round 2: page sizes 8-64, dtdl_tpu/ops/paged_attention.py), so
# ``strict=True`` receipt checks over serving geometries resolve instead
# of spuriously raising.
_SEQ_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192,
                16384, 32768)


def _build_block_table():
    """(head_dim, seq_bucket, causal) -> (block_q, block_k).

    The table came from a v5e sweep at S = 4,096 on the kernels of PR 8;
    that sweep's record is gone.  What stands today: the benchmark's
    cells read ``flash_roofline`` 27.9-44.6 % with it (ledger, PR 30:
    PERF.md §5), 1024×1024 above 512 positions and one block spanning
    the whole sequence at or below it (2048-row blocks: never retried).
    A retune is judged by the cells' ``flash_roofline`` and
    ``flash.*_ms`` (ROADMAP.md S6), not by a sweep.  Every entry is
    EXPLICIT so the preset-config receipt test can pin that no model
    geometry silently falls back; per-geometry retunes edit this table,
    never call sites.
    """
    table = {}
    # 192: latent attention's query/key head (128 + 64) beside a value head
    # of 128.  On a v5e at 2 x 32 heads x 4,095 positions (PERF.md section 6,
    # PR 33) 1024 x 1024 ran forward + backward in 17.0 ms, 512 x 1024 17.9,
    # 1024 x 512 19.7, 512 x 512 19.2, and the head zero-padded to 256 17.8;
    # 2048 x 1024 does not fit VMEM
    for hd in (16, 32, 64, 128, 192):
        for causal in (False, True):
            for seq in _SEQ_BUCKETS:
                table[(hd, seq, causal)] = ((seq, seq) if seq <= 512
                                            else (1024, 1024))
    return table


_BLOCK_TABLE = _build_block_table()
_BLOCK_DEFAULT = (1024, 1024)


def _build_window_table():
    """(head_dim, window bucket) -> (block_q, block_k) of a windowed call
    whose row is longer than its window (one that is not takes the causal
    entry: its band is the causal triangle).

    Square blocks of half the window's bucket, between 128 and 1024: the
    band is then two blocks wide and a q block touches three k blocks (two
    whole and the two halves the diagonals cut), so two thirds of what the
    kernels multiply is the band's, whatever the window.  Measured at one
    point alone, a window of 2048 in rows of 8,191 at a head of 128
    (PERF.md section 6, PR 35); every other entry is that ratio carried
    over, not a sweep."""
    return {(hd, w): (min(1024, max(128, w // 2)),) * 2
            for hd in (16, 32, 64, 128, 192) for w in _SEQ_BUCKETS}


_WINDOW_TABLE = _build_window_table()


def block_table_entry(head_dim: int, seq: int, causal: bool = True):
    """The explicit autotune-table entry covering (head_dim, seq, causal),
    or None if the geometry has no entry (callers then get
    :data:`_BLOCK_DEFAULT` unless they asked ``strict``)."""
    bucket = next((b for b in _SEQ_BUCKETS if seq <= b), _SEQ_BUCKETS[-1])
    return _BLOCK_TABLE.get((int(head_dim), bucket, bool(causal)))


def resolve_blocks(head_dim: int, seq_q: int, seq_k: int | None = None, *,
                   causal: bool = True, strict: bool = False,
                   window: int | None = None):
    """(block_q, block_k) for a kernel geometry, from the autotune table.

    ``strict=True`` raises instead of falling back to the default — the
    preset-config receipt tests use it to pin that every shipped model
    geometry resolves to an explicit, swept entry.  ``window``: a band
    narrower than the row takes its entry of :data:`_WINDOW_TABLE`.
    """
    seq = max(int(seq_q), int(seq_k if seq_k is not None else seq_q))
    entry = block_table_entry(head_dim, seq, causal)
    if window is not None and window < seq:
        bucket = next((b for b in _SEQ_BUCKETS if window <= b),
                      _SEQ_BUCKETS[-1])
        entry = _WINDOW_TABLE.get((int(head_dim), bucket))
    if entry is None:
        if strict:
            raise ValueError(
                f"no explicit attention block-table entry for head_dim="
                f"{head_dim}, seq={seq}, causal={causal} (buckets: "
                f"{_SEQ_BUCKETS}; head_dims: "
                f"{sorted({k[0] for k in _BLOCK_TABLE})})")
        return _BLOCK_DEFAULT
    return entry


# ---------------------------------------------------------------------------
# causal DMA-eliding index maps
# ---------------------------------------------------------------------------

class _Band:
    """The blocks a band of ``window`` keys under the bottom-aligned
    diagonal touches, from shapes alone: query ``i`` sees keys ``j`` with
    ``i + off - window < j <= i + off``.  The windowed kernels' sequential
    grid axis walks ``k_steps`` (``q_steps`` in the dkv grid) blocks from
    ``first_k(i)`` (``first_q(j)``), the most any block of the other side
    touches, and not the whole row; a step past ``last_k(i)`` (``last_q(j)``)
    repeats that block's index, so it fetches nothing, and its guard is
    false.  Every method takes a Python int or a traced one (``hi``/``lo``:
    the matching ``max``/``min``)."""

    def __init__(self, window, block_q, block_k, sq, sk):
        self.window, self.bq, self.bk = int(window), block_q, block_k
        self.off = sk - sq
        self.nq, self.nk = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)
        self.k_steps = max(self.last_k(i) - self.first_k(i) + 1
                           for i in range(self.nq))
        self.q_steps = max(self.last_q(j) - self.first_q(j) + 1
                           for j in range(self.nk))

    def first_k(self, i, hi=max):
        return hi(i * self.bq + self.off - self.window + 1, 0) // self.bk

    def last_k(self, i, hi=max, lo=min):
        return lo(hi((i + 1) * self.bq + self.off - 1, 0) // self.bk,
                  self.nk - 1)

    def first_q(self, j, hi=max, lo=min):
        return lo(hi(j * self.bk - self.off, 0) // self.bq, self.nq - 1)

    def last_q(self, j, hi=max, lo=min):
        return lo(hi((j + 1) * self.bk - self.off + self.window - 2, 0)
                  // self.bq, self.nq - 1)

    def k_block(self, i, step):
        """The k block of grid step ``step`` of q block ``i`` (traced)."""
        return self.first_k(i, jnp.maximum) + step

    def q_block(self, j, step):
        return self.first_q(j, jnp.maximum, jnp.minimum) + step

    def kmap(self, b, i, j):
        """Index map of the K-side blocks in the fwd/dq grids: step ``j``
        is the band's ``j``-th block, a step past its last repeats it."""
        return (b, jnp.minimum(self.k_block(i, j), self.last_k(
            i, jnp.maximum, jnp.minimum)), 0)

    def qmap(self, b, j, i):
        """Index map of the Q-side blocks in the dkv grid: the dead steps
        sit at the END of the walk and clamp back to the last q block."""
        return (b, jnp.minimum(self.q_block(j, i), self.last_q(
            j, jnp.maximum, jnp.minimum)), 0)

    def live(self, qi, ki):
        """Whether tile ``(qi, ki)`` holds a pair of the band (traced): under
        the diagonal, over the band's lower edge, inside both rows."""
        return ((ki * self.bk < (qi + 1) * self.bq + self.off)
                & ((ki + 1) * self.bk > qi * self.bq + self.off
                   - self.window + 1)
                & (ki < self.nk) & (qi < self.nq))

    def mask(self, rows, cols):
        """The band's pairs of a tile, from its row and column ids."""
        return ((rows + self.off >= cols)
                & (rows + self.off - self.window < cols))


def _kmaps(causal, block_q, block_k, off, lead_b: bool):
    """Index map for K-side blocks in the fwd/dq grids ``(b, i, j)``.

    Causal: iterations whose whole tile sits above the diagonal clamp to
    the last contributing k block — Mosaic skips the DMA when the block
    index repeats, so masked tiles cost no bandwidth (their compute is
    already skipped by the ``pl.when`` guard).  ``lead_b=False`` builds
    the same map for the [S, d] rope tables, which have no batch dim.
    (A windowed call's map is its band's own: :meth:`_Band.kmap`.)
    """
    if not causal:
        if lead_b:
            return lambda b, i, j: (b, j, 0)
        return lambda b, i, j: (j, 0)

    def last_block(i):
        return jnp.maximum(((i + 1) * block_q + off - 1) // block_k, 0)

    if lead_b:
        return lambda b, i, j: (b, jnp.minimum(j, last_block(i)), 0)
    return lambda b, i, j: (jnp.minimum(j, last_block(i)), 0)


def _qmaps(causal, block_q, block_k, off, nq, lead_b: bool):
    """Index map for Q-side blocks in the dkv grid ``(b, j, i)``: the
    masked iterations sit at the START of the q loop, so they clamp
    forward to the first contributing q block (which the pipeline then
    prefetches during the dead iterations instead of refetching it).
    (A windowed call's map is its band's own: :meth:`_Band.qmap`.)"""
    if not causal:
        if lead_b:
            return lambda b, j, i: (b, i, 0)
        return lambda b, j, i: (i, 0)

    def clamp(i, j):
        first = jnp.maximum((j * block_k - off) // block_q, 0)
        return jnp.minimum(jnp.maximum(i, first), nq - 1)

    if lead_b:
        return lambda b, j, i: (b, clamp(i, j), 0)
    return lambda b, j, i: (clamp(i, j), 0)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
                seq_k, off, rope, band=None):
    if rope:
        (qc_ref, qs_ref, kc_ref, ks_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr, qrot_scr) = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    # ``step`` walks the sequential axis; it is the k block itself unless a
    # band starts the walk at the band's first block
    qi, step = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    ki = step if band is None else band.k_block(qi, step)

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        if rope:
            # the q tile is the same for every k step: rotate ONCE into
            # scratch (each k tile is fresh data, so rotating it per
            # step is already once per loaded tile)
            qrot_scr[:] = _rotate(q_ref[0], qc_ref[:], qs_ref[:])

    # tiles strictly above the (bottom-aligned) diagonal contribute nothing
    guard = (ki * block_k < (qi + 1) * block_q + off) if causal else (ki >= 0)
    if band is not None:
        guard = band.live(qi, ki)

    @pl.when(guard)
    def _compute():
        # matmul inputs stay in their native dtype (bf16 on the MXU runs at
        # 2x f32 throughput); preferred_element_type gives f32 accumulation
        q = q_ref[0]                              # [bq, d]
        k = k_ref[0]                              # [bk, d]
        if rope:
            # rotation rides the tile load: f32 compute, cast back to the
            # native dtype — bitwise what apply_rope-then-kernel produces
            q = qrot_scr[:]
            k = _rotate(k, kc_ref[:], ks_ref[:])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk] f32

        cols = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            # bottom-aligned diagonal (== mha_reference's tril(k=sk-sq)):
            # query row i attends keys <= i + (seq_k - seq_q)
            rows = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(rows + off >= cols if band is None
                          else band.mask(rows, cols), s, NEG_INF)
        if seq_k % block_k:                        # mask padded tail keys
            s = jnp.where(cols < seq_k, s, NEG_INF)

        # a row none of whose keys lie in this tile of a band reads p = 1
        # here (both maxima are NEG_INF); the first tile that holds one of
        # its keys (its own is always one) scales that away by alpha = 0
        m_prev = m_scr[:]                          # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]                               # [bk, d] native dtype
        if seq_k % block_k:
            v = _zero_pad_rows(v, ki * block_k, seq_k)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    @pl.when(step == nk - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # lse layout [bh, 1, sq]: keeps the trailing block dims TPU-tileable
        lse_ref[0] = (m_scr[:] + jnp.log(l_safe)).reshape(1, -1)


def _band_of(window, block_q, block_k, sq, sk):
    """The :class:`_Band` of a windowed call (causal, no fused rotation:
    :func:`flash_attention` refuses the rest), None for a call without a
    window: that one lowers to what it always did."""
    return None if window is None else _Band(window, block_q, block_k,
                                             sq, sk)


def _fwd(q, k, v, tabs, scale, causal, block_q, block_k, window=None):
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    rope = tabs is not None
    band = _band_of(window, block_q, block_k, sq, sk)
    grid = (bh, pl.cdiv(sq, block_q),
            pl.cdiv(sk, block_k) if band is None else band.k_steps)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_k=sk, off=sk - sq, rope=rope,
        band=band)
    kmap = band.kmap if band else _kmaps(causal, block_q, block_k, sk - sq,
                                         lead_b=True)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kmap),
        pl.BlockSpec((1, block_k, dv), kmap),
    ]
    operands = (q, k, v)
    if rope:
        tmap = _kmaps(causal, block_q, block_k, sk - sq, lead_b=False)
        in_specs += [
            pl.BlockSpec((block_q, d), lambda b, i, j: (i, 0)),
            pl.BlockSpec((block_q, d), lambda b, i, j: (i, 0)),
            pl.BlockSpec((block_k, d), tmap),
            pl.BlockSpec((block_k, d), tmap),
        ]
        operands += tabs
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd" if band is None else "flash_swa_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            _sds((bh, sq, dv), q.dtype, _vma_of(q, k, v)),
            _sds((bh, 1, sq), jnp.float32, _vma_of(q, k, v)),
        ],
        scratch_shapes=(_scratch(block_q, dv)
                        + ([_vmem((block_q, d), q.dtype)] if rope else [])),
        interpret=_use_interpret(),
        **_pallas_kwargs(),
    )(*operands)
    return o, lse


def _vmem(shape, dtype):
    return pltpu.VMEM(shape, dtype)


def _scratch(block_q, d):
    return [
        _vmem((block_q, 1), jnp.float32),
        _vmem((block_q, 1), jnp.float32),
        _vmem((block_q, d), jnp.float32),
    ]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, block_q, block_k, seq_k, off, rope,
                   band=None):
    if rope:
        (qc_ref, qs_ref, kc_ref, ks_ref,
         dq_ref, dq_scr, qrot_scr) = rest
    else:
        dq_ref, dq_scr = rest
    qi, step = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    ki = step if band is None else band.k_block(qi, step)

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        if rope:
            # same once-per-q-tile rotation as the forward kernel
            qrot_scr[:] = _rotate(q_ref[0], qc_ref[:], qs_ref[:])

    guard = (ki * block_k < (qi + 1) * block_q + off) if causal else (ki >= 0)
    if band is not None:
        guard = band.live(qi, ki)

    @pl.when(guard)
    def _compute():
        # native-dtype (bf16) matmul inputs, f32 accumulation — see _fwd_kernel
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        if rope:
            # recompute the rotation on tile load (like the probability
            # tiles): the residuals stay unrotated
            q = qrot_scr[:]
            k = _rotate(k, kc_ref[:], ks_ref[:])
        lse = lse_ref[0].reshape(block_q, 1)      # [bq, 1]
        delta = delta_ref[0].reshape(block_q, 1)  # [bq, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        cols = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            rows = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(rows + off >= cols if band is None
                          else band.mask(rows, cols), s, NEG_INF)
        if seq_k % block_k:
            s = jnp.where(cols < seq_k, s, NEG_INF)
            k = _zero_pad_rows(k, ki * block_k, seq_k)
            v = _zero_pad_rows(v, ki * block_k, seq_k)
        p = jnp.exp(s - lse)                       # [bq, bk] f32
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bq, bk]
        ds = p * (dp - delta) * scale              # lse/delta refs are f32
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == nk - 1)
    def _finalize():
        dq = dq_scr[:]
        if rope:
            # the accumulated grad is w.r.t. the ROTATED q; rope is
            # orthogonal per row, so its VJP is the inverse rotation —
            # applied once to the f32 accumulator, then cast
            dq = _unrotate_f32(dq, qc_ref[:], qs_ref[:])
        dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, block_q, block_k, seq_k, seq_q, off, rope,
                    band=None):
    if rope:
        (qc_ref, qs_ref, kc_ref, ks_ref,
         dk_ref, dv_ref, dk_scr, dv_scr, krot_scr) = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
    ki, step = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    qi = step if band is None else band.q_block(ki, step)

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        if rope:
            # this grid holds the K tile fixed and walks q blocks, so
            # here it is K that rotates once into scratch
            krot_scr[:] = _rotate(k_ref[0], kc_ref[:], ks_ref[:])

    guard = ((qi + 1) * block_q + off > ki * block_k) if causal else (qi >= 0)
    if band is not None:
        guard = band.live(qi, ki)

    @pl.when(guard)
    def _compute():
        # native-dtype (bf16) matmul inputs, f32 accumulation — see _fwd_kernel
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        if rope:
            q = _rotate(q, qc_ref[:], qs_ref[:])
            k = krot_scr[:]
        lse = lse_ref[0].reshape(block_q, 1)      # f32 (fwd out_shape)
        delta = delta_ref[0].reshape(block_q, 1)  # f32 (computed in _bwd)
        if seq_q % block_q:
            q = _zero_pad_rows(q, qi * block_q, seq_q)
            do = _zero_pad_rows(do, qi * block_q, seq_q)
            lse = _zero_pad_rows(lse, qi * block_q, seq_q)
            delta = _zero_pad_rows(delta, qi * block_q, seq_q)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        cols = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            rows = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(rows + off >= cols if band is None
                          else band.mask(rows, cols), s, NEG_INF)
        if seq_k % block_k:
            s = jnp.where(cols < seq_k, s, NEG_INF)
        p = jnp.exp(s - lse)                       # [bq, bk] f32
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale              # [bq, bk]
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bk, d]

    @pl.when(step == nq - 1)
    def _finalize():
        dk = dk_scr[:]
        if rope:
            dk = _unrotate_f32(dk, kc_ref[:], ks_ref[:])
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, res, do_4d, tabs=None,
         window=None):
    q, k, v, o, lse = res
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    rope = tabs is not None
    band = _band_of(window, block_q, block_k, sq, sk)
    do = do_4d
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]          # [bh, 1, sq]

    kmap = band.kmap if band else _kmaps(causal, block_q, block_k, sk - sq,
                                         lead_b=True)
    grid_dq = (bh, pl.cdiv(sq, block_q),
               pl.cdiv(sk, block_k) if band is None else band.k_steps)
    in_specs_dq = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kmap),
        pl.BlockSpec((1, block_k, dv), kmap),
        pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
    ]
    operands = (q, k, v, do, lse, delta)
    if rope:
        tmap = _kmaps(causal, block_q, block_k, sk - sq, lead_b=False)
        in_specs_dq += [
            pl.BlockSpec((block_q, d), lambda b, i, j: (i, 0)),
            pl.BlockSpec((block_q, d), lambda b, i, j: (i, 0)),
            pl.BlockSpec((block_k, d), tmap),
            pl.BlockSpec((block_k, d), tmap),
        ]
        operands += tabs
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=sk,
                          off=sk - sq, rope=rope, band=band),
        name="flash_bwd_dq" if band is None else "flash_swa_bwd_dq",
        grid=grid_dq,
        in_specs=in_specs_dq,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=_sds((bh, sq, d), q.dtype, _vma_of(q, k, v, do)),
        scratch_shapes=([_scratch(block_q, d)[2]]
                        + ([_vmem((block_q, d), q.dtype)] if rope else [])),
        interpret=_use_interpret(),
        **_pallas_kwargs(),
    )(*operands)

    nq = pl.cdiv(sq, block_q)
    qmap = band.qmap if band else _qmaps(causal, block_q, block_k, sk - sq,
                                         nq, lead_b=True)
    qmap_s = _qmaps(causal, block_q, block_k, sk - sq, nq, lead_b=False)

    def _lse_map(b, j, i):
        bi, ii, _ = qmap(b, j, i)
        return (bi, 0, ii)

    grid_dkv = (bh, pl.cdiv(sk, block_k),
                nq if band is None else band.q_steps)
    in_specs_dkv = [
        pl.BlockSpec((1, block_q, d), qmap),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, dv), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, dv), qmap),
        pl.BlockSpec((1, 1, block_q), _lse_map),
        pl.BlockSpec((1, 1, block_q), _lse_map),
    ]
    operands = (q, k, v, do, lse, delta)
    if rope:
        in_specs_dkv += [
            pl.BlockSpec((block_q, d), qmap_s),
            pl.BlockSpec((block_q, d), qmap_s),
            pl.BlockSpec((block_k, d), lambda b, j, i: (j, 0)),
            pl.BlockSpec((block_k, d), lambda b, j, i: (j, 0)),
        ]
        operands += tabs
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=sk,
                          seq_q=sq, off=sk - sq, rope=rope, band=band),
        name="flash_bwd_dkv" if band is None else "flash_swa_bwd_dkv",
        grid=grid_dkv,
        in_specs=in_specs_dkv,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _sds((bh, sk, d), k.dtype, _vma_of(q, k, v, do)),
            _sds((bh, sk, dv), v.dtype, _vma_of(q, k, v, do)),
        ],
        scratch_shapes=([_scratch(block_k, d)[2], _scratch(block_k, dv)[2]]
                        + ([_vmem((block_k, d), k.dtype)] if rope else [])),
        interpret=_use_interpret(),
        **_pallas_kwargs(),
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public ops with custom VJP (plain + fused-rope variant)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q, block_k):
    o, _ = _fwd(q, k, v, None, scale, causal, block_q, block_k)
    return o


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    o, lse = checkpoint_name(
        _fwd(q, k, v, None, scale, causal, block_q, block_k), FLASH_OUT)
    q, k, v = checkpoint_name((q, k, v), FLASH_QKV)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, res, do):
    return _bwd(scale, causal, block_q, block_k, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_swa(q, k, v, scale, block_q, block_k, window):
    """:func:`_flash` under a band of ``window`` keys (always causal): calls
    of their own names, so that a trace tells them from the full ones."""
    o, _ = _fwd(q, k, v, None, scale, True, block_q, block_k, window)
    return o


def _flash_swa_fwd(q, k, v, scale, block_q, block_k, window):
    o, lse = checkpoint_name(
        _fwd(q, k, v, None, scale, True, block_q, block_k, window),
        FLASH_OUT)
    q, k, v = checkpoint_name((q, k, v), FLASH_QKV)
    return o, (q, k, v, o, lse)


def _flash_swa_bwd(scale, block_q, block_k, window, res, do):
    return _bwd(scale, True, block_q, block_k, res, do, window=window)


_flash_swa.defvjp(_flash_swa_fwd, _flash_swa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _flash_rope(q, k, v, qc, qs, kc, ks, scale, causal, block_q, block_k):
    o, _ = _fwd(q, k, v, (qc, qs, kc, ks), scale, causal, block_q, block_k)
    return o


def _flash_rope_fwd(q, k, v, qc, qs, kc, ks, scale, causal, block_q,
                    block_k):
    o, lse = checkpoint_name(
        _fwd(q, k, v, (qc, qs, kc, ks), scale, causal, block_q, block_k),
        FLASH_OUT)
    # residuals keep q/k UNROTATED — the backward kernels re-rotate on
    # tile load, so the rotation never round-trips HBM
    q, k, v = checkpoint_name((q, k, v), FLASH_QKV)
    return o, (q, k, v, o, lse, qc, qs, kc, ks)


def _flash_rope_bwd(scale, causal, block_q, block_k, res, do):
    q, k, v, o, lse, qc, qs, kc, ks = res
    dq, dk, dv = _bwd(scale, causal, block_q, block_k, (q, k, v, o, lse),
                      do, tabs=(qc, qs, kc, ks))
    # rope tables come from rope_frequencies (position constants, never
    # trained) — their cotangents are defined as zero
    return (dq, dk, dv, jnp.zeros_like(qc), jnp.zeros_like(qs),
            jnp.zeros_like(kc), jnp.zeros_like(ks))


_flash_rope.defvjp(_flash_rope_fwd, _flash_rope_bwd)


def _legal_block(seq: int, block: int) -> int:
    """Normalize a block size to Mosaic-legal tiling geometry.

    A block's seq dims must be 128-multiples or span the whole array dim:
    whole-seq when the seq fits in one block (or the 128 floor), else the
    largest 128-multiple <= the request.  Applied **unconditionally** — the
    interpreter (CPU test) path runs the exact tiling geometry the TPU path
    compiles, so CPU green means the TPU grid shape was exercised.
    """
    if seq <= block:
        return seq
    b = max(128, block // 128 * 128)
    return seq if seq <= b else b


def _call_blocks(d, sq, sk, causal, block_q, block_k, window=None):
    """The blocks a call runs at: the table's unless given, made legal."""
    if block_q is None or block_k is None:
        auto_q, auto_k = resolve_blocks(d, sq, sk, causal=causal,
                                        window=window)
        block_q = block_q if block_q is not None else auto_q
        block_k = block_k if block_k is not None else auto_k
    return _legal_block(sq, block_q), _legal_block(sk, block_k)


def band_tiles(seq_q: int, seq_k: int, head_dim: int, window: int,
               block_q: int | None = None,
               block_k: int | None = None) -> dict:
    """What a windowed call costs, from its shapes alone, a head a row: the
    blocks it runs at; the sequential grid steps of the forward (and dq)
    grid and of the dkv grid; of those the tiles each **computes** (its
    guard is true: the rest neither multiply nor fetch); the tiles that
    hold a pair of the band (``needed``: the least any kernel at these
    blocks computes) and its pairs; and the tiles a causal call at these
    blocks computes.  The compile account keeps one of these a traced
    windowed layer (runtime/compile_cache.py:record_window_call)."""
    bq, bk = _call_blocks(head_dim, seq_q, seq_k, True, block_q, block_k,
                          window)
    band = _Band(window, bq, bk, seq_q, seq_k)
    off = seq_k - seq_q
    # the kernels' own walk and guard, step by step
    computed = sum(bool(band.live(i, band.first_k(i) + step))
                   for i in range(band.nq) for step in range(band.k_steps))
    computed_dkv = sum(bool(band.live(band.first_q(j) + step, j))
                       for j in range(band.nk)
                       for step in range(band.q_steps))

    def holds(i, j, w):         # a pair of the band (w: its width) in tile
        return (j * bk < min((i + 1) * bq, seq_q) + off
                and min((j + 1) * bk, seq_k) > i * bq + off - w + 1)

    tiles = [(i, j) for i in range(band.nq) for j in range(band.nk)]
    pairs = sum(max(0, min(i + off, seq_k - 1) - max(i + off - window + 1, 0)
                    + 1) for i in range(seq_q))
    return {"block_q": bq, "block_k": bk, "window": int(window),
            "grid_steps": band.nq * band.k_steps,
            "grid_steps_dkv": band.nk * band.q_steps,
            "computed_tiles": computed, "computed_tiles_dkv": computed_dkv,
            "needed_tiles": sum(holds(i, j, window) for i, j in tiles),
            "causal_tiles": sum(holds(i, j, seq_k + seq_q) for i, j in tiles),
            "band_pairs": pairs}


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    rope=None, rope_positions=None,
                    window: int | None = None):
    """Flash attention over [batch, heads, seq, head_dim] tensors.

    ``v`` may have a head size of its own (latent attention: query/key 192,
    value 128): scores run over the query/key size, the output, the
    accumulator and ``dv`` at the value's.  ``scale`` defaults to the
    query/key size's ``1 / sqrt``; the block table is keyed by that size.

    Differentiable (custom VJP, recompute-based backward); O(seq) memory.
    On the CPU platform the same kernel code runs under the Pallas
    interpreter (tests).

    ``block_q``/``block_k`` default to the static autotune table
    (:func:`resolve_blocks`, keyed on head_dim / seq bucket / causal;
    :func:`_build_block_table`; explicit args override).  VMEM per grid
    step ~= bq·bk·4 (score tile) + bq·d·4 (acc) + (bq+bk)·d·8 (rope
    tables): ~6.5 MB at 1024/1024/d=128 with rope.

    ``rope=(cos, sin)`` — the :func:`dtdl_tpu.ops.rope.rope_frequencies`
    tables, [max_seq, head_dim//2] — fuses the rotary embedding into the
    kernels: Q/K rotate on tile load (forward AND backward recompute),
    and dq/dk are inverse-rotated at finalize, so the separate
    apply_rope HBM round-trip disappears.  Numerically the rotation is
    the same f32-compute/native-cast arithmetic as ``apply_rope``.
    ``rope_positions=(pos_q, pos_k)`` gives each row an explicit global
    position (sequence-parallel shards, zigzag layouts); the default is
    k at 0..sk-1 with q bottom-aligned (the self-attention / training
    case: positions 0..seq-1 for both).

    ``window=W`` (with ``causal``, without ``rope``): query ``i``,
    bottom-aligned as above, sees keys ``j`` with ``i - W < j <= i``: ``W``
    keys with its own.  The three kernels mask by the band, a tile wholly
    outside it is neither computed nor fetched from either side, and the
    sequential grid axis spans the blocks the band touches (three at two
    blocks' width), not the row: a call's cost follows from its shapes
    (:func:`band_tiles`).  The calls are named ``flash_swa_fwd``,
    ``flash_swa_bwd_dq``, ``flash_swa_bwd_dkv``.  ``window=None`` is the
    call this function always made.
    """
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if window is not None and (window < 1 or not causal or rope is not None):
        raise ValueError(
            f"window={window}: a band of at least one key under the causal "
            f"diagonal; the fused rotation takes none (rotate outside)")
    block_q, block_k = _call_blocks(d, sq, sk, causal, block_q, block_k,
                                    window)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, dv)
    if window is not None:
        o = _flash_swa(qf, kf, vf, scale, block_q, block_k, int(window))
    elif rope is None:
        o = _flash(qf, kf, vf, scale, causal, block_q, block_k)
    else:
        cos, sin = rope
        if rope_positions is None:
            if max(sq, sk) > cos.shape[0]:
                # the unfused path failed loudly on a short table (shape
                # mismatch in apply_rope); a silent take-clamp here would
                # instead reuse the last row's rotation for every
                # position past the table — wrong outputs, no error
                raise ValueError(
                    f"rope table covers {cos.shape[0]} positions but "
                    f"seq_q={sq}, seq_k={sk}; build rope_frequencies "
                    f"with max_seq >= the sequence length")
            pos_k = jnp.arange(sk)
            pos_q = jnp.maximum(jnp.arange(sq) + (sk - sq), 0)
        else:
            # explicit positions are data (possibly traced) — the caller
            # owns keeping them inside the table, as with apply_rope
            pos_q, pos_k = rope_positions
        qc, qs = _rope_rows(cos, sin, pos_q)
        kc, ks = _rope_rows(cos, sin, pos_k)
        o = _flash_rope(qf, kf, vf, qc, qs, kc, ks, scale, causal,
                        block_q, block_k)
    return o.reshape(b, h, sq, dv)
