"""Flash-attention Pallas kernel vs the dense reference (SURVEY §4 pattern:
numerics on CPU via the Pallas interpreter, same kernel code as TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtdl_tpu.ops.attention import flash_attention, mha_reference
from dtdl_tpu.ops.rope import apply_rope, rope_frequencies


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def _sq_loss(fn):
    return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)


def test_legal_block_geometry():
    """Blocks normalize to Mosaic-legal sizes identically on CPU and TPU:
    whole-seq when it fits (or under the 128 floor), else 128-multiples."""
    from dtdl_tpu.ops.attention import _legal_block
    assert _legal_block(96, 32) == 96      # sub-floor seq: one whole block
    assert _legal_block(96, 512) == 96     # seq fits the block
    assert _legal_block(200, 128) == 128   # ragged tail tile
    assert _legal_block(640, 512) == 512
    assert _legal_block(200, 150) == 128   # rounds down to the 128 grid
    assert _legal_block(1024, 512) == 512


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_dense(causal):
    # seq 256 with 128-blocks: a real 2x2 multi-block grid (the normalized
    # geometry — sub-128 blocks round up to whole-seq, see _legal_block)
    q, k, v = (_rand((2, 2, 256, 32), s) for s in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=1e-5)


def test_flash_grads_match_dense():
    q, k, v = (_rand((1, 1, 256, 16), s) for s in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_flash = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128)), (0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda q, k, v: mha_reference(
        q, k, v, causal=True)), (0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)


@pytest.mark.parametrize("dims, causal", [
    ((192, 128), True), ((192, 128), False), ((24, 16), True),
    ((16, 40), True)])
def test_flash_takes_a_value_head_size_of_its_own(dims, causal):
    """Latent attention's shapes: scores over the query/key size, the
    output, the accumulator and ``dv`` at the value's, over a ragged
    multi-block grid; values and all three gradients against the dense
    reference, the scale the query/key size's."""
    d, dv = dims
    q, k = (_rand((1, 2, 300, d), s) for s in range(2))
    v = _rand((1, 2, 300, dv), 2)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=128,
                               block_k=128)

    def dense(q, k, v):
        return mha_reference(q, k, v, causal=causal)

    with jax.default_matmul_precision("highest"):
        out, ref = flash(q, k, v), dense(q, k, v)
        g_flash = jax.grad(_sq_loss(flash), (0, 1, 2))(q, k, v)
        g_ref = jax.grad(_sq_loss(dense), (0, 1, 2))(q, k, v)
    assert out.shape == (1, 2, 300, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-6, rtol=1e-5)
    for a, b in zip(g_flash, g_ref):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-4)


# (rows, keys, window, block_q, block_k): the band narrower than a block,
# equal to one, wider than one, wider than the row (then the causal
# triangle), meeting a padded tail on either side, with blocks that are not
# square, and with more keys than queries (the diagonal bottom-aligned)
_BANDS = [
    (512, 512, 100, 128, 128), (512, 512, 128, 128, 128),
    (512, 512, 300, 128, 128), (300, 300, 1000, 128, 128),
    (500, 500, 256, 128, 128), (511, 511, 200, 256, 128),
    (511, 511, 200, 128, 256), (200, 456, 64, 128, 128),
    (300, 300, 1, 128, 128)]


@pytest.mark.parametrize("sq, sk, window, bq, bk", _BANDS, ids=[
    "narrower_than_a_block", "a_block_wide", "wider_than_a_block",
    "wider_than_the_row", "padded_tail", "tall_blocks", "wide_blocks",
    "more_keys_than_queries", "its_own_key_alone"])
def test_windowed_flash_matches_dense(sq, sk, window, bq, bk):
    """The three kernels under a band against ``mha_reference`` with the
    same window: the output and all three gradients."""
    from dtdl_tpu.ops.attention import band_tiles
    q = _rand((1, 2, sq, 32), 0)
    k, v = _rand((1, 2, sk, 32), 1), _rand((1, 2, sk, 32), 2)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=bq, block_k=bk)

    def dense(q, k, v):
        return mha_reference(q, k, v, causal=True, window=window)

    with jax.default_matmul_precision("highest"):
        out, ref = flash(q, k, v), dense(q, k, v)
        g_flash = jax.grad(_sq_loss(flash), (0, 1, 2))(q, k, v)
        g_ref = jax.grad(_sq_loss(dense), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-6, rtol=1e-5)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)
    if window >= sk:        # the band is the causal triangle
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(mha_reference(q, k, v, causal=True)),
            atol=5e-6, rtol=1e-5)
    # the call's cost follows from its shapes: the tiles the kernels compute
    # (their own walk and guard) are the tiles that hold a pair of the band
    # by an explicit mask, never more than a causal call's, and the grid
    # spans the band's blocks where that is less than the row's
    tiles = band_tiles(sq, sk, 32, window, bq, bk)
    off = sk - sq
    rows, cols = np.arange(sq)[:, None] + off, np.arange(sk)[None, :]
    causal = rows >= cols
    band = causal & (rows - window < cols)

    def blocks(mask):
        return sum(bool(mask[i:i + bq, j:j + bk].any())
                   for i in range(0, sq, bq) for j in range(0, sk, bk))

    assert tiles["needed_tiles"] == blocks(band)
    assert tiles["causal_tiles"] == blocks(causal)
    assert tiles["computed_tiles"] == tiles["computed_tiles_dkv"] \
        == tiles["needed_tiles"] <= tiles["causal_tiles"]
    assert tiles["band_pairs"] == int(band.sum())
    nq, nk = -(-sq // bq), -(-sk // bk)
    assert tiles["computed_tiles"] <= tiles["grid_steps"] <= nq * nk
    assert tiles["computed_tiles"] <= tiles["grid_steps_dkv"] <= nq * nk


def test_a_window_is_a_band_under_the_causal_diagonal_alone():
    q = _rand((1, 1, 64, 16))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, window=8, rope=rope_frequencies(16, 64))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, window=0)
    with pytest.raises(ValueError, match="window"):
        mha_reference(q, q, q, causal=False, window=8)


# sha256 of the lowered text of the call without a window, forward and all
# three gradients, as the parent of PR 35 lowers it (bf16, 2 heads of 32,
# 300 positions, 128 x 128 blocks; plain and with the fused rotation)
_PARENT_TEXT = {
    False: "0dddf4ae5852f4f57c756c6407377b87fd9dd42325d2f68e86ee67a336f9b0e9",
    True: "4d3858374a9f8e8d5b5417e7d5a1dca3f8875d5cc9b4846a4ef055ff34e5580d",
}


@pytest.mark.parametrize("rope", [False, True], ids=["plain", "rope"])
def test_without_a_window_the_call_lowers_to_the_parents_text(rope):
    import hashlib
    q = jax.ShapeDtypeStruct((1, 2, 300, 32), jnp.bfloat16)
    tabs = rope_frequencies(32, 300) if rope else None

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, rope=tabs,
            window=None).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        q, q, q).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_TEXT[rope]


def test_windowed_block_table_entries():
    """A band narrower than the row takes square blocks of half its bucket
    (the band two blocks wide, three computed a q block); one that is not
    takes the causal entry."""
    from dtdl_tpu.ops.attention import band_tiles, resolve_blocks
    assert resolve_blocks(128, 8191, causal=True, window=2048,
                          strict=True) == (1024, 1024)
    assert resolve_blocks(128, 8191, causal=True, window=1024,
                          strict=True) == (512, 512)
    assert resolve_blocks(128, 8191, causal=True, window=100,
                          strict=True) == (128, 128)
    assert resolve_blocks(128, 2047, causal=True, window=4096) \
        == resolve_blocks(128, 2047, causal=True)
    # the Trinity cell's windowed layer, a head a row: 21 tiles computed of
    # a grid of 24 steps, for 14.0 tiles' worth of pairs; a causal call 36
    tiles = band_tiles(8191, 8191, 128, 2048)
    assert (tiles["block_q"], tiles["block_k"]) == (1024, 1024)
    assert (tiles["grid_steps"], tiles["computed_tiles"],
            tiles["needed_tiles"], tiles["causal_tiles"]) == (24, 21, 21, 36)
    assert tiles["band_pairs"] == 14_679_040


@pytest.mark.parametrize("causal", [True, False])
def test_flash_cross_attention(causal):
    """q shorter than k/v; causal must be bottom-aligned like the oracle.
    q gets a ragged 128+32 grid, k/v a ragged 2.5-block grid."""
    q = _rand((2, 2, 160, 16), 0)
    k = _rand((2, 2, 320, 16), 1)
    v = _rand((2, 2, 320, 16), 2)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    assert out.shape == q.shape
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=1e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128)), (0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda q, k, v: mha_reference(
        q, k, v, causal=causal)), (0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_ragged_blocks(causal):
    # seq not a multiple of the block size exercises padded edge tiles
    q, k, v = (_rand((1, 1, 200, 32), s) for s in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=1e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128)), (0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda q, k, v: mha_reference(
        q, k, v, causal=causal)), (0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)


def test_flash_bf16_forward_and_grads():
    """bf16 inputs exercise the native-dtype matmul paths (the astype calls
    at every dot site are no-ops under f32); f32 reference with loose
    tolerance bounds the bf16 rounding."""
    q, k, v = (_rand((2, 2, 256, 32), s).astype(jnp.bfloat16)
               for s in range(3))
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    assert out.dtype == jnp.bfloat16
    ref = mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=5e-2, rtol=5e-2)

    def loss(fn, cast):
        return lambda q, k, v: jnp.sum(
            fn(cast(q), cast(k), cast(v)).astype(jnp.float32) ** 2)

    g = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128), lambda x: x),
        (0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda q, k, v: mha_reference(q, k, v, causal=True),
                          lambda x: x.astype(jnp.float32)), (0, 1, 2))(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    for a, b in zip(g, g_ref):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=0.15, rtol=0.15)


@pytest.mark.parametrize("causal", [True, False])
def test_fused_rope_matches_unfused(causal):
    """rope=(cos, sin) fused into the kernels == apply_rope outside then
    the plain kernels, fwd AND grads — the round-13 fusion contract.
    f32 is exact (the in-kernel rotation is the same f32 arithmetic);
    the grad comparison is against autodiff THROUGH apply_rope, i.e. the
    fused backward's inverse rotation vs jax's linearized rotation."""
    d = 32
    q, k, v = (_rand((2, 2, 256, d), s) for s in range(3))
    cos, sin = rope_frequencies(d, 512)
    fused = flash_attention(q, k, v, causal=causal, rope=(cos, sin),
                            block_q=128, block_k=128)
    unfused = flash_attention(apply_rope(q, cos, sin),
                              apply_rope(k, cos, sin), v, causal=causal,
                              block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               atol=1e-6, rtol=1e-6)
    ref = mha_reference(apply_rope(q, cos, sin), apply_rope(k, cos, sin),
                        v, causal=causal)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=2e-6, rtol=1e-5)

    g_f = jax.grad(_sq_loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, rope=(cos, sin),
        block_q=128, block_k=128)), (0, 1, 2))(q, k, v)
    g_u = jax.grad(_sq_loss(lambda q, k, v: flash_attention(
        apply_rope(q, cos, sin), apply_rope(k, cos, sin), v,
        causal=causal, block_q=128, block_k=128)), (0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_u):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)


@pytest.mark.slow
def test_fused_rope_ragged_and_cross():
    """Odd shapes through the fused path: a ragged 200-row tail tile and
    a cross-attention 160/320 (q bottom-aligned, the default positions:
    unfused parity needs apply_rope(q, offset=sk-sq)).  slow: four
    extra fwd+bwd interpreter compiles; the tier-1 parity pin is
    test_fused_rope_matches_unfused (870s budget discipline)."""
    d = 16
    cos, sin = rope_frequencies(d, 512)
    for (sq, sk) in ((200, 200), (160, 320)):
        q = _rand((1, 2, sq, d), 0)
        k = _rand((1, 2, sk, d), 1)
        v = _rand((1, 2, sk, d), 2)
        fused = flash_attention(q, k, v, causal=True, rope=(cos, sin),
                                block_q=128, block_k=128)
        qr = apply_rope(q, cos, sin, offset=sk - sq)
        kr = apply_rope(k, cos, sin)
        np.testing.assert_allclose(
            np.asarray(fused),
            np.asarray(mha_reference(qr, kr, v, causal=True)),
            atol=2e-6, rtol=1e-5)

        g_f = jax.grad(_sq_loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, rope=(cos, sin),
            block_q=128, block_k=128)), (0, 1, 2))(q, k, v)
        g_u = jax.grad(_sq_loss(lambda q, k, v: flash_attention(
            apply_rope(q, cos, sin, offset=sk - sq),
            apply_rope(k, cos, sin), v, causal=True,
            block_q=128, block_k=128)), (0, 1, 2))(q, k, v)
        for a, b in zip(g_f, g_u):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=1e-4)


@pytest.mark.slow
def test_fused_rope_explicit_positions():
    """rope_positions overrides the contiguous default — the sequence-
    parallel / zigzag hook: parity vs apply_rope(positions=...).
    slow: two extra interpreter compiles (budget discipline)."""
    d, s = 16, 256
    cos, sin = rope_frequencies(d, 512)
    pos = jnp.asarray(np.random.default_rng(9).permutation(512)[:s],
                      jnp.int32)
    q, k, v = (_rand((1, 2, s, d), i) for i in range(3))
    fused = flash_attention(q, k, v, causal=True, rope=(cos, sin),
                            rope_positions=(pos, pos),
                            block_q=128, block_k=128)
    unfused = flash_attention(apply_rope(q, cos, sin, positions=pos),
                              apply_rope(k, cos, sin, positions=pos), v,
                              causal=True, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.slow
def test_fused_rope_bf16():
    """bf16 through the fused kernels: XLA may fold the rotate→cast→dot
    chain differently than the pre-rotated path (observed: ~0.03% of
    elements one bf16 ulp apart), so the pin is one-ulp-loose against
    unfused and standard bf16 tolerance against the f32 reference.
    slow: fwd+bwd compiles in two dtypes (budget discipline; the bf16
    kernel path itself stays tier-1-covered via test_transformer's
    flash-model tests and test_flash_bf16_forward_and_grads)."""
    d = 32
    q, k, v = (_rand((2, 2, 256, d), s).astype(jnp.bfloat16)
               for s in range(3))
    cos, sin = rope_frequencies(d, 512)
    fused = flash_attention(q, k, v, causal=True, rope=(cos, sin),
                            block_q=128, block_k=128)
    assert fused.dtype == jnp.bfloat16
    unfused = flash_attention(apply_rope(q, cos, sin),
                              apply_rope(k, cos, sin), v, causal=True,
                              block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(unfused, np.float32),
                               atol=1e-2, rtol=5e-2)
    ref = mha_reference(
        apply_rope(q, cos, sin).astype(jnp.float32),
        apply_rope(k, cos, sin).astype(jnp.float32),
        v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(ref), atol=5e-2, rtol=5e-2)

    g_f = jax.grad(_sq_loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, rope=(cos, sin),
        block_q=128, block_k=128)), (0, 1, 2))(q, k, v)
    g_u = jax.grad(_sq_loss(lambda q, k, v: flash_attention(
        apply_rope(q, cos, sin), apply_rope(k, cos, sin), v,
        causal=True, block_q=128, block_k=128)), (0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_u):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=0.15, rtol=0.15)


def test_fused_rope_short_table_raises():
    """A rope table shorter than the sequence fails LOUDLY (the unfused
    path's apply_rope shape error) — never a silent take-clamp that
    would reuse the last row's rotation past the table."""
    d = 16
    q = _rand((1, 1, 64, d), 0)
    cos, sin = rope_frequencies(d, 32)          # table < seq
    with pytest.raises(ValueError, match="rope table"):
        flash_attention(q, q, q, causal=True, rope=(cos, sin))


def test_block_table_covers_presets():
    """The autotune-table receipt (ISSUE 8): every shipped model preset
    resolves to an EXPLICIT block-table entry — no silent fallback —
    and so do the long-context geometries.  Unknown geometries
    fall back to the documented default unless strict."""
    from dtdl_tpu.models.transformer import transformer_lm
    from dtdl_tpu.ops.attention import (_BLOCK_DEFAULT, block_table_entry,
                                        resolve_blocks)
    for size in ("tiny", "small", "base", "large", "base-moe8",
                 "small-hd128", "base-hd128"):
        cfg = transformer_lm(size)
        for causal in (True, False):
            entry = block_table_entry(cfg.head_dim, cfg.max_seq, causal)
            assert entry is not None, (size, causal)
            assert resolve_blocks(cfg.head_dim, cfg.max_seq,
                                  causal=causal, strict=True) == entry
    for d in (64, 128, 192):     # 192: latent attention's query/key head
        for s in (4096, 32768):
            assert block_table_entry(d, s, True) is not None
    assert resolve_blocks(192, 4095, strict=True) == (1024, 1024)
    assert resolve_blocks(256, 999) == _BLOCK_DEFAULT
    with pytest.raises(ValueError, match="block-table"):
        resolve_blocks(256, 999, strict=True)


def test_ring_attention_bf16_matches_dense():
    """bf16 through the ring (shard_map over 'seq') — exercises the
    native-dtype einsums and the causal block skip."""
    from jax.sharding import Mesh, PartitionSpec as P
    from dtdl_tpu.parallel.sequence import ring_attention

    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("seq",))
    q, k, v = (_rand((2, 2, 64, 16), s).astype(jnp.bfloat16)
               for s in range(3))
    ring = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq", causal=True),
        mesh=mesh, in_specs=(P(None, None, "seq"),) * 3,
        out_specs=P(None, None, "seq")))
    out = ring(q, k, v)
    ref = mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=5e-2, rtol=5e-2)
