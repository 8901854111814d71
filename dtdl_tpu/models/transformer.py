"""Decoder-only Transformer language model (the long-context flagship).

The reference tops out at CNNs/MLPs over 784-pixel images (SURVEY §5.7 —
reference pytorch/model.py:53-118, chainer/train_mnist_multi.py:15-28); this
framework treats sequence models and long context as first-class, so the
model zoo gains a modern decoder-only LM:

* pre-norm blocks, RMSNorm, rotary position embeddings, SwiGLU MLP
* causal **flash attention** via the Pallas TPU kernel
  (dtdl_tpu/ops/attention.py); ``attn_impl='dense'`` selects the reference
  einsum path for numerics tests
* optional **mixture-of-experts** MLP — dense top-1 one-hot dispatch (the
  numerics oracle) or GShard-style routed capacity-factor top-k (the
  scale path: static-shape dispatch einsums GSPMD partitions over an
  'expert' mesh axis; see :class:`MoE`)
* every parameter is annotated with flax *logical axes* so the same module
  runs replicated, FSDP, or tensor-parallel under pjit by flipping the
  logical→mesh rules (dtdl_tpu/parallel/tensor.py)
* ``remat`` applies ``jax.checkpoint`` per block — the standard TPU
  memory/FLOPs trade for long sequences; what each block keeps beside its
  input is planned from shapes and the device's memory limit
  (models/remat_plan.py), nothing where neither is known

Logical axis names: 'vocab', 'embed', 'heads', 'head_dim' (attention
projections), 'mlp' (FFN hidden), 'expert' (MoE).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dtdl_tpu.models import remat_plan
from dtdl_tpu.ops.attention import (band_tiles, flash_attention,
                                    mha_reference)
from dtdl_tpu.ops.gated_delta import gated_delta_rule, kda_rule, stage_plan
from dtdl_tpu.ops.grouped_matmul import (
    ROW_TILE, first_buffer_rows, grouped_matmul, held_buffer_rows, rows_of,
    weighted_rows_sum)
from dtdl_tpu.ops.paged_attention import paged_attention
from dtdl_tpu.ops.rope import apply_rope, rope_frequencies
from dtdl_tpu.quant import (QuantDenseGeneral, canon_kv_dtype, kv_quantize,
                            kv_scale_dtype, weight_dtypes)
from dtdl_tpu.runtime.compile_cache import (record_expert_buffer,
                                            record_gdn_path,
                                            record_kda_path,
                                            record_remat_plan,
                                            record_window_call)

Dtype = Any


class CacheOverflowError(ValueError):
    """Decode would write past the KV cache / rope table (``max_seq``).

    Raised eagerly whenever the cache index is a concrete value (plain
    ``model.apply(..., mutable=['cache'])`` outside jit).  Inside a
    compiled program the index is a tracer and cannot be checked here —
    ``generate`` validates ``prompt + max_new_tokens <= max_seq`` before
    tracing, and the serving scheduler (dtdl_tpu/serve/scheduler.py)
    retires a slot the moment its sequence reaches ``cache_max_seq`` —
    without a caller-level guard the cache index would silently clamp
    into the last position and corrupt it.
    """


def cache_max_seq(cache) -> int:
    """The ``max_seq`` a KV cache was built for (its rope-table length).

    Reads the [.., max_seq, head_dim] K/V buffer shape, so it works on a
    live cache pytree, the ``jax.eval_shape`` result, or a serving arena.
    """
    for leaf in jax.tree.leaves(cache):
        if getattr(leaf, "ndim", 0) >= 3:
            return int(leaf.shape[-2])
    raise ValueError("no K/V buffers in cache pytree")


def _part(init, *names):
    return nn.with_logical_partitioning(init, names)


def _required_cache_leaf(name):
    """Init fn for cache leaves the caller must supply (the paged and
    int8 arena layouts are built by the serving engine's init helpers,
    never by an init trace): if flax falls back to initializing one, the
    cache pytree was malformed — fail with the diagnosis instead of
    allocating a silent zero."""
    def init(*_):
        raise ValueError(
            f"KV cache is missing the '{name}' leaf; build the arena "
            f"with TransformerLM.init_cache/init_paged_cache (the "
            f"serving engine inserts any per-call page_table/active "
            f"leaves itself — dtdl_tpu/serve/engine.py)")
    return init


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Dtype = jnp.float32
    # ``x_hat * (1 + scale)`` with the scale drawn around 0, in place of
    # ``x_hat * scale`` around 1
    zero_centered: bool = False
    axis_name: str = "embed"      # logical axis of the scale

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.zero_centered \
            else nn.initializers.ones
        scale = self.param("scale", _part(init, self.axis_name),
                           (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        if self.zero_centered:
            scale = 1.0 + scale
        return (norm * scale).astype(self.dtype)


class Attention(nn.Module):
    n_heads: int
    head_dim: int
    attn_impl: str = "flash"      # 'flash' | 'dense'
    dtype: Dtype = jnp.bfloat16
    quantize: Any = False         # weight-only projections (serve):
    #                               True/'int8' -> int8, 'w8f' -> fp8
    paged_kernel: bool = False    # Pallas paged attend (kernel round 2)
    # grouped-query / gated attention (all off: the module above, bit for
    # bit).  ``n_kv_heads`` K/V heads each serve ``n_heads // n_kv_heads``
    # query heads; ``rope_dims`` rotates only the head's first dims (the
    # rotation then runs outside the kernel, whose fused one turns the
    # whole head); ``qk_norm`` is an RMSNorm over the head on q and k;
    # ``gate`` doubles the q projection and multiplies the attention's
    # output by the sigmoid of the second half; ``gate='own'`` takes the
    # gate from a projection of its own (``gate_proj``) instead.
    # ``window`` (0: none): a query sees the ``window`` keys that end with
    # its own (ops/attention.py's band); ``rotate=False`` leaves q and k
    # unrotated (``rope_dims=0`` means the whole head, so "none" is a field
    # of its own); ``norm_eps`` is the head norms'.
    n_kv_heads: int = 0
    rope_dims: int = 0
    qk_norm: bool = False
    gate: Any = False
    norm_zero_centered: bool = False
    window: int = 0
    rotate: bool = True
    norm_eps: float = 1e-6

    @property
    def grouped(self) -> bool:
        return bool(self.n_kv_heads or self.rope_dims or self.qk_norm
                    or self.gate or self.window or not self.rotate)

    @nn.compact
    def __call__(self, x, cos, sin, decode: bool = False):
        if self.grouped:
            return self._grouped_attend(x, cos, sin, decode)
        d_model = x.shape[-1]
        def proj(name):
            if self.quantize:
                # same module path + 'kernel' param name as the f32
                # layer, so quantize_params maps tree-to-tree
                return QuantDenseGeneral(
                    features=(self.n_heads, self.head_dim), axis=-1,
                    dtype=self.dtype, mode=self.quantize, name=name)
            return nn.DenseGeneral(
                features=(self.n_heads, self.head_dim), axis=-1,
                use_bias=False, dtype=self.dtype,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  "embed", "heads", "head_dim"),
                name=name)
        q = proj("q")(x)
        k = proj("k")(x)
        v = proj("v")(x)
        # batched multi-LoRA (round 22): when the engine passes a 'lora'
        # collection, every projection gains a low-rank delta gathered
        # from the adapter bank by each row's adapter id — per-slot DATA
        # (dtdl_tpu/serve/tenant/lora.py), so one compiled step serves a
        # mixed-adapter batch.  Absent during the init trace and for
        # engines without a bank: params and programs are unchanged.
        lora = self.has_variable("lora", "q_a")
        if lora:
            aid = self.get_variable("lora", "aid")           # [B] int32

            def lo_delta(name, h):
                a = jnp.take(self.get_variable("lora", f"{name}_a"),
                             aid, axis=0)                    # [B, d, r]
                bb = jnp.take(self.get_variable("lora", f"{name}_b"),
                              aid, axis=0)                   # [B, r, H, D]
                t = jnp.einsum("bsd,bdr->bsr", x.astype(a.dtype), a)
                return h + jnp.einsum("bsr,brhe->bshe", t,
                                      bb).astype(h.dtype)
            q, k, v = lo_delta("q", q), lo_delta("k", k), lo_delta("v", v)
        # [B, S, H, D] -> [B, H, S, D]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if decode:
            o = self._decode_attend(q, k, v, cos, sin)
        elif self.attn_impl == "flash":
            # fused rope (round 13): the rotation rides the kernel's Q/K
            # tile loads instead of round-tripping [B, H, S, D] through
            # HBM per layer (ops/attention.py); block shapes resolve from
            # the static autotune table keyed on (head_dim, seq, causal)
            o = flash_attention(q, k, v, causal=True, rope=(cos, sin))
        else:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            o = mha_reference(q, k, v, causal=True).astype(self.dtype)
        o = o.transpose(0, 2, 1, 3)
        if self.quantize:
            out = QuantDenseGeneral(
                features=d_model, axis=(-2, -1), dtype=self.dtype,
                mode=self.quantize, name="out")(o)
        else:
            out = nn.DenseGeneral(
                features=d_model, axis=(-2, -1), use_bias=False,
                dtype=self.dtype,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  "heads", "head_dim", "embed"),
                name="out")(o)
        out = checkpoint_name(out, remat_plan.ATTN_OUT)
        if lora:
            a = jnp.take(self.get_variable("lora", "out_a"),
                         aid, axis=0)                        # [B, H, D, r]
            bb = jnp.take(self.get_variable("lora", "out_b"),
                          aid, axis=0)                       # [B, r, d]
            t = jnp.einsum("bshe,bher->bsr", o.astype(a.dtype), a)
            out = out + jnp.einsum("bsr,brd->bsd", t, bb).astype(out.dtype)
        return out

    def _grouped_attend(self, x, cos, sin, decode):
        """Grouped-query heads with partial rotary, q/k norm and an output
        gate (class fields).  K and V are repeated to the query heads
        before the flash kernels, which take as many K/V heads as Q heads,
        and the rotation is applied outside them: both are copies a later
        change can remove."""
        if decode or self.quantize:
            raise NotImplementedError(
                "grouped-query / gated attention trains only: decoding "
                "through a KV cache and weight-only serving of it are "
                "missing (Attention._decode_attend takes as many K/V "
                "heads as Q heads and rotates the whole head"
                + (f"; a windowed layer (window={self.window}) also needs "
                   f"a cache that holds its last {self.window} keys alone, "
                   f"a page lifetime of its own in serve/paged.py"
                   if self.window else "") + ")")
        d_model = x.shape[-1]
        h, d = self.n_heads, self.head_dim
        kv = self.n_kv_heads or h
        if h % kv:
            raise ValueError(f"{h} query heads over {kv} K/V heads")

        def proj(name, heads, width):
            return nn.DenseGeneral(
                features=(heads, width), axis=-1, use_bias=False,
                dtype=self.dtype,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  "embed", "heads", "head_dim"),
                name=name)(x)

        own_gate = self.gate == "own"
        q = proj("q", h, 2 * d if self.gate and not own_gate else d)
        gate = None
        if own_gate:
            # kept with the out projection's output (rung 2: every
            # projection of the layer goes)
            gate = checkpoint_name(proj("gate_proj", h, d),
                                   remat_plan.ATTN_OUT)
        elif self.gate:
            q, gate = q[..., :d], q[..., d:]
        k, v = proj("k", kv, d), proj("v", kv, d)
        if self.qk_norm:
            def head_norm(name):
                return RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                               axis_name="head_dim",
                               zero_centered=self.norm_zero_centered,
                               name=name)
            q, k = head_norm("q_norm")(q), head_norm("k_norm")(k)
        # [B, S, H, D] -> [B, H, S, D]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if self.rotate:
            r = self.rope_dims or d
            q = jnp.concatenate(
                [apply_rope(q[..., :r], cos, sin), q[..., r:]], axis=-1)
            k = jnp.concatenate(
                [apply_rope(k[..., :r], cos, sin), k[..., r:]], axis=-1)
        k, v = (jnp.repeat(t, h // kv, axis=1) for t in (k, v))
        banded = {"window": self.window} if self.window else {}
        if self.attn_impl == "flash":
            step_name = remat_plan.traced_step_name()
            if self.window and step_name is not None:
                record_window_call(
                    step_name, (q.shape[0], h) + q.shape[2:],
                    band_tiles(q.shape[2], k.shape[2], d, self.window))
            o = flash_attention(q, k, v, causal=True, **banded)
        else:
            o = mha_reference(q, k, v, causal=True,
                              **banded).astype(self.dtype)
        o = o.transpose(0, 2, 1, 3)
        if gate is not None:
            with jax.named_scope("gate"):
                o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                    o.dtype)
        out = nn.DenseGeneral(
            features=d_model, axis=(-2, -1), use_bias=False,
            dtype=self.dtype,
            kernel_init=_part(nn.initializers.lecun_normal(),
                              "heads", "head_dim", "embed"),
            name="out")(o)
        return checkpoint_name(out, remat_plan.ATTN_OUT)

    # prefill query rows are processed in blocks of this many: peak
    # attention memory stays O(chunk * max_seq) instead of the
    # O(prompt * max_seq) f32 logits a one-shot dense prefill would
    # materialize per layer — the same memory bound the flash kernel
    # gives training (advisor finding, round 4)
    PREFILL_CHUNK = 256

    def _decode_attend(self, q, k, v, cos, sin):
        """Incremental attention against a KV cache ('cache' collection).

        Serves both prefill (S = prompt length) and stepping (S = 1): the
        new keys/values land at positions [index, index+S) of a
        [B, H, max_seq, D] cache (max_seq = the rope table length), the
        rope rotation uses the true global positions, and each new query
        row attends every cached position up to and including its own.
        Dense masked attention — decode is one query row against a cache,
        which is exactly the memory-light shape the flash kernel's tiling
        is NOT for; long prefills are chunked over query rows
        (``PREFILL_CHUNK``) to keep the same O(seq) memory bound.
        Mutate via ``apply(..., mutable=['cache'])``.

        The cache ``index`` may be a scalar (every row at the same
        position — the ``generate`` path) or a **[B] vector of per-row
        positions** (the serving arena: each batch row is an independent
        slot at its own decode position, so one compiled step serves a
        continuously-batched mix of sequence lengths).  The vector path
        takes S >= 1 tokens per row (:meth:`_verify_attend_slots`): S = 1
        is the decode step, S = k+1 the speculative-decoding verify pass
        — prefill happens per slot at scalar index and is scattered into
        the arena by the engine (dtdl_tpu/serve/engine.py).
        """
        import math
        b, h, s_new, d = q.shape
        max_len = cos.shape[0]
        if s_new > max_len:
            raise CacheOverflowError(
                f"{s_new} new tokens cannot fit a max_seq={max_len} "
                f"KV cache/rope table")
        # block-paged arena (cache built by init_paged_cache, page
        # tables inserted per call by the serving engine): route before
        # the dense declarations below can allocate [B, max_seq] buffers
        if self.has_variable("cache", "pages_key"):
            return self._paged_attend_slots(q, k, v, cos, sin)
        # has_variable BEFORE self.variable: during the init trace the
        # cache does not exist yet, and mutating it there would bake the
        # example input into the returned cache and leave index=1 — every
        # later position would be off by one
        cache_exists = self.has_variable("cache", "key")
        # int8 KV layout (init_cache(kv_dtype='int8')): the cache pytree
        # itself carries the layout — scale leaves present means the K/V
        # buffers are int8 and every write quantizes / every read
        # dequants in-kernel.  Data-driven like the paged routing above,
        # so the SAME module serves both layouts (one compiled program
        # per engine either way; the engine never mixes layouts).
        quant = self.has_variable("cache", "key_scale")
        ck = self.variable("cache", "key", jnp.zeros,
                           (b, h, max_len, d), self.dtype)
        cv = self.variable("cache", "value", jnp.zeros,
                           (b, h, max_len, d), self.dtype)
        cks = cvs = None
        if quant:
            cks = self.variable("cache", "key_scale",
                                _required_cache_leaf("key_scale"))
            cvs = self.variable("cache", "value_scale",
                                _required_cache_leaf("value_scale"))
        ci = self.variable("cache", "index",
                           lambda: jnp.zeros((), jnp.int32))
        if not cache_exists:
            # this IS the init trace: shapes only, no cache mutation
            return jnp.zeros_like(q)
        pos = ci.value
        if not isinstance(pos, jax.core.Tracer):
            # eager decode: the index is concrete, so overflow is
            # checkable HERE instead of silently clamping the write into
            # the last cache row (jitted callers must bound-check before
            # tracing — see CacheOverflowError)
            # audit: ok[host-sync-float] eager-only overflow check — jitted callers never reach this branch
            limit = int(jnp.max(pos)) if pos.ndim else int(pos)
            if limit + s_new > max_len:
                raise CacheOverflowError(
                    f"decode at position {limit} with {s_new} new "
                    f"token(s) exceeds max_seq={max_len}; the cache "
                    f"index would clamp and corrupt the last row")
        if pos.ndim:
            return self._verify_attend_slots(q, k, v, cos, sin,
                                             ck, cv, ci, pos, cks, cvs)
        q = apply_rope(q, cos, sin, offset=pos)
        k = apply_rope(k, cos, sin, offset=pos)
        if quant:
            # quantize-on-scatter: each new position's K/V row is scaled
            # off its own max (write-once — see quant.kv_quantize); the
            # cache leaf's dtype picks the payload (int8 or fp8)
            k8, ks = kv_quantize(k, dtype=ck.value.dtype)
            v8, vs = kv_quantize(v, dtype=cv.value.dtype)
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k8, (0, 0, pos, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v8, (0, 0, pos, 0))
            cks.value = jax.lax.dynamic_update_slice(
                cks.value, ks, (0, 0, pos))
            cvs.value = jax.lax.dynamic_update_slice(
                cvs.value, vs, (0, 0, pos))
        else:
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k.astype(self.dtype), (0, 0, pos, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v.astype(self.dtype), (0, 0, pos, 0))
        ci.value = pos + s_new

        keys, values = ck.value, cv.value
        scale = 1.0 / math.sqrt(d)

        def attend(q_rows, qpos):
            """[B, H, C, D] query rows at global positions qpos [C]."""
            mask = jnp.arange(max_len)[None, :] <= qpos[:, None]
            if quant:
                # dequant-on-gather, fused: the int8→dtype convert rides
                # the einsum's operand read, the per-position key scale
                # multiplies the [.., K] logits (constant along the
                # contracted D, so this IS the dequantized matmul), and
                # the value scale folds into the softmax weights — no
                # dequantized [.., D] copy is ever materialized
                logits = jnp.einsum("bhqd,bhkd->bhqk", q_rows,
                                    keys.astype(self.dtype),
                                    preferred_element_type=jnp.float32)
                logits = logits * cks.value[:, :, None, :]
            else:
                logits = jnp.einsum("bhqd,bhkd->bhqk", q_rows, keys,
                                    preferred_element_type=jnp.float32)
            logits = jnp.where(mask[None, None], logits * scale, -1e30)
            probs = jax.nn.softmax(logits, axis=-1)
            if quant:
                w = (probs * cvs.value[:, :, None, :]).astype(self.dtype)
                return jnp.einsum("bhqk,bhkd->bhqd", w,
                                  values.astype(self.dtype))
            return jnp.einsum("bhqk,bhkd->bhqd",
                              probs.astype(self.dtype), values)

        chunk = self.PREFILL_CHUNK
        if s_new <= chunk:
            return attend(q, pos + jnp.arange(s_new))
        # long prefill: pad the query rows to a chunk multiple and map
        # over [n_chunks, B, H, chunk, D] blocks — the pad rows compute
        # garbage (masked to a uniform softmax) and are sliced away
        pad = -s_new % chunk
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        n_chunks = (s_new + pad) // chunk
        q_blocks = jnp.moveaxis(
            qp.reshape(b, h, n_chunks, chunk, d), 2, 0)
        pos_blocks = (pos + jnp.arange(s_new + pad)).reshape(
            n_chunks, chunk)
        out = jax.lax.map(lambda args: attend(*args),
                          (q_blocks, pos_blocks))
        out = jnp.moveaxis(out, 0, 2).reshape(b, h, s_new + pad, d)
        return out[:, :, :s_new]

    def _verify_attend_slots(self, q, k, v, cos, sin, ck, cv, ci, pos,
                             cks=None, cvs=None):
        """Vector-index cached attention, ``s_new`` tokens per slot: row b
        is an independent slot whose new tokens sit at global positions
        ``pos[b] .. pos[b]+s_new-1``.  Same math as the scalar path per
        row — rope at each token's own global position, K/V scattered
        into the row's cache at ``pos[b]``, causal mask per query row —
        so scoring k candidate positions in one pass is token-identical
        to k sequential single-token decodes (pinned by
        tests/test_spec_decode.py; ``s_new=1`` is exactly the decode step
        the serving engine compiles, pinned by tests/test_serve.py).

        This is the verify half of speculative decoding: one parameter
        sweep scores ``s_new`` candidate tokens per slot against the KV
        arena (dtdl_tpu/serve/engine.py builds the accept/advance logic
        on top).  The index advances by the full ``s_new``; a caller that
        commits fewer tokens (rejected candidates) rolls the index leaves
        back itself — the overwritten-before-attended discipline makes
        the stale K/V rows beyond the committed index harmless, exactly
        like prefill's pad positions.

        Callers must guarantee ``pos[b] + s_new <= max_seq`` for every
        row that matters: the per-row scatter clamps its start index, so
        an overflowing write would land misaligned over live positions
        (jitted callers bound-check before tracing — the serving
        scheduler settles worst-case indices before dispatch; eager
        callers are checked in ``_decode_attend``).
        """
        import math
        b, h, s_new, d = q.shape
        max_len = cos.shape[0]
        quant = cks is not None
        rope_row = jax.vmap(
            lambda xb, p: apply_rope(xb[None], cos, sin, offset=p)[0])
        q = rope_row(q, pos)
        k = rope_row(k, pos)
        scatter_row = jax.vmap(
            lambda buf, new, p: jax.lax.dynamic_update_slice(
                buf, new, (0, p, 0)))
        if quant:
            # quantize-on-scatter, per (row, head, position) — the same
            # write-once discipline as the scalar path (quant.kv_quantize)
            k8, ks = kv_quantize(k, dtype=ck.value.dtype)
            v8, vs = kv_quantize(v, dtype=cv.value.dtype)
            ck.value = scatter_row(ck.value, k8, pos)
            cv.value = scatter_row(cv.value, v8, pos)
            scatter_s = jax.vmap(
                lambda buf, new, p: jax.lax.dynamic_update_slice(
                    buf, new, (0, p)))
            cks.value = scatter_s(cks.value, ks, pos)
            cvs.value = scatter_s(cvs.value, vs, pos)
        else:
            ck.value = scatter_row(ck.value, k.astype(self.dtype), pos)
            cv.value = scatter_row(cv.value, v.astype(self.dtype), pos)
        ci.value = pos + s_new

        scale = 1.0 / math.sqrt(d)
        qpos = pos[:, None] + jnp.arange(s_new)[None, :]        # [B, S]
        mask = (jnp.arange(max_len)[None, None, :]
                <= qpos[:, :, None])                            # [B, S, max]
        if quant:
            # dequant-on-gather, fused exactly like the scalar path: the
            # int8→dtype convert rides the einsum operand read, the key
            # scale multiplies the [.., K] logits, the value scale folds
            # into the softmax weights
            logits = jnp.einsum("bhqd,bhkd->bhqk", q,
                                ck.value.astype(self.dtype),
                                preferred_element_type=jnp.float32)
            logits = logits * cks.value[:, :, None, :]
        else:
            logits = jnp.einsum("bhqd,bhkd->bhqk", q, ck.value,
                                preferred_element_type=jnp.float32)
        logits = jnp.where(mask[:, None], logits * scale, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        if quant:
            w = (probs * cvs.value[:, :, None, :]).astype(self.dtype)
            return jnp.einsum("bhqk,bhkd->bhqd", w,
                              cv.value.astype(self.dtype))
        return jnp.einsum("bhqk,bhkd->bhqd",
                          probs.astype(self.dtype), cv.value)

    def _paged_attend_slots(self, q, k, v, cos, sin):
        """The vector-index cached attend (:meth:`_verify_attend_slots`)
        generalized to a **block-paged** KV arena: instead of row b
        owning a contiguous ``[max_seq]`` cache row, its positions map
        through a per-row page table onto a shared pool of
        ``page_size``-token pages (``pages_key``/``pages_value``
        ``[n_pages, H, page_size, D]``), so a short sequence pins only
        the pages it has reached.  Per row the math is IDENTICAL to the
        dense vector path — rope at each token's true global position,
        K/V scattered at ``pos[b] .. pos[b]+s_new-1`` (now through the
        table), causal mask per query row over the gathered logical view
        — which is what keeps paged decode/verify token-identical to the
        dense arena (tests/test_paged_kv.py).  ``s_new`` spans the same
        three shapes: prefill (B=1, S=suffix bucket, index=#cached
        prefix tokens), decode (S=1), speculative verify (S=k+1).

        Cache leaves: ``pages_key``/``pages_value`` (the pool),
        ``index`` [B] — the arena the engine donates — plus two
        **per-call data leaves** the engine inserts before ``apply`` and
        strips after: ``page_table`` [B, n_ptab] int32 (logical page ->
        physical page; unmapped entries point at the reserved garbage
        page 0) and ``active`` [B] bool.  Page tables are data, never
        shapes: remapping pages or changing occupancy reuses the same
        compiled program.

        The one discipline the dense path did not need: an INACTIVE
        row's write is explicitly routed to the garbage page.  Dense
        slots write garbage into their *own* row (harmless); a paged
        slot's stale table may point at pages long since freed and
        remapped to a live request, so writes gate on ``active``.
        Positions of garbage rows are also clamped before they index
        the rope/page tables — out-of-range stale indices must produce
        discarded garbage, not NaNs that a masked-but-gathered page
        could leak into a live row's softmax·V sum (0 · NaN = NaN).

        Callers guarantee, for every ACTIVE row, ``pos[b] + s_new <=
        max_seq`` and a table mapping every logical page up to that
        bound (the serving scheduler allocates pages from the same
        worst-case index tracking it already settles overflow with).
        """
        import math
        b, h, s_new, d = q.shape
        max_len = cos.shape[0]
        pk = self.variable("cache", "pages_key",
                           _required_cache_leaf("pages_key"))
        pv = self.variable("cache", "pages_value",
                           _required_cache_leaf("pages_value"))
        pt = self.variable("cache", "page_table",
                           _required_cache_leaf("page_table"))
        act = self.variable("cache", "active",
                            _required_cache_leaf("active"))
        ci = self.variable("cache", "index",
                           _required_cache_leaf("index"))
        # int8 pools (init_paged_cache(kv_dtype='int8')): per-(page,
        # head, in-page position) scales ride WITH their page through
        # the same table — layout is data, same compiled program shape
        quant = self.has_variable("cache", "pages_key_scale")
        pks = pvs = None
        if quant:
            pks = self.variable("cache", "pages_key_scale",
                                _required_cache_leaf("pages_key_scale"))
            pvs = self.variable("cache", "pages_value_scale",
                                _required_cache_leaf("pages_value_scale"))
        pos, table, active = ci.value, pt.value, act.value
        n_pages, H, page, D = pk.value.shape
        n_ptab = table.shape[1]
        if not isinstance(pos, jax.core.Tracer):
            # eager misuse check, mirroring the dense vector path (the
            # serving engine always runs this jitted and bound-checks
            # host-side before dispatch)
            live = jnp.where(jnp.asarray(active), jnp.asarray(pos), 0)
            # audit: ok[host-sync-float] eager-only overflow check — jitted callers never reach this branch
            if int(jnp.max(live)) + s_new > max_len:
                raise CacheOverflowError(
                    # audit: ok[host-sync-float] eager-only overflow check — jitted callers never reach this branch
                    f"paged decode at position {int(jnp.max(live))} with "
                    f"{s_new} new token(s) exceeds max_seq={max_len}")
        # clamped positions: identity for active rows (caller contract),
        # keeps stale inactive rows inside every table (see docstring)
        pos_safe = jnp.clip(pos, 0, max_len - s_new)
        rope_row = jax.vmap(
            lambda xb, p: apply_rope(xb[None], cos, sin, offset=p)[0])
        q = rope_row(q, pos_safe)
        k = rope_row(k, pos_safe)

        # (page, offset) scatter coordinates for the S new tokens,
        # computed ONCE per step and shared by every pool leaf — K, V
        # and (int8) their scale siblings (the PR 6 known-remaining:
        # the old path flattened/unflattened the ENTIRE pool around
        # every leaf's scatter — two full-pool transposes per leaf per
        # decode step; scattering straight onto the (page, offset) axes
        # leaves the pool layout untouched, and the gather stays
        # page-granular so XLA moves contiguous [H, page, D] chunks).
        # Token t of row b lands at offset g%page of physical page
        # table[b, g//page]; inactive rows route to garbage page 0.
        g = pos_safe[:, None] + jnp.arange(s_new)[None, :]       # [B, S]
        phys = jnp.take_along_axis(
            table, jnp.clip(g // page, 0, n_ptab - 1), axis=1)   # [B, S]
        page_idx = jnp.where(active[:, None], phys, 0).reshape(-1)
        off_idx = (g % page).reshape(-1)

        def update_and_view(pool, new):
            """Scatter ``new`` [B,H,S,...] onto the shared (page_idx,
            off_idx) coordinates and gather the [B,H,n_ptab*page,...]
            logical view; returns (pool', view)."""
            if pool.ndim == 4:
                upd = new.transpose(0, 2, 1, 3).reshape(b * s_new, H, D)
                pool = pool.at[page_idx, :, off_idx, :].set(
                    upd.astype(pool.dtype))
                pages = jnp.take(pool, table, axis=0)
                gat = pages.transpose(0, 2, 1, 3, 4).reshape(
                    b, H, n_ptab * page, D)
            else:
                upd = new.transpose(0, 2, 1).reshape(b * s_new, H)
                pool = pool.at[page_idx, :, off_idx].set(upd)
                pages = jnp.take(pool, table, axis=0)
                gat = pages.transpose(0, 2, 1, 3).reshape(
                    b, H, n_ptab * page)
            return pool, gat

        if quant:
            # quantize-on-scatter through the SAME (page, offset)
            # coordinates: each new position's K/V row is scaled off its
            # own max, so append-only shared pages never need rescaling
            k, ks = kv_quantize(k, dtype=pk.value.dtype)
            v, vs = kv_quantize(v, dtype=pv.value.dtype)

        scale = 1.0 / math.sqrt(d)
        if self.paged_kernel:
            # kernel round 2: scatter-only pool updates (no gathered
            # [B, H, n_ptab*page, D] view exists), then the Pallas
            # paged-attention kernel walks the table itself — page-
            # granular DMAs with the scale fusion folded into the tile
            # loads (dtdl_tpu/ops/paged_attention.py)
            def scatter(pool, new):
                if pool.ndim == 4:
                    upd = new.transpose(0, 2, 1, 3).reshape(
                        b * s_new, H, D)
                    return pool.at[page_idx, :, off_idx, :].set(
                        upd.astype(pool.dtype))
                upd = new.transpose(0, 2, 1).reshape(b * s_new, H)
                return pool.at[page_idx, :, off_idx].set(
                    upd.astype(pool.dtype))

            if quant:
                pks.value = scatter(pks.value, ks)
                pvs.value = scatter(pvs.value, vs)
            pk.value = scatter(pk.value, k)
            pv.value = scatter(pv.value, v)
            ci.value = pos + s_new   # engine masks/rolls back, as dense
            return paged_attention(
                q, pk.value, pv.value, table, pos_safe, active,
                scale=scale,
                key_scale=pks.value if quant else None,
                value_scale=pvs.value if quant else None)

        if quant:
            pks.value, kss = update_and_view(pks.value, ks)
            pvs.value, vss = update_and_view(pvs.value, vs)
        pk.value, keys = update_and_view(pk.value, k)
        pv.value, values = update_and_view(pv.value, v)
        ci.value = pos + s_new   # engine masks/rolls back, as dense

        qpos = pos_safe[:, None] + jnp.arange(s_new)[None, :]    # [B, S]
        mask = (jnp.arange(n_ptab * page)[None, None, :]
                <= qpos[:, :, None])                     # [B, S, n_ptab*pg]
        if quant:
            # dequant-on-gather, fused as in the dense paths: int8
            # pages convert inside the einsum read, the key scale (the
            # same gathered logical view as the pages, through the same
            # shared offsets) multiplies the [.., K] logits, the value
            # scale folds into the softmax weights — garbage-page
            # positions carry scale 0 or stale finite values, masked
            # exactly like their K/V
            logits = jnp.einsum("bhqd,bhkd->bhqk", q,
                                keys.astype(self.dtype),
                                preferred_element_type=jnp.float32)
            logits = logits * kss[:, :, None, :]
        else:
            logits = jnp.einsum("bhqd,bhkd->bhqk", q, keys,
                                preferred_element_type=jnp.float32)
        logits = jnp.where(mask[:, None], logits * scale, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        if quant:
            w = (probs * vss[:, :, None, :]).astype(self.dtype)
            return jnp.einsum("bhqk,bhkd->bhqd", w,
                              values.astype(self.dtype))
        return jnp.einsum("bhqk,bhkd->bhqd",
                          probs.astype(self.dtype), values)


class SwiGLU(nn.Module):
    d_ff: int
    dtype: Dtype = jnp.bfloat16
    quantize: Any = False         # weight-only wi/wg/wo (serve):
    #                               True/'int8' -> int8, 'w8f' -> fp8

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        if self.quantize:
            # same module paths + 'kernel' param names as the f32
            # layers, so quantize_params maps tree-to-tree
            def dense(features, name):
                return QuantDenseGeneral(features=features, axis=-1,
                                         dtype=self.dtype,
                                         mode=self.quantize, name=name)
        else:
            def dense(features, name):
                # wo is the row-parallel projection whatever the
                # geometry — key the partition names off the param,
                # not the feature count (d_ff == d_model would flip it)
                names = (("mlp", "embed") if name == "wo"
                         else ("embed", "mlp"))
                return nn.Dense(
                    features, use_bias=False, dtype=self.dtype,
                    kernel_init=_part(nn.initializers.lecun_normal(),
                                      *names), name=name)
        wi = checkpoint_name(dense(self.d_ff, "wi")(x), remat_plan.MLP_UP)
        wg = checkpoint_name(dense(self.d_ff, "wg")(x), remat_plan.MLP_UP)
        h = nn.silu(wg) * wi
        return dense(d_model, "wo")(h)


class MoE(nn.Module):
    """Mixture-of-experts MLP with two XLA-friendly dispatch modes.

    ``dispatch='dense'`` (the numerics oracle): top-1 routing through a
    one-hot einsum — every device computes every expert's einsum over all
    tokens, O(E · tokens · D · F) FLOPs.  Fine for tests and small E;
    useless at scale.

    ``dispatch='routed'`` (the GSPMD scale path): GShard-style
    capacity-factor top-k.  Tokens are split into routing groups of up
    to ``group_size`` consecutive tokens (1024 default — the measured
    knee; ragged tails padded and masked out of routing), each group
    getting ``C = ceil(cf · g · k / E)`` slots per expert; assignments
    fill choice-major (every first choice before any second choice,
    matching the megatron engine's routed dispatch,
    parallel/megatron.py:286-392), tokens past capacity are dropped
    (their residual passes through).  Dispatch/combine are one-hot
    einsums to a fixed [E, n_groups, C, D] expert buffer — static shapes
    throughout, so under the 'ep' logical rules (parallel/tensor.py) the
    expert dim shards on 'model' and XLA's partitioner inserts the token
    all-to-all; expert FFN FLOPs drop to O(cf · k · tokens · D · F),
    E-independent.

    Both modes share identical parameters (router/wi/wg/wo), so a dense
    checkpoint loads into a routed model and, with ``capacity_factor >=
    n_experts / top_k`` (nothing droppable), routed computes the same
    function as dense top-1 — the oracle-equality contract the tests pin.

    A Switch load-balance aux (E · <f, p>, first-choice counts) is
    stashed via ``self.sow`` under 'aux_loss'; the LM train step adds it
    to the loss (train/step.py:make_lm_train_step).
    """
    n_experts: int
    d_ff: int
    dtype: Dtype = jnp.bfloat16
    dispatch: str = "dense"       # 'dense' | 'routed'
    capacity_factor: float = 1.25
    top_k: int = 1
    # routing-group CAP (tokens): the dispatch/combine one-hot einsums
    # cost O(tokens · E · C · D) with C = cf·g·k/E, i.e. O(tokens · g)
    # per token — groups bound g the way GShard does, instead of paying
    # the whole sequence length.  Groups are g consecutive tokens within
    # a batch row; a ragged tail is padded and the pad tokens are
    # excluded from routing (they take no capacity).  0 = the measured
    # default cap of 1024
    group_size: int = 0
    # weight-only expert wi/wg/wo (serve): per-(expert, output channel)
    # scales, True/'int8' int8 or 'w8f' fp8; the router stays f32 (O(d)
    # bytes, high sensitivity — dtdl_tpu/quant/core.py)
    quantize: Any = False

    @nn.compact
    def __call__(self, x):
        if not 1 <= self.top_k <= self.n_experts:
            # same guard as the megatron engine's MegatronConfig: top_k=0
            # would silently zero every MoE output, top_k > E dies deep in
            # lax.top_k with an opaque trace error
            raise ValueError(f"top_k={self.top_k} must be in "
                             f"[1, n_experts={self.n_experts}]")
        if self.dispatch == "dense" and self.top_k != 1:
            # dense dispatch is top-1 by construction; silently training
            # top-1 when the user asked for top-2 would be invisible
            raise ValueError("dense dispatch is top-1 only; top_k="
                             f"{self.top_k} requires dispatch='routed'")
        b, s, d_model = x.shape
        router = nn.Dense(self.n_experts, use_bias=False, dtype=jnp.float32,
                          kernel_init=_part(nn.initializers.lecun_normal(),
                                            "embed", "expert"),
                          name="router")(x.astype(jnp.float32))
        probs = jax.nn.softmax(router, axis=-1)          # [b, s, E]
        onehot1 = jax.nn.one_hot(jnp.argmax(probs, axis=-1),
                                 self.n_experts, dtype=jnp.float32)

        # load-balance aux loss (Switch Transformer): E * <f, p> over the
        # first choice — identical formula for both dispatch modes
        self.sow("aux_loss", "moe",
                 self.n_experts * jnp.sum(onehot1.mean(axis=(0, 1))
                                          * probs.mean(axis=(0, 1))))

        def expert_param(name, shape, in_ax, out_ax):
            if self.quantize:
                # quantized kernel + per-(expert, output-channel) scale,
                # with the same param name (+ '_scale' sibling) so
                # quantize_params maps tree-to-tree; placeholder values
                # — a quantized model is served, never trained
                payload_dt, scale_dt = weight_dtypes(self.quantize)
                q = self.param(name,
                               lambda *_: jnp.zeros(shape, payload_dt))
                s = self.param(
                    f"{name}_scale",
                    lambda *_: jnp.ones((shape[0], 1, shape[2]),
                                        scale_dt))
                return q.astype(self.dtype), s
            # batch_axis keeps the expert dim out of fan_in so every expert
            # initializes like its dense counterpart
            init = nn.initializers.lecun_normal(batch_axis=(0,))
            return self.param(
                name, _part(init, *(("expert",) + (in_ax, out_ax))),
                shape).astype(self.dtype), None

        w_in = expert_param("wi", (self.n_experts, d_model, self.d_ff),
                            "embed", "mlp")
        w_gate = expert_param("wg", (self.n_experts, d_model, self.d_ff),
                              "embed", "mlp")
        w_out = expert_param("wo", (self.n_experts, self.d_ff, d_model),
                             "mlp", "embed")

        if self.dispatch == "routed":
            return self._routed(x, probs, w_in, w_gate, w_out)
        if self.dispatch != "dense":
            raise ValueError(f"unknown MoE dispatch {self.dispatch!r}")

        gate = jnp.sum(probs * onehot1, axis=-1, keepdims=True)
        # dense dispatch: xe[e, b, s, d] = onehot[b, s, e] * x[b, s, d]
        xe = jnp.einsum("bse,bsd->ebsd", onehot1.astype(self.dtype), x)
        h = nn.silu(self._emm("ebsd,edf->ebsf", xe, w_gate)) * \
            self._emm("ebsd,edf->ebsf", xe, w_in)
        # quantized wo keeps the expert axis through the matmul (each
        # expert has its own output scale, which cannot factor out of a
        # cross-expert contraction) and sums after dequant; unquantized
        # stays the original single contraction bit-for-bit
        y = (jnp.sum(self._emm("ebsf,efd->ebsd", h, w_out), axis=0)
             if self.quantize else
             jnp.einsum("ebsf,efd->bsd", h, w_out[0]))
        return y * gate.astype(self.dtype)

    def _emm(self, spec, x, w):
        """Expert matmul over a ``(kernel, scale-or-None)`` pair: the
        per-(expert, out-channel) scale is constant along the contracted
        dims, so multiplying the e-leading rank-4 OUTPUT is exactly the
        dequantized matmul (same identity as
        dtdl_tpu/quant/layers.py:QuantDenseGeneral)."""
        kernel, scale = w
        y = jnp.einsum(spec, x, kernel)
        if scale is not None:
            y = (y * scale.reshape(scale.shape[0], 1, 1, -1)
                 .astype(jnp.float32)).astype(self.dtype)
        return y

    def _routed(self, x, probs, w_in, w_gate, w_out):
        """Capacity-factor top-k dispatch (see class docstring).

        Tokens are split into routing groups of up to ``group_size``
        consecutive tokens (GShard-style): capacity is per (batch row,
        group), so the [*, g, E, C] dispatch tensors stay O(g) per token
        instead of O(seq) — at seq 4096 / E 8 / cf 1.25 the ungrouped
        dispatch einsum alone would cost ~2x the expert FFN FLOPs.  A
        ragged last group is padded; pad tokens are masked out of the
        routing entirely (no capacity consumed, output sliced away), so
        any sequence length works — including single-token decode, where
        g=1 makes capacity a no-drop identity (inference never drops).
        Measured on the v5e ('base'+E8 forward, bs 8 seq 4096): dense
        dispatch 54.6 ms, routed ungrouped 45.1 ms, g=1024 **38.2 ms**,
        g=256 38.8 ms — the 1024 default cap is the measured knee."""
        import math
        b, s_full, d_model = x.shape
        g = min(self.group_size or 1024, s_full)
        pad = -s_full % g
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            probs = jnp.pad(probs, ((0, 0), (0, pad), (0, 0)))
        n_groups = b * ((s_full + pad) // g)
        # [g] validity per position of each row-group, tiled over rows
        valid = (jnp.arange(s_full + pad) < s_full).astype(jnp.float32)
        valid = jnp.tile(valid.reshape(-1, g), (b, 1))   # [n_groups, g]
        x = x.reshape(n_groups, g, d_model)
        probs = probs.reshape(n_groups, g, self.n_experts)
        b, s = n_groups, g
        E, k = self.n_experts, self.top_k
        C = min(s, int(math.ceil(self.capacity_factor * s * k / E)))

        gates, idx = jax.lax.top_k(probs, k)             # [b, s, k]
        if k > 1:
            # GShard-style renormalization over the chosen k (top-1 keeps
            # the raw softmax prob — Switch semantics, == dense mode)
            gates = gates / jnp.maximum(
                jnp.sum(gates, -1, keepdims=True), 1e-9)

        dispatch = jnp.zeros((b, s, E, C), jnp.float32)
        combine = jnp.zeros((b, s, E, C), jnp.float32)
        taken = jnp.zeros((b, 1, E), jnp.float32)        # slots used so far
        for j in range(k):                               # choice-major fill
            m = jax.nn.one_hot(idx[:, :, j], E,
                               dtype=jnp.float32) * valid[..., None]
            pos = jnp.cumsum(m, axis=1) - m + taken      # [b, s, E]
            keep = m * (pos < C)
            slot = jax.nn.one_hot(pos.astype(jnp.int32), C,
                                  dtype=jnp.float32)     # [b, s, E, C]
            d_j = keep[..., None] * slot
            dispatch = dispatch + d_j
            combine = combine + gates[:, :, j, None, None] * d_j
            taken = taken + jnp.sum(m, axis=1, keepdims=True)

        # [E, B, C, D] expert buffers: 'expert' leads so that, under a
        # caller-installed nn.logical_axis_rules context (e.g. the 'ep'
        # preset via make_sharded_lm_train_step), the constraint pins the
        # buffer's expert dim to its mesh axis and GSPMD inserts the
        # token all-to-all; with no context installed the constraint is
        # a no-op and the layout falls back to propagation from the
        # weight shardings
        xe = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(self.dtype), x)
        xe = nn.with_logical_constraint(
            xe, ("expert", "batch", None, "embed"))
        h = nn.silu(self._emm("ebcd,edf->ebcf", xe, w_gate)) * \
            self._emm("ebcd,edf->ebcf", xe, w_in)
        y = self._emm("ebcf,efd->ebcd", h, w_out)
        y = nn.with_logical_constraint(
            y, ("expert", "batch", None, "embed"))
        out = jnp.einsum("ebcd,bsec->bsd", y,
                         combine.astype(self.dtype))
        return out.reshape(-1, s_full + pad, d_model)[:, :s_full]


class DepthwiseConv(nn.Module):
    """Causal depthwise conv over the sequence, no bias: ``y_t = sum_j
    w_j x_{t - (width - 1) + j}`` a channel, zeros before the first token."""
    width: int = 4
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", _part(nn.initializers.normal(stddev=0.5), None, "mlp"),
            (self.width, x.shape[-1]))
        seq = x.shape[1]
        xp = jnp.pad(x.astype(self.dtype),
                     ((0, 0), (self.width - 1, 0), (0, 0)))
        taps = kernel.astype(self.dtype)
        return sum(taps[j] * xp[:, j:j + seq] for j in range(self.width))


class GatedDeltaNet(nn.Module):
    """Gated DeltaNet linear attention (arXiv:2412.06464), as Qwen3-Next
    lays it out: ``in_qkvz`` gives each of ``key_heads`` key heads its q, k
    (``key_dim``), and for the ``value_heads // key_heads`` value heads it
    serves v and the output gate z (``value_dim`` each); ``in_ba`` their
    write strength and decay inputs.  q, k, v pass a causal depthwise conv
    and SiLU; q and k are L2-normalised over the head, q scaled by
    ``key_dim ** -0.5``; ``beta = sigmoid(b)``, ``g = -exp(A_log) *
    softplus(a + dt_bias)`` in float32.  The rule itself is
    ops/gated_delta.py; its output passes an RMSNorm over the value head,
    times ``silu(z)``, and ``out``.  Trains only: decoding needs the
    recurrent state and the conv's tail beside the K/V cache."""
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_width: int = 4
    eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, d_model = x.shape
        hk, hv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        r = hv // hk
        if hv % hk:
            raise ValueError(f"{hv} value heads over {hk} key heads")

        def proj(name, width, dtype):
            return nn.DenseGeneral(
                features=(hk, width), axis=-1, use_bias=False, dtype=dtype,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  "embed", "heads", "head_dim"),
                name=name)

        qkvz = checkpoint_name(
            proj("in_qkvz", 2 * dk + 2 * r * dv, self.dtype)(x),
            remat_plan.GDN_IN)
        ba = proj("in_ba", 2 * r, jnp.float32)(x.astype(jnp.float32))
        q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
        mixed = jnp.concatenate(
            [t.reshape(b, s, -1) for t in (q, k, v)], axis=-1)
        mixed = nn.silu(DepthwiseConv(self.conv_width, self.dtype,
                                      name="conv")(mixed))
        q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
        q = q.reshape(b, s, hk, dk).astype(jnp.float32)
        k = k.reshape(b, s, hk, dk).astype(jnp.float32)
        v = v.reshape(b, s, hv, dv)

        def l2(t):
            return t * jax.lax.rsqrt(
                jnp.sum(t * t, axis=-1, keepdims=True) + self.eps)

        q, k = l2(q) * dk ** -0.5, l2(k)
        a_log = self.param("A_log", _part(nn.initializers.zeros, "heads"),
                           (hv,))
        dt_bias = self.param("dt_bias", _part(nn.initializers.ones, "heads"),
                             (hv,))
        beta = jax.nn.sigmoid(ba[..., :r].reshape(b, s, hv))
        g = -jnp.exp(a_log) * jax.nn.softplus(
            ba[..., r:].reshape(b, s, hv) + dt_bias)
        step_name = remat_plan.traced_step_name()
        if step_name is not None:
            record_gdn_path(step_name, *stage_plan(dk, dv),
                            shapes=(b, s, hk, hv, dk, dv))
        o = gated_delta_rule(                            # [B, S, Hv, Dv] f32
            q, k, v, g, beta,
            operand_dtype=None if self.dtype == jnp.float32 else self.dtype)
        o = RMSNorm(eps=self.eps, dtype=jnp.float32, axis_name="head_dim",
                    name="norm")(o)
        o = o * nn.silu(z.reshape(b, s, hv, dv).astype(jnp.float32))
        return nn.DenseGeneral(
            features=d_model, axis=(-2, -1), use_bias=False,
            dtype=self.dtype,
            kernel_init=_part(nn.initializers.lecun_normal(),
                              "heads", "head_dim", "embed"),
            name="out")(o.astype(self.dtype))


class KimiDeltaAttention(nn.Module):
    """Kimi Delta Attention (arXiv:2510.26692): a delta rule whose decay is
    a vector over the key channels.  ``in_q``, ``in_k``, ``in_v`` project to
    ``heads x head_dim`` each, pass a causal depthwise conv (no bias) and
    SiLU; q and k are L2-normalised over the head, q scaled by ``head_dim **
    -0.5``; ``beta = sigmoid(in_b x)`` a head; ``g = -exp(A_log) *
    softplus(f_b(f_a x) + dt_bias)`` a key channel in float32 (``A_log`` a
    head, ``dt_bias`` a channel, the two-step projection through
    ``gate_rank``).  The rule is ops/gated_delta.py:kda_rule; its output
    passes an RMSNorm over the head, times ``sigmoid(g_b(g_a x))`` (``g_b``
    with a bias), and ``out``.  Trains only, as :class:`GatedDeltaNet`."""
    heads: int
    head_dim: int
    gate_rank: int
    conv_width: int = 4
    eps: float = 1e-6             # inside the q/k L2 normalisation
    norm_eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, d_model = x.shape
        h, d = self.heads, self.head_dim

        def heads_of(name, dtype, bias=False):
            return nn.DenseGeneral(
                features=(h, d), axis=-1, use_bias=bias, dtype=dtype,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  "embed", "heads", "head_dim"),
                name=name)

        def dense(name, width, dtype):
            return nn.Dense(width, use_bias=False, dtype=dtype,
                            kernel_init=_part(nn.initializers.lecun_normal(),
                                              "embed", None), name=name)

        q, k, v = (checkpoint_name(heads_of(name, self.dtype)(x),
                                   remat_plan.KDA_IN)
                   for name in ("in_q", "in_k", "in_v"))
        mixed = jnp.concatenate(
            [t.reshape(b, s, h * d) for t in (q, k, v)], axis=-1)
        mixed = nn.silu(DepthwiseConv(self.conv_width, self.dtype,
                                      name="conv")(mixed))
        q, k, v = (t.reshape(b, s, h, d) for t in jnp.split(mixed, 3, axis=-1))
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)

        def l2(t):
            return t * jax.lax.rsqrt(
                jnp.sum(t * t, axis=-1, keepdims=True) + self.eps)

        q, k = l2(q) * d ** -0.5, l2(k)
        x32 = x.astype(jnp.float32)
        beta = jax.nn.sigmoid(dense("in_b", h, jnp.float32)(x32))
        a_log = self.param("A_log", _part(nn.initializers.zeros, "heads"),
                           (h,))
        dt_bias = self.param(
            "dt_bias", _part(nn.initializers.ones, "heads", "head_dim"),
            (h, d))
        low = dense("f_a", self.gate_rank, jnp.float32)(x32)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            heads_of("f_b", jnp.float32)(low) + dt_bias)
        step_name = remat_plan.traced_step_name()
        if step_name is not None:
            record_kda_path(step_name, *stage_plan(d, d, channelwise=True),
                            shapes=(b, s, h, d, d))
        o = kda_rule(                                    # [B, S, H, D] f32
            q, k, v, g, beta,
            operand_dtype=None if self.dtype == jnp.float32 else self.dtype)
        o = RMSNorm(eps=self.norm_eps, dtype=jnp.float32,
                    axis_name="head_dim", name="norm")(o)
        gate = heads_of("g_b", self.dtype, bias=True)(
            dense("g_a", self.gate_rank, self.dtype)(x))
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32))
        return nn.DenseGeneral(
            features=d_model, axis=(-2, -1), use_bias=False,
            dtype=self.dtype,
            kernel_init=_part(nn.initializers.lecun_normal(),
                              "heads", "head_dim", "embed"),
            name="out")(o.astype(self.dtype))


class LatentAttention(nn.Module):
    """Multi-head latent attention without a rotation (MLA, NoPE): ``q``
    projects to ``heads x (nope_dim + rope_dim)``; ``kv_a`` to ``kv_rank +
    rope_dim``, of which the first ``kv_rank`` pass an RMSNorm
    (``kv_norm``) and ``kv_b`` to each head's ``nope_dim`` of key and
    ``v_dim`` of value, and the last ``rope_dim`` are one key part shared by
    all heads (not rotated); causal softmax attention at the query/key size
    ``nope_dim + rope_dim`` with values of ``v_dim`` (the flash kernels take
    the two sizes), then ``out``.  Trains only: in training there is no
    cache, so what is latent about it is the shape."""
    n_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    norm_eps: float = 1e-6
    attn_impl: str = "flash"
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, d_model = x.shape
        h = self.n_heads

        def heads_of(name, width):
            return nn.DenseGeneral(
                features=(h, width), axis=-1, use_bias=False,
                dtype=self.dtype,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  "embed", "heads", "head_dim"),
                name=name)

        q = heads_of("q", self.nope_dim + self.rope_dim)(x)
        latent = nn.Dense(self.kv_rank + self.rope_dim, use_bias=False,
                          dtype=self.dtype,
                          kernel_init=_part(nn.initializers.lecun_normal(),
                                            "embed", None), name="kv_a")(x)
        shared = latent[..., self.kv_rank:]              # [B, S, rope_dim]
        kv = heads_of("kv_b", self.nope_dim + self.v_dim)(
            RMSNorm(eps=self.norm_eps, dtype=self.dtype, axis_name=None,
                    name="kv_norm")(latent[..., :self.kv_rank]))
        k = jnp.concatenate(
            [kv[..., :self.nope_dim],
             jnp.broadcast_to(shared[:, :, None, :],
                              (b, s, h, self.rope_dim))], axis=-1)
        v = kv[..., self.nope_dim:]
        # [B, S, H, D] -> [B, H, S, D]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if self.attn_impl == "flash":
            o = flash_attention(q, k, v, causal=True)
        else:
            o = mha_reference(q, k, v, causal=True).astype(self.dtype)
        out = nn.DenseGeneral(
            features=d_model, axis=(-2, -1), use_bias=False,
            dtype=self.dtype,
            kernel_init=_part(nn.initializers.lecun_normal(),
                              "heads", "head_dim", "embed"),
            name="out")(o.transpose(0, 2, 1, 3))
        return checkpoint_name(out, remat_plan.ATTN_OUT)


class _RoutedExperts(nn.Module):
    """The held experts' weights in the compute dtype, ``(wi, wg, wo)``,
    made once a layer (``HeldExperts`` plans the buffer and chooses its
    size; :func:`_grouped_swiglu` multiplies)."""
    held: int
    d_ff: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, d_model):
        init = nn.initializers.lecun_normal(batch_axis=(0,))

        def weight(name, shape, in_ax, out_ax):
            return self.param(name, _part(init, "expert", in_ax, out_ax),
                              shape).astype(self.dtype)

        return (weight("wi", (self.held, d_model, self.d_ff), "embed", "mlp"),
                weight("wg", (self.held, d_model, self.d_ff), "embed", "mlp"),
                weight("wo", (self.held, self.d_ff, d_model), "mlp", "embed"))


def _grouped_swiglu(xf, gates, weights, plan, n_rows: int):
    """[T, d] float32: the tokens ``xf`` through their held experts over the
    first ``n_rows`` rows of the sorted buffer: the way in, the three grouped
    matmuls with their SwiGLU, the way out weighted by ``gates``.  ``plan``
    is ``(tile_expert, row_assign, assign_row)`` of the full buffer; a row
    of ``assign_row`` behind ``n_rows`` reads zeros, as "no row" does."""
    wi, wg, wo = weights
    tile_expert, row_assign, assign_row = plan
    tile_expert = tile_expert[:n_rows // ROW_TILE]
    row_assign = row_assign[:n_rows]
    with jax.named_scope("moe_dispatch"):
        rows = rows_of(xf, row_assign, assign_row)           # [n_rows, d]
    with jax.named_scope("experts"):
        h = nn.silu(grouped_matmul(rows, wg, tile_expert)) * \
            grouped_matmul(rows, wi, tile_expert)
        y = grouped_matmul(h, wo, tile_expert)
    with jax.named_scope("moe_dispatch"):
        return weighted_rows_sum(y, gates, row_assign, assign_row)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _first_or_full(sizes, fits, xf, gates, weights, plan):
    """:func:`_grouped_swiglu` over ``sizes[0]`` rows where ``fits`` (a
    scalar on the device), else over ``sizes[1]``: one ``lax.cond`` between
    two programs of static shapes.  The backward pass is a second ``cond``
    on the same scalar, each branch differentiating its own size from the
    operands, which the two share: so what crosses from the forward pass to
    the backward is the operands alone, and nothing of a branch's size (a
    differentiated ``cond`` would keep the union of both branches'
    residuals, the branch not taken writing zeros in the other's shapes)."""
    return jax.lax.cond(fits, *(
        functools.partial(_grouped_swiglu, n_rows=n) for n in sizes),
        xf, gates, weights, plan)


def _first_or_full_fwd(sizes, fits, xf, gates, weights, plan):
    return (_first_or_full(sizes, fits, xf, gates, weights, plan),
            (fits, xf, gates, weights, plan))


def _first_or_full_bwd(sizes, res, d_out):
    fits, *operands = res

    def backward_over(n_rows):
        def branch(xf, gates, weights, plan, d_out):
            # the branch's forward, run again: under the scope jax gives a
            # checkpoint's, so that a trace reads it as recomputation
            with jax.named_scope("rematted_computation"):
                _, pull = jax.vjp(
                    lambda *a: _grouped_swiglu(*a, plan, n_rows),
                    xf, gates, weights)
            return pull(d_out)
        return branch
    # the barrier keeps what follows a gradient out of the branches: without
    # it XLA moves AdamW's first elementwise steps on the weights' gradients
    # into both, and each branch gives every gradient twice in float32 (13
    # ms of a 313 ms step on a v5e: PERF.md section 6, PR 32)
    return (None, *jax.lax.optimization_barrier(
        jax.lax.cond(fits, *map(backward_over, sizes), *operands, d_out)),
            None)


_first_or_full.defvjp(_first_or_full_fwd, _first_or_full_bwd)


class _SharedExpert(nn.Module):
    """``sigmoid(x w_s) * SwiGLU(x)``: the expert every token passes;
    without ``gated`` the plain ``SwiGLU(x)``."""
    d_ff: int
    dtype: Dtype = jnp.bfloat16
    gated: bool = True

    @nn.compact
    def __call__(self, x):
        def dense(features, name, names):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=_part(nn.initializers.lecun_normal(),
                                              *names), name=name)
        h = nn.silu(dense(self.d_ff, "wg", ("embed", "mlp"))(x)) * \
            dense(self.d_ff, "wi", ("embed", "mlp"))(x)
        y = dense(x.shape[-1], "wo", ("mlp", "embed"))(h)
        if not self.gated:
            return y
        return jax.nn.sigmoid(dense(1, "gate", ("embed", None))(x)) * y


class HeldExperts(nn.Module):
    """One chip's share of an expert layer under expert parallelism.

    The router scores all ``router_width`` experts of the model (float32
    matmul and softmax, or a sigmoid an expert: ``router_act``), takes the
    ``top_k`` largest and divides their probabilities by their sum (times
    ``routed_scale``).  This chip holds experts ``first ...
    first + held``: it keeps the assignments that name one of them, and
    computes ``sum_e p_e * W_down,e(silu(W_gate,e x) * W_up,e x)`` over
    those alone.  What the absent experts would add is absent (no
    all-to-all, nothing stands in for the other chips).  A shared expert
    of width ``shared_d_ff``, if any, is computed whole and added.

    **The buffer.**  The kept assignments are sorted by expert into a
    buffer of ``R`` rows, ``R`` a function of shapes alone
    (ops/grouped_matmul.py:``held_buffer_rows``: a stated multiple of the
    assignments expected here under even routing, at most every choice of
    every token, in whole row tiles; every expert's group starts on a tile
    and holds at least one).  Rows are gathered, multiplied by their
    expert's weights, and each token sums its rows weighted by ``p``
    (``rows_of``, ``weighted_rows_sum``: gathers both ways).  **Two sizes
    are computed, never one that follows the routing**: the first buffer
    (``first_buffer_rows``: a smaller stated multiple of the expected
    assignments, a prefix of the full one, since the sort, the groups'
    starts and ``tile_expert`` are the same) where ``live_rows``, the rows
    the aligned groups need, fits it, and the full ``R`` rows where it does
    not: a ``lax.cond`` on the device between two programs of static
    shapes, every tile of the chosen one computed.  The tiles the first
    buffer leaves out held zeros only, so both give the same numbers.  The
    backward pass chooses again and each branch differentiates its own size
    from the operands, which the two share (tokens, gates, weights, the
    plan), so nothing of a branch's own size crosses from one pass to the
    other (``_first_or_full``).  Where the first buffer would be as
    large as the full one there is one buffer and no choice.  The layer
    sows ``full_buffer`` (1 where it took the full buffer of two, else 0),
    and the train step counts the layers that did.  An assignment that does
    not fit the full buffer is never dropped in silence: the layer sows
    ``overflow_rows`` (and ``live_rows``) into the ``moe_stats``
    collection, and the train step makes an overflowing step's loss
    non-finite.  At the stated multiple of the Qwen3-Next share the full
    buffer holds every assignment the shapes allow and none can overflow.

    No balance loss: the layer sows no ``aux_loss``.
    """
    router_width: int
    first: int
    held: int
    top_k: int
    d_ff: int
    shared_d_ff: int = 0
    # the router's activation, 'softmax' over all experts or 'sigmoid' an
    # expert; the chosen scores are divided by their sum either way, then
    # times ``routed_scale``; ``shared_gate``: the shared expert's sigmoid
    # gate (a model hyperparameter: some have none)
    router_act: str = "softmax"
    routed_scale: float = 1.0
    shared_gate: bool = True
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        if self.router_act not in ("softmax", "sigmoid"):
            raise ValueError(f"router activation {self.router_act!r}")
        if not (0 <= self.first
                and self.first + self.held <= self.router_width
                and 1 <= self.top_k <= self.router_width):
            raise ValueError(
                f"experts {self.first}..{self.first + self.held} of "
                f"{self.router_width}, {self.top_k} a token")
        b, s, d_model = x.shape
        tokens, k, held = b * s, self.top_k, self.held
        xf = x.reshape(tokens, d_model)
        logits = nn.Dense(self.router_width, use_bias=False,
                          dtype=jnp.float32,
                          kernel_init=_part(nn.initializers.lecun_normal(),
                                            "embed", "expert"),
                          name="router")(xf.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1) \
            if self.router_act == "softmax" else jax.nn.sigmoid(logits)
        gates, idx = checkpoint_name(jax.lax.top_k(probs, k),
                                     remat_plan.MOE_PLAN)    # [tokens, k]
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        if self.routed_scale != 1.0:
            gates = gates * self.routed_scale

        shapes = (tokens, k, held, self.router_width)
        n_rows, expected = held_buffer_rows(*shapes)
        first_rows = first_buffer_rows(*shapes)
        step_name = remat_plan.traced_step_name()
        if step_name is not None:
            record_expert_buffer(step_name, n_rows, ROW_TILE, expected,
                                 first_rows)
        with jax.named_scope("moe_dispatch"):
            local = idx.reshape(-1) - self.first             # [tokens * k]
            local = jnp.where((local >= 0) & (local < held), local, held)
            counts = jnp.sum(local[:, None] == jnp.arange(held)[None, :],
                             axis=0, dtype=jnp.int32)        # [held]
            tiles = jnp.maximum(1, -(-counts // ROW_TILE))
            tile_end = jnp.cumsum(tiles)
            group_start = (tile_end - tiles) * ROW_TILE      # buffer row
            sorted_start = jnp.cumsum(counts) - counts       # in ``order``
            order = checkpoint_name(jnp.argsort(local, stable=True),
                                    remat_plan.MOE_PLAN)
            tile_expert = jnp.minimum(
                jnp.sum(jnp.arange(n_rows // ROW_TILE)[:, None]
                        >= tile_end[None, :], axis=1), held - 1)
            row_expert = jnp.repeat(tile_expert, ROW_TILE)   # [R]
            offset = jnp.arange(n_rows) - group_start[row_expert]
            live = (offset >= 0) & (offset < counts[row_expert])
            # each row's assignment (tokens * k: none) ...
            row_assign = jnp.where(live, order[jnp.where(
                live, sorted_start[row_expert] + offset, 0)], tokens * k)
            # ... and each assignment's row (n_rows: not held here, or
            # behind the buffer's end)
            rank = checkpoint_name(jnp.argsort(order), remat_plan.MOE_PLAN)
            mine = jnp.minimum(local, held - 1)
            row = group_start[mine] + rank - sorted_start[mine]
            assign_row = jnp.where((local < held) & (row < n_rows), row,
                                   n_rows).reshape(tokens, k)
            fits = jnp.clip(n_rows - group_start, 0, counts)
            self.sow("moe_stats", "overflow_rows",
                     jnp.sum(counts - fits))
            live_rows = tile_end[-1] * ROW_TILE
            self.sow("moe_stats", "live_rows", live_rows)
        weights = _RoutedExperts(held, self.d_ff, self.dtype,
                                 name="experts")(d_model)
        operands = (xf, gates, weights, (tile_expert, row_assign, assign_row))
        if first_rows < n_rows:
            fits = live_rows <= first_rows
            out = _first_or_full((first_rows, n_rows), fits, *operands)
        else:
            fits = True
            out = _grouped_swiglu(*operands, n_rows)
        self.sow("moe_stats", "full_buffer",
                 jnp.logical_not(fits).astype(jnp.int32))
        out = out.astype(self.dtype)
        if self.shared_d_ff:
            out = out + _SharedExpert(self.shared_d_ff, self.dtype,
                                      gated=self.shared_gate,
                                      name="shared")(xf)
        return out.reshape(b, s, d_model)


class GdnSpec(NamedTuple):
    """``GatedDeltaNet``'s sizes."""
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_width: int


class KdaSpec(NamedTuple):
    """``KimiDeltaAttention``'s sizes."""
    heads: int
    head_dim: int
    gate_rank: int
    conv_width: int


class MlaSpec(NamedTuple):
    """``LatentAttention``'s sizes."""
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int


class HeldSpec(NamedTuple):
    """``HeldExperts``' sizes and the router's hyperparameters."""
    router_width: int
    first: int
    held: int
    top_k: int
    d_ff: int
    shared_d_ff: int
    router_act: str = "softmax"
    routed_scale: float = 1.0
    shared_gate: bool = True


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """What a block is beyond the dense one (``TransformerLM``'s keywords,
    gathered so that ``Block`` stays one module): the token mixer's kind
    and sizes, the norms' centring, and the held experts' layer."""
    kind: str = "full"                 # 'full' | 'linear' | 'kda' | 'mla'
    n_kv_heads: int = 0
    rope_dims: int = 0
    qk_norm: bool = False
    attn_gate: Any = False             # True: from a doubled q; 'own'
    norm_zero_centered: bool = False
    window: int = 0                    # a 'full' layer's band; 0: causal
    rotate: bool = True                # whether a 'full' layer rotates q, k
    post_norms: bool = False           # a norm on each sublayer's output
    gdn: Any = None                    # a GdnSpec on a linear layer
    held: Any = None                   # a HeldSpec where experts are held
    kda: Any = None                    # a KdaSpec on a 'kda' layer
    mla: Any = None                    # an MlaSpec on an 'mla' layer
    norm_eps: float = 1e-6             # of the block's norms


class Block(nn.Module):
    n_heads: int
    head_dim: int
    d_ff: int
    n_experts: int = 0
    attn_impl: str = "flash"
    dtype: Dtype = jnp.bfloat16
    moe_dispatch: str = "dense"
    capacity_factor: float = 1.25
    moe_top_k: int = 1
    moe_group_size: int = 0
    quantize: Any = False         # weight-only matmuls (serve):
    #                               True/'int8' -> int8, 'w8f' -> fp8
    paged_kernel: bool = False    # Pallas paged attend (kernel round 2)
    spec: Any = None              # a BlockSpec; None: the dense block

    @nn.compact
    def __call__(self, x, cos, sin, decode: bool = False):
        if self.spec is not None:
            return self._hybrid(x, cos, sin, decode)
        h = RMSNorm(dtype=self.dtype, name="ln_attn")(x)
        x = x + Attention(self.n_heads, self.head_dim, self.attn_impl,
                          self.dtype, quantize=self.quantize,
                          paged_kernel=self.paged_kernel,
                          name="attn")(h, cos, sin, decode=decode)
        h = RMSNorm(dtype=self.dtype, name="ln_mlp")(x)
        if self.n_experts > 0:
            x = x + MoE(self.n_experts, self.d_ff, self.dtype,
                        dispatch=self.moe_dispatch,
                        capacity_factor=self.capacity_factor,
                        top_k=self.moe_top_k,
                        group_size=self.moe_group_size,
                        quantize=self.quantize, name="moe")(h)
        else:
            x = x + SwiGLU(self.d_ff, self.dtype,
                           quantize=self.quantize, name="mlp")(h)
        return x

    def _hybrid(self, x, cos, sin, decode):
        """``h = x + Mixer(norm(x)); y = h + FFN(norm(h))`` with the mixer
        and the expert layer the spec names; with ``post_norms`` a norm on
        each sublayer's output too, ``h = x + norm(Mixer(norm(x)))``."""
        spec = self.spec

        def norm(name):
            return RMSNorm(eps=spec.norm_eps, dtype=self.dtype,
                           zero_centered=spec.norm_zero_centered, name=name)

        def post(name, y):
            return norm(name)(y) if spec.post_norms else y

        h = norm("ln_attn")(x)
        if spec.kind in ("linear", "kda", "mla") and decode:
            raise NotImplementedError(
                "a model with linear-attention or latent-attention layers "
                "trains only: decoding needs the delta rule's recurrent "
                "state and the conv's tail in the cache (GatedDeltaNet and "
                "KimiDeltaAttention have neither), or the latent row a "
                "token (LatentAttention), and a step that advances them")
        if spec.kind == "linear":
            x = x + GatedDeltaNet(**spec.gdn._asdict(), dtype=self.dtype,
                                  name="gdn")(h)
        elif spec.kind == "kda":
            x = x + KimiDeltaAttention(
                **spec.kda._asdict(), norm_eps=spec.norm_eps,
                dtype=self.dtype, name="kda")(h)
        elif spec.kind == "mla":
            x = x + LatentAttention(
                self.n_heads, **spec.mla._asdict(), norm_eps=spec.norm_eps,
                attn_impl=self.attn_impl, dtype=self.dtype, name="attn")(h)
        elif spec.kind == "full":
            x = x + post("ln_attn_out", Attention(
                self.n_heads, self.head_dim, self.attn_impl, self.dtype,
                quantize=self.quantize, n_kv_heads=spec.n_kv_heads,
                rope_dims=spec.rope_dims, qk_norm=spec.qk_norm,
                gate=spec.attn_gate,
                norm_zero_centered=spec.norm_zero_centered,
                window=spec.window, rotate=spec.rotate,
                norm_eps=spec.norm_eps,
                name="attn")(h, cos, sin, decode=decode))
        else:
            raise ValueError(f"unknown layer kind {spec.kind!r}")
        if spec.post_norms and spec.kind != "full":
            raise ValueError("norms on the sublayers' outputs are built for "
                             "'full' layers alone")
        h = norm("ln_mlp")(x)
        if spec.held:
            return x + post("ln_mlp_out", HeldExperts(
                **spec.held._asdict(), dtype=self.dtype, name="moe")(h))
        return x + post("ln_mlp_out",
                        SwiGLU(self.d_ff, self.dtype, name="mlp")(h))


@functools.cache
def _remat_block(rung: int, linear: bool | str = False, held: bool = False):
    """``Block`` under ``jax.checkpoint`` with the policy of ``rung``
    (models/remat_plan.py); rung 0 is no policy, the whole forward again."""
    return nn.remat(Block, static_argnums=(),
                    policy=remat_plan.policy(rung, linear, held))


class TransformerLM(nn.Module):
    """Decoder-only LM; input int32 tokens [batch, seq] -> logits f32."""
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    d_ff: int = 1408
    max_seq: int = 2048
    n_experts: int = 0            # 0 = dense SwiGLU MLP
    moe_every: int = 2            # every k-th block is MoE (when n_experts>0)
    moe_dispatch: str = "dense"   # 'dense' oracle | 'routed' capacity top-k
    capacity_factor: float = 1.25  # routed: slots = ceil(cf * g * k / E)
    moe_top_k: int = 1            # routed: experts per token
    moe_group_size: int = 0       # routing group (0 = min(seq, 1024))
    attn_impl: str = "flash"
    remat: bool = False
    dtype: Dtype = jnp.bfloat16
    # weight-only serving: every matmul kernel becomes a quantized
    # tensor + per-output-channel scale with dequant fused into the
    # matmul (dtdl_tpu/quant/) — ``True``/'int8' the int8+f32 recipe,
    # 'w8f' the fp8+bf16 one.  A quantized model is built as
    # ``model.clone(quantize=mode)`` and loaded via
    # ``quant.quantize_params`` — never trained.  Embedding, norms and
    # MoE routers stay f32 (see dtdl_tpu/quant/core.py for why).
    quantize: Any = False
    # Pallas paged-attention decode kernel (kernel round 2): the paged
    # arena's decode/verify attend walks the page table inside the
    # kernel instead of gathering the whole logical view
    # (dtdl_tpu/ops/paged_attention.py).  The serving engine resolves
    # its 'auto' flag to this bool at construction.
    paged_kernel: bool = False
    # --- hybrid architectures (all at their defaults: the model above, the
    # same parameter paths and the same compiled programs) -----------------
    # per-layer token mixer: 'full' (softmax attention), 'linear' (Gated
    # DeltaNet), 'kda' (Kimi Delta Attention) or 'mla' (latent attention
    # without a rotation); () = every layer 'full'
    layer_kinds: tuple = ()
    n_kv_heads: int = 0           # K/V heads; 0 = n_heads
    attn_head_dim: int = 0        # stated head size; 0 = d_model // n_heads
    rope_dims: int = 0            # rotated dims of a head; 0 = all of them
    rope_theta: float = 10000.0
    qk_norm: bool = False         # RMSNorm over the head on q and k
    attn_gate: Any = False        # sigmoid output gate from a doubled q;
    #                               'own': from a projection of its own
    norm_zero_centered: bool = False   # x_hat * (1 + w), w around 0
    # per-layer pattern of windowed and full attention: the window of each
    # 'full' layer (0: causal; () = none windowed), and whether each layer
    # rotates q and k (() = every layer does)
    layer_windows: tuple = ()
    layer_rotates: tuple = ()
    post_norms: bool = False      # a norm on each sublayer's output too
    embed_scale: float = 1.0      # the embedding's factor
    gdn_key_heads: int = 0        # linear attention: key heads,
    gdn_value_heads: int = 0      # value heads,
    gdn_key_dim: int = 0          # their head sizes,
    gdn_value_dim: int = 0
    gdn_conv: int = 4             # and the causal conv's width
    kda_heads: int = 0            # Kimi Delta Attention: heads, their size
    kda_head_dim: int = 0         # (key and value alike), the rank of the
    kda_gate_rank: int = 0        # decay's and the gate's two-step
    kda_conv: int = 4             # projections, the causal conv's width
    mla_nope_dim: int = 0         # latent attention: a head's key part of
    mla_rope_dim: int = 0         # its own, the part all heads share (not
    mla_v_dim: int = 0            # rotated), the value head, the latent's
    mla_kv_rank: int = 0          # width
    norm_eps: float = 1e-6        # of the blocks' and the final RMSNorm
    # moe_dispatch='held' (one chip's share under expert parallelism,
    # HeldExperts): n_experts are held here, ids moe_first_expert onward, of
    # the moe_router_width the router scores; moe_top_k a token; expert width
    # moe_d_ff (0 = d_ff), a shared expert of moe_shared_d_ff (0 = none)
    moe_router_width: int = 0
    moe_first_expert: int = 0
    moe_d_ff: int = 0
    moe_shared_d_ff: int = 0
    # 'held' alone: the router's activation ('softmax' | 'sigmoid'), what
    # the chosen, renormalised scores are multiplied by, whether the shared
    # expert has a sigmoid gate, and how many leading layers keep the dense
    # SwiGLU of d_ff in place of experts
    moe_router_act: str = "softmax"
    moe_routed_scale: float = 1.0
    moe_shared_gate: bool = True
    first_dense_layers: int = 0
    tie_embeddings: bool = True   # False: a head table of its own, 'head'

    @property
    def head_dim(self):
        return self.attn_head_dim or self.d_model // self.n_heads

    @property
    def hybrid(self) -> bool:
        """Whether any block departs from the dense one (BlockSpec)."""
        return bool(self.layer_kinds or self.n_kv_heads or self.rope_dims
                    or self.qk_norm or self.attn_gate
                    or self.norm_zero_centered
                    or self.moe_dispatch == "held"
                    or self.layer_windows or self.layer_rotates
                    or self.post_norms)

    def block_specs(self, is_moe):
        """A :class:`BlockSpec` a layer, or None a layer for the dense
        model."""
        if not self.hybrid:
            return [None] * self.n_layers
        kinds = self.layer_kinds or ("full",) * self.n_layers
        windows = self.layer_windows or (0,) * self.n_layers
        rotates = self.layer_rotates or (True,) * self.n_layers
        if not len(kinds) == len(windows) == len(rotates) == self.n_layers:
            raise ValueError(f"{len(kinds)} layer kinds, {len(windows)} "
                             f"windows, {len(rotates)} rotations for "
                             f"{self.n_layers} layers")
        held = None
        if self.moe_dispatch == "held":
            held = HeldSpec(
                router_width=self.moe_router_width,
                first=self.moe_first_expert, held=self.n_experts,
                top_k=self.moe_top_k, d_ff=self.moe_d_ff or self.d_ff,
                shared_d_ff=self.moe_shared_d_ff,
                router_act=self.moe_router_act,
                routed_scale=self.moe_routed_scale,
                shared_gate=self.moe_shared_gate)
        elif self.n_experts:
            raise ValueError("hybrid blocks route through "
                             "moe_dispatch='held' alone")
        gdn = GdnSpec(self.gdn_key_heads, self.gdn_value_heads,
                      self.gdn_key_dim, self.gdn_value_dim, self.gdn_conv)
        kda = KdaSpec(self.kda_heads, self.kda_head_dim, self.kda_gate_rank,
                      self.kda_conv)
        mla = MlaSpec(self.mla_nope_dim, self.mla_rope_dim, self.mla_v_dim,
                      self.mla_kv_rank)
        return [BlockSpec(kind=kind, n_kv_heads=self.n_kv_heads,
                          rope_dims=self.rope_dims, qk_norm=self.qk_norm,
                          attn_gate=self.attn_gate,
                          norm_zero_centered=self.norm_zero_centered,
                          window=int(window), rotate=bool(rotate),
                          post_norms=self.post_norms,
                          gdn=gdn if kind == "linear" else None,
                          held=held if moe else None,
                          kda=kda if kind == "kda" else None,
                          mla=mla if kind == "mla" else None,
                          norm_eps=self.norm_eps)
                for kind, moe, window, rotate
                in zip(kinds, is_moe, windows, rotates)]

    def cache_shapes(self, batch_size: int, per_slot_index: bool = False,
                     kv_dtype=None):
        """Abstract (ShapeDtypeStruct) KV-cache pytree for ``batch_size``
        rows — one [B, H, max_seq, head_dim] K/V buffer pair + position
        index per block, no compute (``jax.eval_shape`` of the decode
        init trace).  ``per_slot_index=True`` widens the index leaves from
        a scalar to [B] — the serving-arena layout where each row is an
        independent slot at its own decode position.

        ``kv_dtype='int8'`` is the **quantized** cache layout
        (dtdl_tpu/quant): the K/V buffers become int8 and each gains a
        per-(row, head, position) f32 ``*_scale`` sibling [B, H,
        max_seq] — :meth:`Attention._decode_attend` quantizes on scatter
        and dequants in the attention einsums on gather, so decode HBM
        traffic per cached byte halves vs bf16 (quarters vs f32) at the
        cost of one scale float per position per head.
        ``kv_dtype='fp8'`` is the same layout with a float8_e4m3fn
        payload and bf16 scales (quant.kv_scale_dtype)."""
        kv_dtype = canon_kv_dtype(kv_dtype)
        shapes = jax.eval_shape(
            functools.partial(self.init, decode=True),
            jax.random.PRNGKey(0),
            jnp.zeros((batch_size, 1), jnp.int32))["cache"]
        if per_slot_index:
            shapes = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((batch_size,), s.dtype)
                if s.ndim == 0 else s, shapes)
        if kv_dtype is not None:
            def conv(tree):
                if isinstance(tree, dict):
                    if "key" in tree and "index" in tree:
                        kv = tree["key"].shape          # [B, H, S, D]
                        sc = jax.ShapeDtypeStruct(
                            kv[:3], kv_scale_dtype(kv_dtype))
                        return dict(
                            tree,
                            key=jax.ShapeDtypeStruct(kv, kv_dtype),
                            value=jax.ShapeDtypeStruct(kv, kv_dtype),
                            key_scale=sc, value_scale=sc)
                    return {k: conv(v) for k, v in tree.items()}
                return tree
            shapes = conv(shapes)
        return shapes

    def init_cache(self, batch_size: int, per_slot_index: bool = False,
                   kv_dtype=None):
        """Fresh zero KV cache (see :meth:`cache_shapes`); ``max_seq`` of
        the result is recoverable via :func:`cache_max_seq`."""
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_shapes(batch_size, per_slot_index,
                                              kv_dtype))

    def paged_cache_shapes(self, n_slots: int, n_pages: int,
                           page_size: int, kv_dtype=None):
        """Abstract pytree of the **block-paged** serving arena: per
        block, a shared ``pages_key``/``pages_value`` pool of
        ``[n_pages, H, page_size, head_dim]`` plus the per-slot
        ``index`` [n_slots] — the layout
        :meth:`Attention._paged_attend_slots` consumes (per-call
        ``page_table``/``active`` leaves are inserted by the serving
        engine, not stored).  Page 0 is reserved as the garbage page,
        hence ``n_pages >= 2``; ``page_size`` must divide ``max_seq`` so
        the gathered logical view covers exactly the rope table.

        ``kv_dtype='int8'`` quantizes the pools: int8
        ``pages_key``/``pages_value`` plus per-(page, head, in-page
        position) f32 ``pages_key_scale``/``pages_value_scale``
        [n_pages, H, page_size] — each K/V page byte halves vs bf16, so
        a fixed HBM pool holds ~2x the pages (the slots-per-byte
        multiplier the serving engine's ``kv_pool_bytes`` sizing and
        compile_stats receipts expose).  Scales ride WITH their page
        (scattered/gathered through the same page table), so prefix-
        cache sharing of int8 pages needs no extra bookkeeping.
        ``kv_dtype='fp8'`` swaps the payload for float8_e4m3fn and the
        scale sidecars for bf16 — the byte win over int8 is entirely
        the 2-vs-4-byte scales (quant.kv_scale_dtype)."""
        kv_dtype = canon_kv_dtype(kv_dtype)
        if page_size < 1 or self.max_seq % page_size:
            raise ValueError(
                f"page_size must be >= 1 and divide max_seq="
                f"{self.max_seq}, got {page_size}")
        if n_pages < 2:
            raise ValueError(f"n_pages must be >= 2 (page 0 is the "
                             f"reserved garbage page), got {n_pages}")

        def conv(tree):
            if isinstance(tree, dict):
                if "key" in tree and "index" in tree:
                    _, H, _, D = tree["key"].shape
                    pool_dt = kv_dtype or tree["key"].dtype
                    out = {
                        "pages_key": jax.ShapeDtypeStruct(
                            (n_pages, H, page_size, D), pool_dt),
                        "pages_value": jax.ShapeDtypeStruct(
                            (n_pages, H, page_size, D), pool_dt),
                        "index": jax.ShapeDtypeStruct(
                            (n_slots,), jnp.int32),
                    }
                    if kv_dtype is not None:
                        sc = jax.ShapeDtypeStruct(
                            (n_pages, H, page_size),
                            kv_scale_dtype(kv_dtype))
                        out["pages_key_scale"] = sc
                        out["pages_value_scale"] = sc
                    return out
                return {k: conv(v) for k, v in tree.items()}
            return tree
        return conv(self.cache_shapes(1))

    def init_paged_cache(self, n_slots: int, n_pages: int,
                         page_size: int, kv_dtype=None):
        """Fresh zeroed paged arena (see :meth:`paged_cache_shapes`)."""
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.paged_cache_shapes(n_slots, n_pages,
                                                    page_size, kv_dtype))

    def _checkpoint_plan(self, tokens_shape, is_moe, return_hidden,
                         specs=None):
        """What each rematerialized block keeps (models/remat_plan.py), from
        the traced token shape and this model's widths; recorded in the
        compile account beside the step that traces it."""
        batch, seq = tokens_shape
        itemsize = jnp.dtype(self.dtype).itemsize
        params = self.variables.get("params", {})
        param_bytes = remat_plan.tree_bytes(params)
        # a block's gradient has its parameters' shapes and dtypes
        block_grads = [remat_plan.tree_bytes(params.get(f"block_{i}", {}))
                       for i in range(self.n_layers)]
        vocab = 0 if return_hidden else self.vocab_size
        if specs is None or specs[0] is None:
            costs = [remat_plan.residual_bytes(
                batch, seq, self.d_model, self.n_heads,
                0 if moe else self.d_ff, itemsize) for moe in is_moe]
            held = remat_plan.model_held_bytes(
                batch, seq, self.d_model, self.d_ff, self.n_layers, vocab,
                param_bytes, itemsize)
        else:
            # each block's own bytes: a linear block may keep what the delta
            # rule's loop reads, a full one the attention names at its own
            # head width
            attn_width = self.n_heads * self.head_dim

            def cost(spec):
                if spec.kind == "linear":
                    return remat_plan.gdn_residual_bytes(
                        batch, seq, self.d_model, spec.gdn, itemsize)
                if spec.kind == "kda":
                    return remat_plan.kda_residual_bytes(
                        batch, seq, spec.kda, itemsize)
                if spec.kind == "mla":
                    return remat_plan.mla_residual_bytes(
                        batch, seq, self.d_model, self.n_heads, spec.mla,
                        itemsize)
                return remat_plan.residual_bytes(
                    batch, seq, self.d_model, self.n_heads,
                    0 if spec.held else self.d_ff, itemsize,
                    attn_width=attn_width, own_gate=spec.attn_gate == "own")

            costs = [cost(spec) for spec in specs]
            live = max(remat_plan.hybrid_block_live_bytes(
                batch, seq, self.d_model, itemsize,
                attn_width=attn_width if spec.kind == "full" else 0,
                gdn=spec.gdn, held=spec.held,
                d_ff=0 if spec.held else self.d_ff, kda=spec.kda,
                mla=(self.n_heads, spec.mla) if spec.mla else None,
                post_norms=spec.post_norms)
                for spec in specs)
            held = remat_plan.model_held_bytes(
                batch, seq, self.d_model, 0, self.n_layers, vocab,
                param_bytes, itemsize, block_live_bytes=live)
        plan = remat_plan.plan_checkpoints(costs, held, block_grads)
        if plan.fun_name is not None:
            record_remat_plan(plan)
        return plan

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 return_hidden: bool = False, decode: bool = False):
        """``return_hidden=True`` yields the final normalized hidden states
        [B, S, D] instead of logits — the contract of the vocab-chunked LM
        loss (dtdl_tpu/ops/cross_entropy.py:chunked_lm_loss), which never
        materializes the [B, S, V] logits.

        ``decode=True`` runs incremental attention against per-block KV
        caches (the 'cache' variable collection; create it by tracing
        ``init``/``apply`` with decode=True, mutate with
        ``mutable=['cache']``) — the autoregressive-generation contract of
        :func:`generate`."""
        del train
        emb = self.param(
            "embed", _part(nn.initializers.normal(stddev=0.02),
                           "vocab", "embed"),
            (self.vocab_size, self.d_model))
        if decode and (self.hybrid or not self.tie_embeddings):
            raise NotImplementedError(
                "decode=True on a hybrid model (linear-attention or latent-"
                "attention layers, grouped-query or gated attention, held "
                "experts, an untied head): it trains only; serving it needs a recurrent state "
                "beside the K/V pages and K/V heads in the cached attention"
                + ("; a windowed layer also needs a cache of its last "
                   f"{max(self.layer_windows)} keys alone (a page lifetime "
                   "of its own in serve/paged.py)"
                   if any(self.layer_windows) else ""))
        # the model's own two ops outside any flax submodule carry a scope
        # of their own (obs/trace.py:DEVICE_SCOPES), or a device trace can
        # tell them from the blocks by operand names alone
        with jax.named_scope("embed"):
            x = jnp.take(emb, tokens, axis=0)
            if self.embed_scale != 1.0:
                x = x * self.embed_scale
            x = x.astype(self.dtype)
        if self.hybrid:
            # the rotation runs outside the kernels, over the rotated dims
            # and the traced positions alone
            if tokens.shape[1] > self.max_seq:
                raise ValueError(f"{tokens.shape[1]} positions exceed "
                                 f"max_seq={self.max_seq}")
            cos, sin = rope_frequencies(self.rope_dims or self.head_dim,
                                        tokens.shape[1], self.rope_theta)
        else:
            cos, sin = rope_frequencies(self.head_dim, self.max_seq)

        # remat is a training-time memory/FLOPs trade; under decode it
        # would also trace the `decode` flag into a tracer (remat treats
        # every call arg as dynamic) — plain blocks for decode
        is_moe = [self.n_experts > 0 and (i + 1) % self.moe_every == 0
                  and i >= self.first_dense_layers
                  for i in range(self.n_layers)]
        specs = self.block_specs(is_moe)
        rungs = None
        if self.remat and not decode:
            rungs = self._checkpoint_plan(tokens.shape, is_moe,
                                          return_hidden, specs).rungs
        for i, moe in enumerate(is_moe):
            block_cls = Block if rungs is None else (
                _remat_block(rungs[i]) if specs[i] is None
                else _remat_block(
                    rungs[i],
                    specs[i].kind if specs[i].kind in ("linear", "kda")
                    else False, bool(specs[i].held)))
            block = block_cls(
                self.n_heads, self.head_dim, self.d_ff,
                n_experts=self.n_experts if moe else 0,
                attn_impl=self.attn_impl, dtype=self.dtype,
                moe_dispatch=self.moe_dispatch,
                capacity_factor=self.capacity_factor,
                moe_top_k=self.moe_top_k,
                moe_group_size=self.moe_group_size,
                quantize=self.quantize,
                paged_kernel=self.paged_kernel,
                **({} if specs[i] is None else {"spec": specs[i]}),
                name=f"block_{i}")
            # only pass the flag when set: a kwarg through nn.remat is
            # traced, and Attention branches on it in Python
            x = block(x, cos, sin, decode=True) if decode \
                else block(x, cos, sin)

        x = RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                    zero_centered=self.norm_zero_centered, name="ln_f")(x)
        if not self.tie_embeddings:
            # a table of its own; declared before ``return_hidden`` returns
            # so that the parameter tree does not depend on the caller
            emb = self.param(
                "head", _part(nn.initializers.normal(stddev=0.02),
                              "vocab", "embed"),
                (self.vocab_size, self.d_model))
        if return_hidden:
            return x
        with jax.named_scope("head"):
            logits = jnp.einsum("bsd,vd->bsv", x, emb.astype(self.dtype))
            return logits.astype(jnp.float32)


def generate(model: TransformerLM, params, prompt, max_new_tokens: int,
             temperature: float = 0.0, rng=None, strategy=None):
    """Autoregressive generation with per-block KV caches.

    ``prompt``: int32 [B, S0] (S0 + max_new_tokens must fit
    ``model.max_seq``; ``max_new_tokens >= 1``).  One prefill pass embeds
    the whole prompt into the caches, then a ``lax.scan`` of single-token
    steps decodes — the scan keeps the loop inside ONE compiled program
    (no per-token dispatch, static shapes throughout; the cache is a
    fixed [B, H, max_seq, D] buffer indexed by the traced cache
    position), and the compiled program is cached per
    (model, shapes, temperature) so repeated calls don't re-trace.
    ``temperature=0`` is greedy argmax; otherwise samples from
    logits/temperature with ``rng``.

    ``strategy``: a :class:`~dtdl_tpu.parallel.DataParallel` (or any
    mesh strategy) scales decoding like training — the prompt is placed
    batch-sharded on the data axis and XLA propagates that sharding
    through the whole program, so every replica prefils and steps its
    own batch rows with its own cache shards.  Tokens are IDENTICAL to
    the single-device run: the computation is batch-elementwise, and
    JAX's counter-based PRNG makes ``categorical`` draws depend only on
    the global position, not the partitioning.  (jit re-specializes per
    input sharding, so one compiled-program cache entry serves each
    placement.)

    Returns int32 [B, S0 + max_new_tokens].  (The reference has no
    sequence models, let alone inference — SURVEY §5.7; this is part of
    the framework's first-class LM capability.)
    """
    b, s0 = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got "
                         f"{max_new_tokens}")
    if s0 + max_new_tokens > model.max_seq:
        raise ValueError(
            f"prompt ({s0}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_seq ({model.max_seq})")
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    rng = jax.random.PRNGKey(0) if rng is None else rng
    if strategy is not None:
        prompt = strategy.shard_batch(jnp.asarray(prompt))
    run = _compiled_generate(model, b, s0, max_new_tokens, temperature)
    return run(params, prompt, rng)


@functools.lru_cache(maxsize=64)
def _compiled_generate(model, b, s0, max_new_tokens, temperature):
    """Memoized jitted prefill+scan program for one
    (model, shape, temperature) signature — repeated generate() calls
    with the same signature reuse one compiled program.  (flax Modules
    are frozen dataclasses, so ``model`` is a valid cache key.)"""
    from jax import lax

    # abstract trace only: the cache is zeros of the right shapes, no
    # extra full init of the model inside the compiled program
    cache_shapes = jax.eval_shape(
        functools.partial(model.init, decode=True),
        jax.random.PRNGKey(0), jnp.zeros((b, 1), jnp.int32))["cache"]

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature, axis=-1).astype(jnp.int32)

    @jax.jit
    def run(params, prompt, rng):
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             cache_shapes)
        # prefill via return_hidden: only the LAST position's logits are
        # sampled, so the [B, S0, vocab] logit tensor never materializes
        # (the same never-materialize discipline as chunked_lm_loss)
        hidden, muts = model.apply(
            {"params": params, "cache": cache}, prompt, decode=True,
            return_hidden=True, mutable=["cache"])
        emb = params["embed"]
        if hasattr(emb, "unbox"):       # flax logical-partitioning box
            emb = emb.unbox()
        # EXACTLY the module head's numerics (dtype-matched einsum, f32
        # cast after): a higher-precision prefill einsum could pick a
        # different argmax on near-tied logits than the step path does
        logits_last = jnp.einsum(
            "bd,vd->bv", hidden[:, -1],
            emb.astype(model.dtype)).astype(jnp.float32)
        rng_0, rng_scan = jax.random.split(rng)
        tok = sample(logits_last, rng_0)

        def step(carry, key):
            cache, tok = carry
            logits, muts = model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                decode=True, mutable=["cache"])
            nxt = sample(logits[:, -1], key)
            return (muts["cache"], nxt), tok

        keys = jax.random.split(rng_scan, max_new_tokens)[:-1]
        (_, last), toks = lax.scan(step, (muts["cache"], tok), keys)
        toks = jnp.moveaxis(toks, 0, 1)           # [B, max_new-1]
        return jnp.concatenate([prompt, toks, last[:, None]], axis=1)

    return run


def transformer_lm(size: str = "tiny", **overrides) -> TransformerLM:
    """Named configs; 'tiny' fits the CPU test mesh, 'base' one v5e chip.

    'small' and 'base' use **head_dim 128** (the MXU lane width): the Pallas
    flash kernel tiles [block, head_dim] blocks, so head_dim 32 wastes 3/4
    of every matmul lane for the identical FLOP count (what that costs end
    to end is not in the ledger: no cell runs a head size under 128).  Fewer,
    wider heads is the TPU-first layout.

    These are the *v2* geometries (the canonical names 'small-hd128' /
    'base-hd128' alias them): pre-hd128 'small'/'base' snapshots carry
    differently-shaped attention kernels, so an old checkpoint cannot
    silently load into the new head split — both the msgpack weight path
    (`ckpt.load_weights`) and the orbax full-state path
    (`Checkpointer.restore`/`restore_path`) run explicit shape validation
    and reject the mismatch (neither flax's nor orbax's own restore does).
    """
    cfgs = {
        "tiny": dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                     d_ff=128, max_seq=128),
        "small": dict(vocab_size=8192, d_model=256, n_layers=4, n_heads=2,
                      d_ff=704, max_seq=1024),
        "base": dict(vocab_size=32000, d_model=512, n_layers=8, n_heads=4,
                     d_ff=1408, max_seq=2048),
        # 'large' is 'base' at twice the width (its speed is not in the
        # ledger; chip_smoke.py trains it): d_model 1024 doubles every matmul's
        # contraction depth vs 'base' (same head_dim-128 MXU layout), and
        # ~239M params at seq 4096 need the standard long-seq memory
        # discipline — remat'd blocks plus the vocab-chunked loss
        # (pass vocab_chunk_size to make_lm_train_step; the [B,S,32k] f32
        # logits alone would be 4.2 GB at bs8/seq4096)
        "large": dict(vocab_size=32000, d_model=1024, n_layers=16,
                      n_heads=8, d_ff=2816, max_seq=2048, remat=True),
    }
    # routed-MoE variant of 'base': 8 experts every other block, GShard
    # capacity dispatch (the same routing math as the dense dispatch;
    # its speed against that one is not measured in any cell)
    cfgs["base-moe8"] = dict(cfgs["base"], n_experts=8, moe_every=2,
                             moe_dispatch="routed")
    cfgs["small-hd128"] = cfgs["small"]
    cfgs["base-hd128"] = cfgs["base"]
    cfg = dict(cfgs[size])
    cfg.update(overrides)
    return TransformerLM(**cfg)
