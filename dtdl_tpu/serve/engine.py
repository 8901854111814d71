"""Batched LM inference engine: three XLA program families, a slotted KV
arena.

The serving problem on TPU is a *compile-shape* problem: XLA programs are
shape-specialized, so a naive "pad the batch to the longest request and
re-jit per prompt length" serving loop recompiles on every new shape and
stalls every request behind the longest one.  This engine fixes the
shapes once and routes all traffic through a handful of programs per
model (the Orca/vLLM decomposition, rebuilt XLA-native on static shapes):

* ``prefill(params, arena, last, tokens[1, T], length, slot, ...)`` —
  one compiled program per **prompt-length bucket** T (powers of two up
  to ``max_seq``), built lazily on first use and jit-cached forever
  after.  A prompt is right-padded to its bucket, embedded through the
  model's chunked decode path at scalar cache index 0, and its K/V rows
  are scattered into row ``slot`` of the arena.  Pad positions write
  garbage K/V beyond ``length`` — harmless, because a position is only
  ever attended after the decode step that overwrites it (causal mask
  ``<= index``, and the write at ``index`` happens before the attend in
  the same program).  The first output token is sampled in-program from
  the last *real* position's logits (``return_hidden`` + a dtype-matched
  head einsum, the same never-materialize-the-[T, V]-logits discipline
  as ``generate``).
* ``decode(params, arena, last[B], active[B], ...)`` — ONE compiled
  program total: every slot advances one token against its own cache
  row at its own position (the model's vector-index cache path,
  models/transformer.py:_verify_attend_slots at S=1).  Inactive slots
  compute garbage that is masked out of the state (their index does not
  advance); occupancy is a runtime *value*, never a compile shape.
* ``verify(params, arena, last[B], draft[B, k], draft_len[B], ...)`` —
  the THIRD program family, one per draft width k (the scheduler
  buckets k to powers of two, so the family stays as small as the
  prefill one): speculative decoding's verify pass.  One parameter
  sweep scores the slot's last token plus k drafted candidates against
  the KV arena (k+1 query positions through the same vector-index
  path), then per-slot acceptance runs ON DEVICE (exact prefix match
  for greedy rows, one-hot residual rejection sampling otherwise —
  dtdl_tpu/serve/sampling.py:accept_resample), the accepted tokens come
  back as a [B, k+1] window with per-slot counts, and each slot's cache
  index advances by its own *variable* ``n_accepted + 1`` (the index
  leaves are rolled back from the model's +k+1; the stale K/V rows of
  rejected candidates are overwritten before they are ever attended,
  the same discipline as prefill padding).  Decode is HBM-bandwidth
  bound — one token per full parameter read — so verify converts the
  same read into up to k+1 tokens while staying token-losslessly
  equivalent (SCALING.md "Speculative decoding arithmetic").

The **arena** comes in two layouts.  Dense (default): the fixed
[n_slots, H, max_seq, head_dim] per-block K/V buffer pair plus a
per-slot position vector (``cache_shapes(..., per_slot_index=True)``)
— every slot charged max_seq worth of KV bytes up front.  **Paged**
(``page_size > 0``): a fixed pool of [n_pages, H, page_size, head_dim]
pages that per-slot page tables map logical positions onto
(models/transformer.py:_paged_attend_slots), so a slot pins only the
pages its sequence has reached (fragmentation < page_size tokens/slot)
and identical prompt prefixes can SHARE read-only pages across requests
(the scheduler's prefix cache, dtdl_tpu/serve/paged.py) — far more
concurrent slots per HBM byte, and cache-hit prompts skip the shared
prefix's prefill entirely.  Crucially the paged layout reuses the SAME
three program families: page tables and the active mask are plain data
inputs, and a prefix-hit prefill re-enters through the suffix's
(smaller) bucket.  Either arena is donated to every program, so the
cache is updated in place on device — no per-step reallocation of the
largest buffer in serving.  Sampling knobs ride along as per-slot
device arrays (dtdl_tpu/serve/sampling.py), so greedy and nucleus
requests share the same compiled step.

The engine is the functional core: it owns the model, the (unboxed)
params, and the compile caches, and threads ``(arena, last_tokens)``
state the caller owns.  Continuous batching policy — admission, slot
lifecycle, EOS, telemetry — lives in dtdl_tpu/serve/scheduler.py.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dtdl_tpu.ops.attention import block_table_entry, resolve_blocks
from dtdl_tpu.ops.paged_attention import paged_kernel_enabled
from dtdl_tpu.quant import (Fp8UnsupportedError, canon_kv_dtype,
                            canon_weight_quant, quantize_params, tree_bytes)
from dtdl_tpu.serve.sampling import (FILTER_IMPL, SampleParams,
                                     accept_resample, mask_words, pack,
                                     pack_mask, sample)


class PromptTooLongError(ValueError):
    """A prompt exceeds the largest configured prefill bucket.

    Raised by :meth:`InferenceEngine.bucket_for` BEFORE any prefill
    program is built or traced, with the configured bucket list in the
    message — the scheduler surfaces it as a rejected request
    (``Request.error``) instead of letting one oversized prompt crash a
    run with other requests in flight (dtdl_tpu/serve/scheduler.py).
    """


def default_buckets(max_seq: int, start: int = 16) -> tuple[int, ...]:
    """Power-of-two prompt buckets up to ``max_seq`` (always included):
    each prompt pays at most 2x its own prefill FLOPs in padding, for
    log2(max_seq) compiled prefill programs worst case."""
    out, b = [], start
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(max_seq)
    return tuple(out)


def _snapshot(x, dtype=None):
    """A host value as a program input holding what it holds NOW.

    Dispatch is asynchronous, and on the CPU platform ``jnp.asarray`` (and
    jit's own argument handling) may alias a 64-byte-aligned numpy buffer
    instead of copying it — so a caller that keeps mutating its array
    after the call (the scheduler's per-slot page tables and sampling
    knobs: retire zeroes a table row right after the slot's last decode
    is dispatched) would change what the already-dispatched program
    reads.  A private host copy, which nobody mutates, makes "inputs are
    data as of the call" true on every platform; jit uploads it.  jax
    Arrays are immutable and pass through.
    """
    if isinstance(x, jax.Array):
        return x if dtype is None else x.astype(dtype)
    # audit: ok[host-sync-asarray] host-to-host copy of a host array — device arrays returned above
    return np.array(x, dtype=dtype)


def _paged_cache(arena, page_table, active, index=None):
    """Insert the per-call data leaves (page tables + active mask, and
    optionally an index override) into every block's attn cache dict of
    a paged arena — the leaves :meth:`Attention._paged_attend_slots`
    reads but the arena does not store (they are inputs, re-supplied by
    the host each dispatch; remapping pages never recompiles)."""
    def conv(tree):
        if isinstance(tree, dict):
            if "pages_key" in tree:
                out = dict(tree, page_table=page_table, active=active)
                if index is not None:
                    out["index"] = index
                return out
            return {k: conv(v) for k, v in tree.items()}
        return tree
    return conv(arena)


def _dense_index(arena, index):
    """Override every block's per-slot ``index`` leaf of a DENSE arena
    with the given [n_slots] vector — the chunked-prefill hook (round
    19): a prefill chunk's cache position is host-deterministic, so the
    verify program takes it as DATA (``pos_set``) instead of trusting a
    freed slot's stale index leaf.  Non-forced slots are passed their
    own arena value back, so the override is the identity for them."""
    def conv(tree):
        if isinstance(tree, dict):
            if "key" in tree and "index" in tree:
                return dict(tree, index=index)
            return {k: conv(v) for k, v in tree.items()}
        return tree
    return conv(arena)


def _strip_paged(cache):
    """Drop the per-call leaves back out of a mutated paged cache so the
    returned arena keeps the stable pool+index structure."""
    def conv(tree):
        if isinstance(tree, dict):
            if "pages_key" in tree:
                return {k: v for k, v in tree.items()
                        if k not in ("page_table", "active")}
            return {k: conv(v) for k, v in tree.items()}
        return tree
    return conv(cache)


def _lora_vars(bank, aids):
    """Insert the per-call adapter-id vector into every attention node
    of the LoRA bank tree — the 'lora' collection leaf
    :class:`~dtdl_tpu.models.transformer.Attention` gathers its
    adapter rows by (round 22).  Same per-call-data pattern as
    :func:`_paged_cache`: adapter ids are inputs, never shapes."""
    def conv(tree):
        if isinstance(tree, dict):
            if "q_a" in tree:
                return dict(tree, aid=aids)
            return {k: conv(v) for k, v in tree.items()}
        return tree
    return conv(bank)


class InferenceEngine:
    """Compiled prefill/decode pair over a slotted KV arena (see module
    docstring).  ``n_slots`` is the decode batch width — the one shape
    the decode program is specialized to.

    ``page_size > 0`` switches the arena to the **block-paged** layout:
    instead of ``[n_slots, max_seq]`` K/V rows, a pool of ``n_pages``
    pages of ``page_size`` tokens each (page 0 reserved as the garbage
    page) that per-slot page tables map logical positions onto.  The
    SAME three program families serve both layouts — page tables and
    the active mask enter decode/verify as plain int32/bool inputs, and
    prefill takes the slot's table row plus a ``start`` offset (the
    prefix-cached token count), so a prefix-cache hit re-enters through
    a *smaller suffix bucket* instead of a new program.  ``n_pages``
    defaults to dense-equivalent capacity
    (``n_slots * max_seq / page_size + 1``); undersizing it overcommits
    HBM and shifts admission to the scheduler's page accounting
    (dtdl_tpu/serve/paged.py).

    **Quantized serving** (dtdl_tpu/quant) is two more kwargs.
    ``quantize_weights=True`` swaps the model for its
    ``clone(quantize=True)`` (int8 kernels, dequant fused into every
    matmul) and converts the given f32/bf16 params through
    ``quant.quantize_params`` at construction — decode's per-token
    parameter read drops to one byte per weight.  ``kv_dtype='int8'``
    builds the int8+scales arena variant (dense or paged), halving
    K/V bytes vs bf16 (quartering vs f32) with quantize-on-scatter /
    dequant-on-gather folded into the attention programs.  Both ride
    the SAME three program families — quantization is weights+arena
    layout, never a new compile shape — and ``compile_stats()['quant']``
    carries the exact byte receipts.  For paged arenas,
    ``kv_pool_bytes`` sizes ``n_pages`` from an HBM byte budget
    instead: at a fixed budget an int8 pool holds ~2x the pages of a
    bf16 one (~4x an f32 one) — the slots-per-HBM-byte win.

    **Kernel round 2** adds the fp8 variants through the same kwargs —
    ``quantize_weights='w8f'`` (float8_e4m3fn kernels, bf16 scales) and
    ``kv_dtype='fp8'`` (fp8 pools, bf16 write-once scale sidecars) —
    and ``paged_kernel=`` ('auto' default: on TPU, paged decode/verify
    attend through the Pallas paged-attention kernel in
    dtdl_tpu/ops/paged_attention.py — page-table walk inside the
    kernel, page-granular DMA, dequant folded into the tile loads;
    elsewhere the round-6 gather path.  ``True`` forces the kernel —
    on CPU that means the Pallas interpreter, tests only).  Unsupported
    fp8 combinations refuse by NAME at construction
    (quant.Fp8UnsupportedError), never inside a traced program."""

    def __init__(self, model, params, n_slots: int = 8, buckets=None,
                 observer=None, page_size: int = 0,
                 n_pages: int | None = None,
                 quantize_weights=False, kv_dtype=None,
                 kv_pool_bytes: int | None = None, paged_kernel="auto",
                 mesh=None, rules="tp", lora_rank: int = 0,
                 lora_adapters: int = 0):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if lora_rank < 0 or lora_adapters < 0:
            raise ValueError("lora_rank/lora_adapters must be >= 0")
        if bool(lora_rank) != bool(lora_adapters):
            raise ValueError("pass lora_rank AND lora_adapters together "
                             "(both 0 disables the adapter bank)")
        if lora_adapters == 1:
            raise ValueError("lora_adapters must be >= 2: row 0 is the "
                             "reserved all-zeros base adapter")
        # canonicalization raises the NAMED fp8 errors here, at
        # construction (Fp8UnsupportedError on builds without
        # float8_e4m3fn), never from inside a traced program
        self.weight_mode = canon_weight_quant(quantize_weights)
        self.quantized_weights = self.weight_mode
        self.kv_dtype = canon_kv_dtype(kv_dtype)
        if self.weight_mode == "w8f" and mesh is not None \
                and not isinstance(rules, str):
            raise Fp8UnsupportedError(
                "fp8 weights (quantize_weights='w8f') under a mesh need "
                "a NAMED rule preset (parallel/tensor.py RULE_PRESETS): "
                "the quant rule map derives fp8 kernel+scale specs from "
                "the f32 twin per preset; got a raw rules sequence")
        # kernel round 2: resolve the paged-attention kernel flag ONCE
        # ('auto' -> TPU only; True forces the interpreter on CPU) and
        # bake it into the model as a static field — same three program
        # families, the kernel only changes what decode/verify contain
        self._paged_kernel_flag = paged_kernel
        self.paged_kernel = (paged_kernel_enabled(paged_kernel)
                             and page_size > 0)
        if self.paged_kernel:
            model = model.clone(paged_kernel=True)
        if self.weight_mode:
            # params are the UNQUANTIZED tree the caller trained/loaded;
            # the quantized clone declares the payload+scale schema
            # (int8+f32 or fp8+bf16).  On a mesh, the quant-aware rule
            # map below (round 20) shards the quantized kernels on their
            # f32 twins' logical axes and each _scale sibling alongside
            # its tensor.
            params = quantize_params(model, params, self.weight_mode)
            model = model.clone(quantize=self.weight_mode)
        self.model = model
        self.params = nn.unbox(params)   # plain leaves either way
        # tensor-parallel serving proper (round 19, ROADMAP item 3): a
        # mesh plus a parallel/tensor.py rule preset shards the params
        # (flax logical axes -> mesh axes via logical_shardings) and the
        # KV arena (heads dim on the TP axis) — the engine's jitted
        # programs then run under GSPMD on that mesh, with XLA inserting
        # the Megatron collectives.  A serving engine no longer needs
        # the 4D training mesh: megatron.serve_engine is a thin caller.
        self.mesh = mesh
        self.rules = rules if mesh is not None else None
        self._arena_sh = None
        if mesh is not None:
            import functools

            from dtdl_tpu.parallel.tensor import (heads_axis_size,
                                                  logical_shardings,
                                                  quant_logical_shardings)
            tp = heads_axis_size(mesh, rules)
            if self.model.n_heads % tp:
                raise ValueError(
                    f"n_heads={self.model.n_heads} must divide by the "
                    f"mesh's tensor-parallel axis size {tp} "
                    f"(rules={rules!r})")
            if self.weight_mode:
                # the quantized tree carries no flax logical metadata;
                # the quant rule map derives quantized-kernel + scale
                # specs from the f32 twin (parallel/tensor.py, round
                # 20; mode-aware since kernel round 2 — fp8 leaves
                # shard exactly like their int8 counterparts)
                param_sh = quant_logical_shardings(mesh, self.model,
                                                   rules,
                                                   mode=self.weight_mode)
            else:
                abs_boxed = jax.eval_shape(
                    functools.partial(self.model.init,
                                      jax.random.PRNGKey(0)),
                    jnp.zeros((1, 1), jnp.int32))["params"]
                param_sh = logical_shardings(mesh, abs_boxed, rules)
            self.params = jax.device_put(self.params, param_sh)
        # batched multi-LoRA (round 22): a device-resident adapter bank
        # whose rows per-slot int32 ids gather INSIDE the compiled
        # steps (models/transformer.py) — adapter identity is data, so
        # a mixed-adapter batch rides the same three program families.
        # Row 0 stays all-zeros (the base model); the host registry
        # hot-loads/evicts rows through the manifest-integrity
        # checkpoint path (dtdl_tpu/serve/tenant/lora.py).
        self.lora_rank = lora_rank
        self.lora_adapters = lora_adapters
        self.adapter_bank = None
        if lora_rank:
            from dtdl_tpu.serve.tenant.lora import (AdapterBank,
                                                    adapter_template,
                                                    bank_pspecs,
                                                    init_bank)
            bank = init_bank(self.params, lora_rank, lora_adapters)
            if mesh is not None:
                from jax.sharding import NamedSharding
                bank = jax.tree.map(
                    lambda l, s: jax.device_put(
                        l, NamedSharding(mesh, s)),
                    bank, bank_pspecs(bank))
            self.adapter_bank = AdapterBank(
                bank, adapter_template(self.params, lora_rank),
                observer=observer)
        # neutral per-call tenant inputs, allocated once: the all-zeros
        # adapter-id vector and all-true grammar masks keep every
        # unconstrained dispatch bit-identical to the pre-tenant
        # programs WITHOUT re-uploading per-step arrays.  Masks travel
        # PACKED (round 23): uint32 bitset words, ceil(V/32) per row —
        # 8x fewer host->device bytes than the dense [*, V] bools, which
        # the programs expand on device (sampling.unpack_mask).  Every
        # dispatch packs, so the compiled signature is always uint32 and
        # constrained/unconstrained traffic share one program.
        self._zero_aids = jnp.zeros((n_slots,), jnp.int32)
        self._mask_words = mask_words(model.vocab_size)
        _full = np.uint32(0xFFFFFFFF)
        self._ones_decode = jnp.full((n_slots, self._mask_words), _full,
                                     jnp.uint32)
        self._ones_prefill = jnp.full((1, self._mask_words), _full,
                                      jnp.uint32)
        self._ones_verify: dict[int, object] = {}
        # obs facade: when set (directly or by the Scheduler), the
        # recompile sentinel wraps each compiled program — a retrace of
        # the decode program or a re-trace of an already-built prefill
        # bucket is exactly the serving bug the _cache_size tests pin
        self.observer = observer
        self.n_slots = n_slots
        self.max_seq = model.max_seq
        self.buckets = (tuple(sorted(set(buckets))) if buckets
                        else default_buckets(model.max_seq))
        if self.buckets[-1] > model.max_seq:
            raise ValueError(f"bucket {self.buckets[-1]} exceeds "
                             f"max_seq={model.max_seq}")
        self.paged = page_size > 0
        self.page_size = page_size
        self.page_bytes = 0
        if self.paged:
            if model.max_seq % page_size:
                raise ValueError(f"page_size={page_size} must divide "
                                 f"max_seq={model.max_seq}")
            self.n_ptab = model.max_seq // page_size
            # bytes ONE page pair costs across all blocks (K/V pages
            # plus, for int8, their scale rows) — the pool-sizing and
            # capacity-receipt arithmetic
            self.page_bytes = (
                tree_bytes(model.paged_cache_shapes(
                    1, 3, page_size, self.kv_dtype))
                - tree_bytes(model.paged_cache_shapes(
                    1, 2, page_size, self.kv_dtype)))
            if kv_pool_bytes is not None:
                if n_pages is not None:
                    raise ValueError("pass n_pages or kv_pool_bytes, "
                                     "not both")
                # fixed HBM budget -> as many pages as it holds (the
                # garbage page is part of the pool, so no +1); a
                # budget below the 2-page floor raises like every
                # other undersized geometry instead of silently
                # allocating past the caller's stated bytes
                n_pages = kv_pool_bytes // self.page_bytes
                if n_pages < 2:
                    raise ValueError(
                        f"kv_pool_bytes={kv_pool_bytes} holds "
                        f"{n_pages} pages of {self.page_bytes} bytes; "
                        f"the pool needs >= 2 (garbage page + one "
                        f"live page)")
            self.n_pages = (n_pages if n_pages is not None
                            else n_slots * self.n_ptab + 1)
            if self.n_pages < 2:
                raise ValueError(f"n_pages must be >= 2, got "
                                 f"{self.n_pages}")
        else:
            if n_pages is not None:
                raise ValueError("n_pages requires page_size > 0")
            if kv_pool_bytes is not None:
                raise ValueError("kv_pool_bytes requires page_size > 0")
            self.n_ptab = 0
            self.n_pages = 0
        # single-row cache template the dense prefill program zero-fills
        self._cache1 = model.cache_shapes(1, kv_dtype=self.kv_dtype)
        self._prefill_fns: dict[int, object] = {}
        self._decode_fn = None
        self._verify_fns: dict[int, object] = {}
        # prefill/decode disaggregation (round 19): the page-granular
        # KV handoff pair — one gather program (export a slot's prompt
        # pages to host) and one scatter program (adopt them into this
        # engine's pool + seed the slot's index/last) — both fixed
        # [pages_per_slot] shapes, so a fleet's handoffs never recompile
        self._extract_fn = None
        self._inject_fn = None
        # dispatch counters (NOT in compile_stats, which must stay
        # constant across calls): prefill invocations per bucket — the
        # FLOP receipt prefix-cache tests read, since prefill compute
        # is proportional to sum(bucket * calls)
        self.prefill_calls: dict[int, int] = {}

    # ---- state the caller threads ------------------------------------

    def init_arena(self):
        """Fresh zeroed KV arena (donated to every program): dense
        [n_slots, max_seq] rows, or the paged pool + per-slot indices.
        On a TP mesh the K/V leaves come back sharded heads-on-'model'
        (parallel/tensor.py:serve_arena_shardings), so the compiled
        programs inherit the tensor-parallel layout from their inputs."""
        if self.mesh is not None:
            if self._arena_sh is None:
                from dtdl_tpu.parallel.tensor import serve_arena_shardings
                self._arena_sh = serve_arena_shardings(
                    self.mesh, self.arena_shapes(), self.rules)
            return jax.tree.map(
                lambda s, sh: jax.device_put(
                    jnp.zeros(s.shape, s.dtype), sh),
                self.arena_shapes(), self._arena_sh)
        return self._beside_params(jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.arena_shapes()))

    def init_last_tokens(self):
        """The [n_slots] last-sampled-token vector (NOT donated: the
        scheduler's lag harvest holds references to past vectors)."""
        last = jnp.zeros((self.n_slots,), jnp.int32)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            return jax.device_put(
                last, NamedSharding(self.mesh, PartitionSpec()))
        return self._beside_params(last)

    def _beside_params(self, tree):
        """Fresh state the caller threads goes where the params live.
        When the caller committed the params to a device
        (``jax.device_put``), every program output is committed too —
        an uncommitted first arena would then meet a committed second
        one, and each program would trace twice (the recompile sentinel
        fires on the second prefill)."""
        leaf = jax.tree.leaves(self.params)[0]
        if getattr(leaf, "committed", False):
            return jax.device_put(tree, leaf.sharding)
        return tree

    # ---- bucketing ----------------------------------------------------

    def bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        raise PromptTooLongError(
            f"prompt length {length} exceeds the largest prefill bucket "
            f"{self.buckets[-1]} (buckets={self.buckets}, "
            f"max_seq={self.max_seq})")

    # ---- compiled programs -------------------------------------------

    def _build_prefill(self, T: int):
        model, cache1 = self.model, self._cache1
        use_lora = self.lora_rank > 0

        def prefill(params, arena, last, tokens, length, slot, key,
                    temp, top_k, top_p, allowed, aid, lora):
            cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 cache1)
            variables = {"params": params, "cache": cache}
            if use_lora:
                variables["lora"] = _lora_vars(lora, aid[None])
            hidden, muts = model.apply(
                variables, tokens, decode=True,
                return_hidden=True, mutable=["cache"])
            # logits of the last REAL position only (pad rows beyond
            # `length` never touch the head)
            h_last = jax.lax.dynamic_slice_in_dim(
                hidden, length - 1, 1, axis=1)[:, 0]           # [1, D]
            logits = jnp.einsum(
                "bd,vd->bv", h_last,
                params["embed"].astype(model.dtype)).astype(jnp.float32)
            tok = sample(logits, key, temp, top_k, top_p,
                         allowed=allowed)                      # [1]

            def write(a, n):
                if n.ndim == 0:   # index leaf: the true prompt length,
                    return jax.lax.dynamic_update_slice(   # not bucket T
                        a, length[None].astype(a.dtype), (slot,))
                # K/V buffers [1,H,S,D] and (int8 arenas) their scale
                # rows [1,H,S] land in arena row `slot`
                return jax.lax.dynamic_update_slice(
                    a, n.astype(a.dtype), (slot,) + (0,) * (n.ndim - 1))
            arena = jax.tree.map(write, arena, muts["cache"])
            last = jax.lax.dynamic_update_slice(last, tok, (slot,))
            return arena, last, logits[0]

        return jax.jit(prefill, donate_argnums=(1,))

    def _build_prefill_paged(self, T: int):
        model = self.model
        use_lora = self.lora_rank > 0

        def prefill(params, arena, last, tokens, length, slot, start,
                    page_row, key, temp, top_k, top_p, allowed, aid,
                    lora):
            # a single-row paged view over the SHARED (donated) pool:
            # the slot's table row, index at `start` (= the number of
            # prefix-cached tokens already resident in shared pages) —
            # the suffix attends the cached prefix through the same
            # gather path decode uses, which is what makes a prefix hit
            # a smaller-bucket prefill instead of a new program family
            cache = _paged_cache(arena, page_row[None],
                                 jnp.ones((1,), bool),
                                 index=start[None])
            variables = {"params": params, "cache": cache}
            if use_lora:
                variables["lora"] = _lora_vars(lora, aid[None])
            hidden, muts = model.apply(
                variables, tokens, decode=True,
                return_hidden=True, mutable=["cache"])
            # logits of the last REAL suffix position only
            h_last = jax.lax.dynamic_slice_in_dim(
                hidden, length - 1, 1, axis=1)[:, 0]           # [1, D]
            logits = jnp.einsum(
                "bd,vd->bv", h_last,
                params["embed"].astype(model.dtype)).astype(jnp.float32)
            tok = sample(logits, key, temp, top_k, top_p,
                         allowed=allowed)                      # [1]
            new_cache = _strip_paged(muts["cache"])

            def write(a, n):
                if a.ndim == 1:   # [n_slots] index: start + true length
                    return jax.lax.dynamic_update_slice(
                        a, (start + length)[None].astype(a.dtype),
                        (slot,))
                return n          # the pool, updated through the table
            arena = jax.tree.map(write, arena, new_cache)
            last = jax.lax.dynamic_update_slice(last, tok, (slot,))
            return arena, last, logits[0]

        return jax.jit(prefill, donate_argnums=(1,))

    def _build_decode(self):
        model, paged = self.model, self.paged
        use_lora = self.lora_rank > 0

        def decode(params, arena, last, active, tables, key, temp,
                   top_k, top_p, allowed, aids, lora):
            cache = (_paged_cache(arena, tables, active) if paged
                     else arena)
            variables = {"params": params, "cache": cache}
            if use_lora:
                variables["lora"] = _lora_vars(lora, aids)
            logits, muts = model.apply(
                variables, last[:, None],
                decode=True, mutable=["cache"])
            new_cache = (_strip_paged(muts["cache"]) if paged
                         else muts["cache"])

            def fix(old, new):
                if old.ndim == 1:   # index: only active slots advance
                    return jnp.where(active, new, old)
                return new          # garbage K/V writes into dead slots
            arena = jax.tree.map(fix, arena, new_cache)  # (paged: routed
            # to the garbage page inside the model, never a live page)

            lg = logits[:, 0].astype(jnp.float32)              # [B, V]
            tok = sample(lg, key, temp, top_k, top_p, allowed=allowed)
            last = jnp.where(active, tok, last)
            return arena, last, lg

        return jax.jit(decode, donate_argnums=(1,))

    def _build_verify(self, k: int):
        model, paged = self.model, self.paged
        use_lora = self.lora_rank > 0

        def verify(params, arena, last, draft, draft_len, active,
                   forced, first_tok, pos_set, tables, key, temp,
                   top_k, top_p, allowed, aids, lora):
            # the slots' pre-step cache positions: every block's index
            # leaf carries the same per-slot values, take the first.
            # Chunked-prefill rows (forced) take their position from
            # pos_set instead — the prefill cursor is host truth, and a
            # freshly-admitted slot's arena index leaf is the previous
            # occupant's stale value
            pos = next(l for l in jax.tree.leaves(arena) if l.ndim == 1)
            pos = jnp.where(forced, pos_set, pos)
            cache = (_paged_cache(arena, tables, active, index=pos)
                     if paged else _dense_index(arena, pos))
            # forced rows feed their chunk's first token in place of the
            # last sampled one: x = the k+1-token window written at
            # pos..pos+k (prompt chunk for forced rows, last+drafts for
            # speculative ones — same program, per-slot data)
            x0 = jnp.where(forced, first_tok, last)
            x = jnp.concatenate([x0[:, None], draft], axis=1)  # [B,k+1]
            variables = {"params": params, "cache": cache}
            if use_lora:
                variables["lora"] = _lora_vars(lora, aids)
            logits, muts = model.apply(
                variables, x, decode=True,
                mutable=["cache"])
            new_cache = (_strip_paged(muts["cache"]) if paged
                         else muts["cache"])
            tokens, n_acc = accept_resample(
                logits.astype(jnp.float32), draft, draft_len, key,
                temp, top_k, top_p, forced=forced, allowed=allowed)
            n_em = n_acc + 1

            def fix(old, new):
                if old.ndim == 1:
                    # roll the index back from the model's +k+1 to the
                    # committed n_accepted+1; inactive slots stay put
                    return jnp.where(active, pos + n_em, old)
                return new      # garbage K/V past the committed index is
            arena = jax.tree.map(fix, arena, new_cache)  # overwritten
            # before it is attended (see module docstring)
            new_last = jnp.take_along_axis(
                tokens, n_acc[:, None], axis=1)[:, 0]
            last = jnp.where(active, new_last, last)
            tokens = jnp.where(active[:, None], tokens, 0)
            n_em = jnp.where(active, n_em, 0)
            return arena, last, tokens, n_em

        return jax.jit(verify, donate_argnums=(1,))

    def arena_shapes(self):
        """Abstract pytree of the engine's KV arena (no allocation)."""
        if self.paged:
            return self.model.paged_cache_shapes(
                self.n_slots, self.n_pages, self.page_size,
                self.kv_dtype)
        return self.model.cache_shapes(self.n_slots,
                                       per_slot_index=True,
                                       kv_dtype=self.kv_dtype)

    def compile_stats(self) -> dict:
        """Compiled-program counts — the no-per-request-recompile
        receipt: one entry per touched prefill bucket, one per touched
        verify draft-width bucket, one decode program, each with a jit
        cache size that must stay 1.  ``paged`` carries the arena
        layout (None = dense; else page geometry): the SAME program
        families serve both layouts, so a paged engine's receipt is the
        same shape as a dense one's — page tables are data, not shapes.
        (Per-call occupancy — pages_in_use, prefix hit rates — is
        scheduler state, reported by ServeMetrics; this dict stays
        constant across calls so receipts can be compared.)

        ``kernels`` is the kernel-configuration receipt (round 13):
        which attention block-table entry the model's (head_dim,
        max_seq) geometry resolves to — ``explicit`` must be True for
        every shipped preset (no silent fallback; the autotune table in
        dtdl_tpu/ops/attention.py is the single source of tile shapes)
        — and which sampling implementation the decode/verify programs
        fold in (``sortless`` = the threshold-bisection hot path).

        ``quant`` is the BYTE receipt of the quantization layer
        (SCALING.md "Quantized serving arithmetic"): ``param_bytes``
        (what every decode step re-reads), the arena split into K/V
        payload vs int8 scale sidecars, and
        ``decode_hbm_bytes_per_token`` — the full-occupancy
        bandwidth-model upper bound ``(param_bytes + kv_arena_bytes) /
        n_slots``, i.e. the numerator of the serving-latency roofline;
        shrinking it IS the TPU decode speedup."""
        def n(f):
            try:
                return f._cache_size()
            except AttributeError:   # pragma: no cover - jax internals
                return -1
        payload = scales = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.arena_shapes())[0]:
            name = path[-1].key
            nbytes = (int(np.prod(leaf.shape))
                      * np.dtype(leaf.dtype).itemsize)
            if name.endswith("_scale"):
                scales += nbytes
            elif name != "index":
                payload += nbytes
        param_bytes = tree_bytes(self.params)
        hd = self.model.head_dim
        entry = block_table_entry(hd, self.max_seq, causal=True)
        # resolve through the same path the kernels use, so a retuned
        # table/default shows up here without touching this call site
        blocks = resolve_blocks(hd, self.max_seq, causal=True)
        return {"prefill": {T: n(f) for T, f in self._prefill_fns.items()},
                # disaggregation handoff pair (round 19): at most one
                # compiled program each, whatever the migration traffic
                "handoff": {
                    "extract": n(self._extract_fn)
                    if self._extract_fn else 0,
                    "inject": n(self._inject_fn)
                    if self._inject_fn else 0,
                },
                # tensor-parallel geometry (round 19): constant config,
                # None on a single-chip engine
                "tp": ({"rules": self.rules,
                        "mesh": dict(self.mesh.shape)}
                       if self.mesh is not None else None),
                "kernels": {
                    "attention_blocks": {
                        "head_dim": hd, "max_seq": self.max_seq,
                        "block_q": blocks[0], "block_k": blocks[1],
                        "explicit": entry is not None,
                    },
                    "sampling": FILTER_IMPL,
                    # kernel round 2: whether decode/verify attend
                    # through the Pallas paged kernel (page-granular
                    # DMA, scale fusion in the tile loads) instead of
                    # the whole-pool gather — same program families
                    # either way, so this is config, not a count
                    "paged_attention": {
                        "requested": self._paged_kernel_flag,
                        "enabled": self.paged_kernel,
                        "page_size": self.page_size,
                        "fused_scales": self.kv_dtype is not None,
                    },
                },
                "decode": n(self._decode_fn) if self._decode_fn else 0,
                "verify": {k: n(f) for k, f in self._verify_fns.items()},
                "paged": ({"page_size": self.page_size,
                           "n_pages": self.n_pages,
                           "pages_per_slot": self.n_ptab,
                           "page_bytes": self.page_bytes}
                          if self.paged else None),
                # multi-LoRA geometry (round 22): constant config — the
                # bank is a fixed [n_adapters, ...] allocation whatever
                # the load/evict traffic, and adapter ids are data, so
                # a LoRA engine's program counts above are unchanged
                "lora": ({"rank": self.lora_rank,
                          "n_adapters": self.lora_adapters,
                          "bank_bytes": tree_bytes(
                              self.adapter_bank.bank)}
                         if self.lora_rank else None),
                "quant": {
                    "weights": self.quantized_weights,
                    "kv_dtype": (None if self.kv_dtype is None
                                 else "int8"
                                 if self.kv_dtype == jnp.int8
                                 else "fp8"),
                    "param_bytes": param_bytes,
                    "kv_payload_bytes": payload,
                    "kv_scale_bytes": scales,
                    "kv_arena_bytes": payload + scales,
                    "decode_hbm_bytes_per_token": round(
                        (param_bytes + payload + scales)
                        / self.n_slots),
                }}

    # ---- the two entry points ----------------------------------------

    def _lora_args(self, adapter_ids, scalar: bool = False):
        """Normalize the per-call adapter ids + bank pair: the cached
        zero vector (base adapter everywhere) and the live bank tree
        for LoRA engines; unused scalar placeholders otherwise."""
        if self.lora_rank:
            if adapter_ids is None:
                aids = (jnp.zeros((), jnp.int32) if scalar
                        else self._zero_aids)
            else:
                aids = _snapshot(adapter_ids, jnp.int32)
            return aids, self.adapter_bank.bank
        if adapter_ids is not None:
            raise ValueError("adapter ids require an adapter bank "
                             "(lora_rank/lora_adapters > 0)")
        return jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)

    def prefill(self, arena, last_tokens, slot: int, prompt,
                sampling: SampleParams = SampleParams(), key=None,
                page_row=None, start: int = 0, adapter_id=None,
                allowed=None):
        """Admit ``prompt`` into arena row ``slot``; returns the updated
        ``(arena, last_tokens, logits[V])`` — ``last_tokens[slot]`` is
        the request's first sampled token.

        Paged engines take two extras: ``page_row`` — the slot's
        [pages_per_slot] int32 page table row (prefix-cache-hit pages
        first, freshly allocated pages for the rest of the prompt,
        garbage-page 0 beyond) — and ``start``, the number of
        prefix-cached tokens already resident in shared pages
        (page-aligned).  ``prompt`` is then only the UNCACHED suffix:
        the program re-enters through the suffix's (smaller) bucket,
        which is exactly the prefill-FLOPs-skipped win a cache hit
        buys (see ``prefill_calls``)."""
        # audit: ok[host-sync-asarray] admission-time conversion of the caller's host prompt list
        prompt = np.asarray(prompt, np.int32).ravel()
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.n_slots})")
        if self.paged:
            if page_row is None:
                raise ValueError("paged engine prefill needs the slot's "
                                 "page_row (see Scheduler)")
            if start % self.page_size or start < 0:
                raise ValueError(f"start={start} must be a non-negative "
                                 f"multiple of page_size="
                                 f"{self.page_size}")
            # audit: ok[host-sync-asarray] admission-time COPY of the caller's host page_row, which keeps changing (see _snapshot)
            page_row = np.array(page_row, np.int32).ravel()
            if page_row.size != self.n_ptab:
                raise ValueError(f"page_row must have {self.n_ptab} "
                                 f"entries, got {page_row.size}")
        elif page_row is not None or start:
            raise ValueError("page_row/start require a paged engine "
                             "(page_size > 0)")
        if start + prompt.size > self.max_seq:
            raise ValueError(f"prompt length {start + prompt.size} "
                             f"exceeds max_seq={self.max_seq}")
        T = self.bucket_for(prompt.size)
        if start + T > self.max_seq:
            # the PADDED window must fit too: the kernel clamps pos to
            # max_seq - T, so an overshooting bucket would silently
            # shift the whole write window backward over cached prefix
            # pages.  The scheduler caps prefix hits so this never
            # fires (_admit); reaching it means a caller supplied its
            # own too-large start.
            raise ValueError(
                f"prefix start {start} + padded bucket {T} exceeds "
                f"max_seq={self.max_seq}; map fewer prefix pages so "
                f"the suffix bucket fits")
        if T not in self._prefill_fns:
            fn = (self._build_prefill_paged(T) if self.paged
                  else self._build_prefill(T))
            if self.observer is not None:
                fn = self.observer.watch(fn, f"serve.prefill[{T}]")
            self._prefill_fns[T] = fn
        self.prefill_calls[T] = self.prefill_calls.get(T, 0) + 1
        padded = np.zeros((1, T), np.int32)
        padded[0, :prompt.size] = prompt
        key = jax.random.PRNGKey(0) if key is None else key
        aid, lora = self._lora_args(adapter_id, scalar=True)
        allowed = (self._ones_prefill if allowed is None
                   else jnp.asarray(pack_mask(allowed)))
        if self.paged:
            arena, last, logits = self._prefill_fns[T](
                self.params, arena, last_tokens, jnp.asarray(padded),
                jnp.asarray(prompt.size, jnp.int32),
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(start, jnp.int32), jnp.asarray(page_row),
                key, *pack([sampling]), allowed, aid, lora)
        else:
            arena, last, logits = self._prefill_fns[T](
                self.params, arena, last_tokens, jnp.asarray(padded),
                jnp.asarray(prompt.size, jnp.int32),
                jnp.asarray(slot, jnp.int32), key, *pack([sampling]),
                allowed, aid, lora)
        return arena, last, logits

    def _tables_arg(self, page_tables):
        """Validate/normalize the decode/verify page-tables input: the
        [n_slots, pages_per_slot] int32 map for paged engines, a scalar
        placeholder (unused in the trace) for dense ones."""
        if not self.paged:
            if page_tables is not None:
                raise ValueError("page_tables require a paged engine")
            return jnp.zeros((), jnp.int32)
        if page_tables is None:
            raise ValueError("paged engine needs page_tables (see "
                             "Scheduler)")
        page_tables = _snapshot(page_tables, jnp.int32)
        if page_tables.shape != (self.n_slots, self.n_ptab):
            raise ValueError(f"page_tables must be [{self.n_slots}, "
                             f"{self.n_ptab}], got {page_tables.shape}")
        return page_tables

    def decode(self, arena, last_tokens, active, key, temp, top_k,
               top_p, page_tables=None, adapter_ids=None, allowed=None):
        """One token for every active slot; ``active`` is a [n_slots]
        bool mask (a runtime value — occupancy never recompiles).
        Paged engines additionally take the [n_slots, pages_per_slot]
        ``page_tables`` (data, re-supplied each call — remapping never
        recompiles).  Returns ``(arena, last_tokens,
        logits[n_slots, V])``."""
        if self._decode_fn is None:
            fn = self._build_decode()
            if self.observer is not None:
                fn = self.observer.watch(fn, "serve.decode")
            self._decode_fn = fn
        aids, lora = self._lora_args(adapter_ids)
        allowed = (self._ones_decode if allowed is None
                   else jnp.asarray(pack_mask(allowed)))
        return self._decode_fn(self.params, arena, last_tokens,
                               _snapshot(active),
                               self._tables_arg(page_tables), key,
                               _snapshot(temp), _snapshot(top_k),
                               _snapshot(top_p), allowed, aids, lora)

    def verify(self, arena, last_tokens, draft_tokens, draft_len, active,
               key, temp, top_k, top_p, page_tables=None, forced=None,
               first_tok=None, pos_set=None, adapter_ids=None,
               allowed=None):
        """One speculative verify pass over every slot: score each slot's
        ``draft_len[b]`` candidate tokens (``draft_tokens[b, :]``, zero-
        padded to the program's width k) in one parameter sweep, accept a
        prefix on device, advance each slot's cache index by its own
        ``n_accepted + 1``.  Returns ``(arena, last_tokens,
        tokens[n_slots, k+1], n_emitted[n_slots])`` — ``tokens[b,
        :n_emitted[b]]`` is what slot b emitted this step (its last entry
        is the new ``last_tokens[b]``), inactive slots emit 0 tokens.

        **Chunked prefill rides this same program** (round 19): a row
        with ``forced[b]`` True is a prompt chunk, not a speculation —
        its window is ``first_tok[b]`` plus ``draft_len[b]`` further
        prompt tokens in ``draft_tokens[b]``, written at the
        host-supplied cache position ``pos_set[b]`` (the prefill cursor;
        a freed slot's arena index leaf is stale), accepted
        unconditionally (``n_emitted = draft_len + 1``), with the bonus
        token sampled from the last chunk position's target distribution
        — on the prompt's final chunk that IS the request's first
        generated token, from the same distribution whole-prompt prefill
        samples.  Decode steps, speculative verifies and prefill chunks
        therefore share ONE compiled step per width bucket: all three
        are per-slot data on the same program.  Omitting the three
        kwargs (or passing None) is exactly the pre-round-19 verify.

        The caller must guarantee every active slot has room for the
        full write window: ``index[b] + k + 1 <= max_seq`` (the
        scheduler settles worst-case indices before dispatch; a clamped
        scatter would corrupt live cache rows — for a forced row it
        would shift the window backward over its own already-written
        prompt positions).  ``k`` is a compile shape — one compiled
        program per draft width, see :meth:`compile_stats`.
        """
        draft_tokens = _snapshot(draft_tokens, jnp.int32)
        if draft_tokens.ndim != 2 or draft_tokens.shape[0] != self.n_slots:
            raise ValueError(f"draft_tokens must be [n_slots={self.n_slots}"
                             f", k], got {draft_tokens.shape}")
        k = int(draft_tokens.shape[1])
        if k < 1:
            raise ValueError("verify needs k >= 1 draft positions; use "
                             "decode for a plain step")
        if k + 1 > self.max_seq:
            raise ValueError(f"draft width {k} cannot fit "
                             f"max_seq={self.max_seq}")
        B = self.n_slots
        forced = (jnp.zeros((B,), bool) if forced is None
                  else _snapshot(forced, bool))
        first_tok = (jnp.zeros((B,), jnp.int32) if first_tok is None
                     else _snapshot(first_tok, jnp.int32))
        pos_set = (jnp.zeros((B,), jnp.int32) if pos_set is None
                   else _snapshot(pos_set, jnp.int32))
        if k not in self._verify_fns:
            fn = self._build_verify(k)
            if self.observer is not None:
                fn = self.observer.watch(fn, f"serve.verify[{k}]")
            self._verify_fns[k] = fn
        aids, lora = self._lora_args(adapter_ids)
        if allowed is None:
            if k not in self._ones_verify:
                self._ones_verify[k] = jnp.full(
                    (B, k + 1, self._mask_words),
                    np.uint32(0xFFFFFFFF), jnp.uint32)
            allowed = self._ones_verify[k]
        else:
            allowed = jnp.asarray(pack_mask(allowed))
        return self._verify_fns[k](
            self.params, arena, last_tokens, draft_tokens,
            _snapshot(draft_len, jnp.int32), _snapshot(active),
            forced, first_tok, pos_set,
            self._tables_arg(page_tables), key, _snapshot(temp),
            _snapshot(top_k), _snapshot(top_p), allowed, aids, lora)

    # ---- prefill/decode disaggregation: page-granular KV handoff ------

    def _build_extract(self):
        def extract(arena, ids):
            def conv(tree):
                if isinstance(tree, dict):
                    if "pages_key" in tree:
                        # every pool leaf (K/V pages and, on int8
                        # arenas, their scale siblings) gathered at the
                        # same page ids; the per-slot index stays home
                        return {k: jnp.take(v, ids, axis=0)
                                for k, v in tree.items() if k != "index"}
                    return {k: conv(v) for k, v in tree.items()}
                return tree
            return conv(arena)
        return jax.jit(extract)

    def _build_inject(self):
        def inject(arena, last, data, ids, slot, index, first):
            def conv(tree, dtree):
                if isinstance(tree, dict):
                    if "pages_key" in tree:
                        out = {}
                        for k, v in tree.items():
                            if k == "index":
                                # the adopted sequence decodes from its
                                # prompt length, exactly as if this
                                # engine had prefilled it
                                out[k] = jax.lax.dynamic_update_slice(
                                    v, index[None].astype(v.dtype),
                                    (slot,))
                            else:
                                # pad rows carry page id 0: their zero
                                # payload lands on the reserved garbage
                                # page, never a live one
                                out[k] = v.at[ids].set(
                                    dtree[k].astype(v.dtype))
                        return out
                    return {k: conv(v, dtree[k]) for k, v in tree.items()}
                return tree
            arena = conv(arena, data)
            last = jax.lax.dynamic_update_slice(last, first[None], (slot,))
            return arena, last
        return jax.jit(inject, donate_argnums=(0,))

    def extract_pages(self, arena, page_ids):
        """Export ``page_ids`` (a slot's prompt pages, logical order) to
        HOST memory — the source half of prefill/decode disaggregation
        (round 19): a prefill-role replica pulls the finished prompt's
        K/V pages off device here and the Router carries them to a
        decode replica's :meth:`inject_pages`.  Returns a host pytree
        mirroring the pool-leaf structure, each leaf ``[len(page_ids),
        ...]``.  This is the ONE deliberate device sync of the handoff
        path (the ``kv_handoff_s`` metric); everything else stays
        dispatch-only."""
        if not self.paged:
            raise ValueError("KV handoff requires a paged engine "
                             "(page_size > 0)")
        n = len(page_ids)
        if not 0 < n <= self.n_ptab:
            raise ValueError(f"need 1..{self.n_ptab} pages, got {n}")
        ids = np.zeros(self.n_ptab, np.int32)    # pad -> garbage page 0
        ids[:n] = page_ids
        if self._extract_fn is None:
            fn = self._build_extract()
            if self.observer is not None:
                fn = self.observer.watch(fn, "serve.kv_extract")
            self._extract_fn = fn
        # audit: ok[host-sync-get] the ONE deliberate sync of the KV handoff (metered as kv_handoff_s)
        host = jax.device_get(self._extract_fn(arena, jnp.asarray(ids)))
        return jax.tree.map(lambda a: a[:n], host)

    def inject_pages(self, arena, last_tokens, data, page_ids, slot: int,
                     index: int, first_token: int):
        """Adopt extracted prompt pages into THIS engine's pool: write
        ``data`` (an :meth:`extract_pages` result) into ``page_ids``
        (freshly allocated by the target scheduler), set slot ``slot``'s
        cache index to ``index`` (the prompt length) and its last-token
        entry to ``first_token`` — after which the slot decodes through
        the ordinary decode/verify programs exactly as if this engine
        had prefilled the prompt itself (greedy token-identity is the
        disaggregation acceptance oracle).  One compiled program, all
        arguments data.  Returns ``(arena, last_tokens)``."""
        if not self.paged:
            raise ValueError("KV handoff requires a paged engine "
                             "(page_size > 0)")
        n = len(page_ids)
        leaves = jax.tree.leaves(data)
        if not leaves or any(a.shape[0] != n for a in leaves):
            raise ValueError(f"data leaves must carry {n} pages "
                             f"(one per page id)")
        if not 0 < n <= self.n_ptab:
            raise ValueError(f"need 1..{self.n_ptab} pages, got {n}")
        if any(not 0 < p < self.n_pages for p in page_ids):
            raise ValueError(f"page ids must be in [1, {self.n_pages}), "
                             f"got {list(page_ids)}")
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.n_slots})")
        if not 0 < index < self.max_seq:
            raise ValueError(f"index {index} must be in (0, "
                             f"{self.max_seq}) — a full-to-the-brim "
                             f"sequence has nothing left to decode")
        ids = np.zeros(self.n_ptab, np.int32)
        ids[:n] = page_ids

        def pad(a):
            # audit: ok[host-sync-asarray] pads extract_pages output — already host memory
            a = np.asarray(a)
            out = np.zeros((self.n_ptab,) + a.shape[1:], a.dtype)
            out[:n] = a
            return out

        if self._inject_fn is None:
            fn = self._build_inject()
            if self.observer is not None:
                fn = self.observer.watch(fn, "serve.kv_inject")
            self._inject_fn = fn
        return self._inject_fn(
            arena, last_tokens, jax.tree.map(pad, data),
            jnp.asarray(ids), jnp.asarray(slot, jnp.int32),
            jnp.asarray(index, jnp.int32),
            jnp.asarray(first_token, jnp.int32))

    def extract_pages_batch(self, arena, page_ids):
        """Export ANY number of pages in ONE host sync — the spill-on-
        evict primitive (round 23).  ``page_ids`` is chunked into
        ``n_ptab``-wide dispatches of the SAME compiled gather as
        :meth:`extract_pages` (fixed ``[n_ptab]`` id shape — zero new
        program families), every chunk is dispatched before anything is
        read, and a single ``jax.device_get`` collects them all: the
        sync cost of spilling N evicted pages is one round trip, not N.
        Returns a host pytree mirroring the pool-leaf structure, each
        leaf ``[len(page_ids), ...]`` in input order."""
        if not self.paged:
            raise ValueError("KV handoff requires a paged engine "
                             "(page_size > 0)")
        n = len(page_ids)
        if n < 1:
            raise ValueError("need at least one page id")
        if self._extract_fn is None:
            fn = self._build_extract()
            if self.observer is not None:
                fn = self.observer.watch(fn, "serve.kv_extract")
            self._extract_fn = fn
        futs = []
        for i in range(0, n, self.n_ptab):
            chunk = page_ids[i:i + self.n_ptab]
            ids = np.zeros(self.n_ptab, np.int32)  # pad -> garbage page 0
            ids[:len(chunk)] = chunk
            futs.append(self._extract_fn(arena, jnp.asarray(ids)))
        # audit: ok[host-sync-get] the ONE deliberate sync of a batched spill (all chunks dispatched above; metered as spill_s)
        host = jax.device_get(futs)
        trimmed = [jax.tree.map(
            lambda a, m=min(self.n_ptab, n - i): a[:m], out)
            for i, out in zip(range(0, n, self.n_ptab), host)]
        if len(trimmed) == 1:
            return trimmed[0]
        return jax.tree.map(lambda *xs: np.concatenate(xs, axis=0),
                            *trimmed)

    def inject_pages_batch(self, arena, last_tokens, items):
        """Adopt several extracted page groups — ``items`` of ``(data,
        page_ids, slot, index, first_token)`` — in one dispatch-only
        pass: every group rides the SAME compiled scatter as
        :meth:`inject_pages` (the donated arena threads through), and
        since inject was never the sync side of the handoff there are
        ZERO host syncs here regardless of group count.  Returns
        ``(arena, last_tokens)``."""
        for data, page_ids, slot, index, first_token in items:
            arena, last_tokens = self.inject_pages(
                arena, last_tokens, data, page_ids, slot, index,
                first_token)
        return arena, last_tokens
