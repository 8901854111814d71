"""Goodput / MFU accounting: analytic FLOPs in, roofline fractions out.

The MFU arithmetic as a library, fed per drained window so every loop
can report ``mfu`` / ``tokens_per_sec`` / achieved-vs-roofline
continuously.  Three pieces:

* **analytic model FLOPs** — :func:`lm_train_flops` (TransformerLM from
  its config) and
  :func:`netspec_flops` (Caffe-style CNNs from their parsed LayerSpecs).
  Analytic counts are the honest MFU numerator on TPU: XLA's
  ``cost_analysis()`` cannot see inside Pallas custom-calls and misses
  the flash-attention FLOPs entirely.  The
  convention is matmul-only model FLOPs — causal attention at the
  computed half, backward at 2x forward, recompute never credited, and
  elementwise work (rope — fused into the kernels since round 13 —
  norms, activations) never counted.
* **chip peaks** — :func:`peak_flops_per_chip` (public bf16 figures by
  exact device_kind; None on CPU, an unknown accelerator raises).
* :class:`GoodputMeter` — turns (steps, seconds) windows into the
  metric fields, using only numbers the drain already produced: no
  device syncs, per the PR-1 discipline.
"""

from __future__ import annotations

from typing import Optional


# Dense bf16 peak FLOP/s per chip, keyed by the EXACT ``device_kind`` jax
# reports (the spellings jax's own pallas tpu_info matches; v5e reports
# "TPU v5 lite", v5p "TPU v5").  Public figures (Google Cloud TPU docs):
# v2 45T, v3 123T, v4 275T, v5e 197T, v5p 459T, v6e (Trillium) 918T.
_PEAK_BF16 = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops_per_chip() -> Optional[float]:
    """bf16 peak of the local chip, by exact ``device_kind``.

    ``None`` on the cpu platform (no MFU is reported there).  An
    accelerator whose kind is not in the table raises: a substring guess
    would print an MFU computed against another chip's peak under this
    device's name."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    if dev.device_kind not in _PEAK_BF16:
        raise ValueError(
            f"no bf16 peak on record for device_kind "
            f"{dev.device_kind!r} (platform {dev.platform!r}); add it to "
            f"dtdl_tpu/obs/goodput.py:_PEAK_BF16 with its source "
            f"(known: {sorted(_PEAK_BF16)})")
    return _PEAK_BF16[dev.device_kind]


def lm_forward_flops(cfg, batch: int, seq: int) -> float:
    """Matmul-only FLOPs of one LM forward over ``seq`` positions.

    ``cfg`` is a TransformerLM (or anything with d_model / n_heads /
    head_dim / d_ff / n_layers / vocab_size).  Causal attention is
    counted at the *computed half* (the flash kernel skips
    above-diagonal tiles) — conservative vs quoting dense S² work.
    MoE layers count ACTIVATED expert compute (top_k x the dense MLP);
    router/dispatch/capacity overhead is deliberately not credited.
    """
    if getattr(cfg, "hybrid", False):
        raise ValueError(
            "the dense formula does not hold for a hybrid model (linear-"
            "attention layers, grouped-query gated attention, held experts "
            "with a shared one): no FLOP count for it here; the benchmark "
            "counts one (benchmarks/families/qwen3_next.py:train_flops)")
    t = seq
    qkvo = 4 * 2 * batch * t * cfg.d_model * (cfg.n_heads * cfg.head_dim)
    attn = 2 * 2 * batch * cfg.n_heads * t * t * cfg.head_dim * 0.5
    mlp = 3 * 2 * batch * t * cfg.d_model * cfg.d_ff
    head = 2 * batch * t * cfg.d_model * cfg.vocab_size
    n_moe = 0
    if getattr(cfg, "n_experts", 0) and hasattr(cfg, "moe_every"):
        n_moe = cfg.n_layers // cfg.moe_every
    return (cfg.n_layers * (qkvo + attn) + (cfg.n_layers - n_moe) * mlp
            + n_moe * getattr(cfg, "moe_top_k", 1) * mlp + head)


def lm_train_flops(cfg, batch: int, seq: int) -> float:
    """Matmul-only model FLOPs for one LM *train* step (fwd + 2x bwd).

    The train step predicts ``seq - 1`` next tokens, so the forward is
    counted over seq-1 positions; backward at the standard 2x forward
    (the kernel's recompute overhead is NOT credited).  The benchmark's
    ``step_mfu`` uses the same count (``benchmarks/lib/flops.py``, pinned
    equal to this one by ``benchmarks/tests/test_arithmetic.py``).
    """
    return 3.0 * lm_forward_flops(cfg, batch, seq - 1)


def lm_decode_flops(cfg, batch: int, context: int) -> float:
    """Matmul-only FLOPs of ONE batched decode step at KV length
    ``context``: every weight matmul at seq=1 plus the attention reads
    against the cache.  The per-token serving MFU numerator (decode is
    HBM-bound, so this fraction is honest about how far below peak the
    phase must sit — SCALING.md "Serving latency model")."""
    if getattr(cfg, "hybrid", False):
        raise ValueError("a hybrid model (linear-attention layers, held "
                         "experts) does not decode: no FLOP count for it")
    qkvo = 4 * 2 * batch * cfg.d_model * (cfg.n_heads * cfg.head_dim)
    attn = 2 * 2 * batch * cfg.n_heads * context * cfg.head_dim
    mlp = 3 * 2 * batch * cfg.d_model * cfg.d_ff
    head = 2 * batch * cfg.d_model * cfg.vocab_size
    n_moe = 0
    if getattr(cfg, "n_experts", 0) and hasattr(cfg, "moe_every"):
        n_moe = cfg.n_layers // cfg.moe_every
    return (cfg.n_layers * (qkvo + attn) + (cfg.n_layers - n_moe) * mlp
            + n_moe * getattr(cfg, "moe_top_k", 1) * mlp + head)


def lm_prefill_flops(cfg, prompt_len: int) -> float:
    """Forward-only FLOPs of prefilling one prompt (batch 1)."""
    return lm_forward_flops(cfg, 1, prompt_len)


def lm_verify_flops(cfg, batch: int, context: int, k: int) -> float:
    """Matmul-only FLOPs of ONE speculative verify pass scoring k drafts
    (k+1 query positions) per slot at KV length ``context``.

    Essentially ``(k+1) x lm_decode_flops`` — verify stays bandwidth-
    bound on TPU (the same full parameter read as decode) but amortizes
    it over up to k+1 accepted tokens, which is the whole speculative-
    decoding trade (SCALING.md "Speculative decoding arithmetic").
    Goodput itself needs no new field: accepted tokens flow through the
    serve metrics' delivered-token count, so ``decode tokens/sec``
    already counts real tokens, never drafts.
    """
    return (k + 1) * lm_decode_flops(cfg, batch, context)


# ---------------------------------------------------------------------------
# CNN FLOPs from a Caffe netspec
# ---------------------------------------------------------------------------

def _pair(param, key: str, default: int) -> tuple:
    v = param.get_scalar(key, None)
    if v is None:
        return (int(param.get_scalar(key + "_h", default)),
                int(param.get_scalar(key + "_w", default)))
    return int(v), int(v)


def _caffe_pool_out(size: int, k: int, s: int, pad: int) -> int:
    # Caffe sizes pooling with CEIL (netspec.py mirrors this in padding)
    out = -(-(size + 2 * pad - k) // s) + 1
    if pad and (out - 1) * s >= size + pad:
        out -= 1
    return max(out, 1)


def netspec_flops(specs, input_shape, phase: str = "TRAIN",
                  backward: bool = False) -> float:
    """Matmul/conv-only analytic FLOPs of one forward pass through a
    parsed Caffe net (``dtdl_tpu.models.netspec.parse_net`` LayerSpecs,
    or a prototxt path / Message).

    ``input_shape`` is one example's (H, W, C).  Elementwise layers
    (ReLU/LRN/Dropout/Softmax) and pooling count 0 — the MFU-numerator
    convention credits only the dense math.  ``backward=True`` adds the
    standard 2x for the backward pass (one train step = 3x forward).
    Multiply by the batch size for a step's total.
    """
    from dtdl_tpu.models.netspec import parse_net
    from dtdl_tpu.utils.prototxt import Message, parse_file

    if isinstance(specs, str):
        specs = parse_net(parse_file(specs))
    elif isinstance(specs, Message):
        specs = parse_net(specs)

    h, w, c = (int(x) for x in input_shape)
    flat = None                      # set once an InnerProduct flattens
    total = 0.0
    for spec in specs:
        if not spec.in_phase(phase):
            continue
        p = spec.params
        if spec.type == "Convolution":
            cp = p.get_scalar("convolution_param", Message())
            kh, kw = _pair(cp, "kernel_size", 3)
            sh, sw = _pair(cp, "stride", 1)
            ph, pw = _pair(cp, "pad", 0)
            cout = int(cp.get_scalar("num_output"))
            group = int(cp.get_scalar("group", 1))
            oh = (h + 2 * ph - kh) // max(sh, 1) + 1
            ow = (w + 2 * pw - kw) // max(sw, 1) + 1
            total += 2.0 * kh * kw * (c // group) * cout * oh * ow
            if bool(cp.get_scalar("bias_term", True)):
                total += float(cout * oh * ow)
            h, w, c, flat = oh, ow, cout, None
        elif spec.type == "Pooling":
            pp = p.get_scalar("pooling_param", Message())
            if bool(pp.get_scalar("global_pooling", False)):
                h = w = 1
                continue
            kh, kw = _pair(pp, "kernel_size", 2)
            sh, sw = _pair(pp, "stride", 1)
            ph, pw = _pair(pp, "pad", 0)
            h = _caffe_pool_out(h, kh, max(sh, 1), ph)
            w = _caffe_pool_out(w, kw, max(sw, 1), pw)
        elif spec.type == "InnerProduct":
            ip = p.get_scalar("inner_product_param", Message())
            nin = flat if flat is not None else h * w * c
            nout = int(ip.get_scalar("num_output"))
            total += 2.0 * nin * nout
            if bool(ip.get_scalar("bias_term", True)):
                total += float(nout)
            flat = nout
        elif spec.type == "Flatten":
            flat = h * w * c
        # Data/ReLU/LRN/Dropout/Softmax/losses: 0 by convention
    return total * (3.0 if backward else 1.0)


# ---------------------------------------------------------------------------
# the meter
# ---------------------------------------------------------------------------

class GoodputMeter:
    """Per-window goodput fields from numbers the drain already has.

    Configure once with the workload's analytic per-step FLOPs (and
    per-step token count for LMs); each :meth:`window` call converts a
    settled (steps, seconds) window into reporter-ready fields.

    Denominator convention: ``peak_flops="auto"`` (the default) detects
    ONE chip's peak; ``None`` disables MFU outright (throughput fields
    only).  When ``flops_per_step`` covers a step sharded across several
    local devices, pass ``peak_flops=peak_flops_per_chip() * n_devices``
    explicitly — the auto single-chip default would inflate mfu by the
    device count.  A
    ``roofline_mfu`` target (the best ``step_mfu`` the ledger holds for
    the configuration, as a fraction)
    adds ``vs_roofline`` — the achieved fraction of what this chip has
    *demonstrated*, which is the regression signal ``mfu`` alone (a
    fraction of an unreachable dense peak) is too noisy to give.
    """

    def __init__(self, flops_per_step: Optional[float] = None,
                 tokens_per_step: Optional[float] = None,
                 samples_per_step: Optional[float] = None,
                 peak_flops="auto",
                 roofline_mfu: Optional[float] = None):
        self.flops_per_step = flops_per_step
        self.tokens_per_step = tokens_per_step
        self.samples_per_step = samples_per_step
        self.peak_flops = (peak_flops_per_chip() if peak_flops == "auto"
                           else peak_flops)
        self.roofline_mfu = roofline_mfu
        self.total_steps = 0
        self.total_seconds = 0.0
        self._exp_steps = 0
        self._exp_seconds = 0.0

    def window(self, steps: int, seconds: float) -> dict:
        """Goodput fields for one settled window (empty if degenerate)."""
        if steps <= 0 or seconds <= 0:
            return {}
        self.total_steps += steps
        self.total_seconds += seconds
        return self._fields(steps, seconds)

    def _fields(self, steps: int, seconds: float) -> dict:
        out = {"steps_per_sec": round(steps / seconds, 3)}
        if self.tokens_per_step:
            out["tokens_per_sec"] = round(
                self.tokens_per_step * steps / seconds, 1)
        if self.samples_per_step:
            out["samples_per_sec"] = round(
                self.samples_per_step * steps / seconds, 2)
        if self.flops_per_step:
            achieved = self.flops_per_step * steps / seconds
            out["achieved_tflops"] = round(achieved / 1e12, 4)
            if self.peak_flops:
                mfu = achieved / self.peak_flops
                out["mfu"] = round(mfu, 4)
                if self.roofline_mfu:
                    out["vs_roofline"] = round(mfu / self.roofline_mfu, 3)
        return out

    def totals(self) -> dict:
        """Whole-run goodput (same fields over the summed windows)."""
        if self.total_steps <= 0 or self.total_seconds <= 0:
            return {}
        return self._fields(self.total_steps, self.total_seconds)

    def export_window(self) -> dict:
        """Delta since the last :meth:`export_window` call — the no-arg
        source a :class:`~dtdl_tpu.obs.export.MetricsExporter` samples
        at drain boundaries (register as ``exporter.add_source(
        "goodput", meter.export_window)``; keys are bare, the source
        name prefixes them).  Fields cover the steps the loops settled
        via :meth:`window` in the interval: the per-window goodput set
        plus ``steps`` and the mean ``step_time_s`` — the gauge
        ``default_train_slos()`` judges step-time SLOs on.  Empty on an
        idle interval (the SLO layer's gate skips those)."""
        dsteps = self.total_steps - self._exp_steps
        dsecs = self.total_seconds - self._exp_seconds
        self._exp_steps = self.total_steps
        self._exp_seconds = self.total_seconds
        if dsteps <= 0 or dsecs <= 0:
            return {}
        out = self._fields(dsteps, dsecs)
        out["steps"] = dsteps
        out["step_time_s"] = round(dsecs / dsteps, 6)
        return out
