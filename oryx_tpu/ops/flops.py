"""Analytic FLOP accounting and chip-peak lookup for MFU reporting.

The reference publishes qps/latency tables but no utilization measure
(docs/docs/performance.html); on TPU the honest perf bar is MFU —
achieved FLOP/s over the chip's dense peak — because it distinguishes
"fast" from "underutilized" (a serving kernel can beat a 437-qps CPU
baseline a hundredfold while using 2% of the MXU). The FLOP counts here
are analytic lower bounds over the dominant matmul/einsum terms only
(top-k selection, masking and solves are excluded unless noted), so the
reported MFU slightly understates true utilization — never the reverse.
"""

from __future__ import annotations

# Dense per-chip matmul peak in FLOP/s at bf16, from public spec sheets
# (cloud.google.com/tpu/docs/system-architecture-tpu-vm; v5e: "TPU v5e",
# 197 TFLOP/s bf16 and 393 TOP/s int8 per chip). The f32 figure
# is taken as half the bf16 peak — the convention for chips that run f32
# matmuls as multi-pass bf16 on the MXU.
_PEAK_BF16 = {
    "v2": 45e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}

# Dense int8 OP/s peaks. v5e/v5p/v6e run int8 at 2x the bf16 rate on the
# MXU; earlier generations have no int8 fast path and score int8 operands
# at the bf16 rate after conversion. An MFU for a quantized dispatch must
# divide by THIS peak — dividing int8 throughput by the bf16 peak would
# flatter a quantized kernel by up to 2x on chips with int8 support.
_PEAK_INT8 = {
    "v2": 45e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5e": 393e12,
    "v5p": 918e12,
    "v6e": 1836e12,
}


def normalize_dtype(dtype: str) -> str:
    """Canonical dtype tag for peak lookup: int8 / bfloat16 / float32.
    Unrecognized tags conservatively map to bfloat16 (the serving
    default), never to the higher int8 peak."""
    d = str(dtype).lower()
    if d in ("int8", "i8", "s8"):
        return "int8"
    if d in ("float32", "f32"):
        return "float32"
    return "bfloat16"


def peak_flops_for_kind(device_kind: str, dtype: str = "bfloat16") -> float | None:
    """Per-chip dense peak FLOP/s for a jax device_kind string at the
    dtype actually dispatched (int8 / bfloat16 / float32), or None when
    the chip generation can't be identified (MFU is then omitted rather
    than guessed)."""
    kind = device_kind.lower()
    if "v6" in kind or "trillium" in kind:
        gen = "v6e"
    elif "v5p" in kind:
        gen = "v5p"
    elif "v5 lite" in kind or "v5e" in kind or "v5litepod" in kind:
        gen = "v5e"
    elif "v4" in kind:
        gen = "v4"
    elif "v3" in kind:
        gen = "v3"
    elif "v2" in kind:
        gen = "v2"
    else:
        return None
    d = normalize_dtype(dtype)
    if d == "int8":
        return _PEAK_INT8[gen]
    peak = _PEAK_BF16[gen]
    if d == "float32":
        peak /= 2
    return peak


def device_peak_flops(dtype: str = "bfloat16") -> float | None:
    """Peak FLOP/s of jax's default device; None off-TPU (no honest CPU
    peak is derivable from here) or for unknown TPU generations."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        return None
    return peak_flops_for_kind(getattr(d, "device_kind", "") or "", dtype)


def topk_score_flops(n_queries: int, n_items: int, features: int) -> float:
    """FLOPs for exact top-k scoring: one [B,F]x[F,I] matmul = 2·B·I·F
    (selection excluded)."""
    return 2.0 * n_queries * n_items * features


def als_halfstep_flops(n_rows: int, pad_width: int, k: int, n_fixed: int) -> float:
    """Analytic FLOPs for one ALS half-sweep over n_rows padded lists of
    width pad_width against k features (ops/als.py _half_step): the
    normal-equation einsum 2·B·P·K² + the RHS einsum 2·B·P·K, plus the
    fixed side's gram 2·M·K². Cholesky/solves (O(B·K³/3)) excluded."""
    return (
        2.0 * n_rows * pad_width * k * k
        + 2.0 * n_rows * pad_width * k
        + 2.0 * n_fixed * k * k
    )


def mfu(achieved_flops_per_s: float, peak: float | None) -> float | None:
    """Model FLOPs Utilization in [0,1], or None when no peak is known."""
    if not peak or peak <= 0:
        return None
    return achieved_flops_per_s / peak
