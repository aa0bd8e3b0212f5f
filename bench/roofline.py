"""The yardstick for kernels: peaks of the chip, and the bytes each decode
operation needs, whatever implements it.

Peaks of one chip, keyed by ``jax.Device.device_kind``. Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s. A
device kind that is not here is an error, not a default.
"""
from __future__ import annotations

from typing import Dict, Optional

from bench import spans, tracing

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}") from None


def dict_decode_bytes(n_tokens: int, dict_len: float) -> float:
    """4 B of code read and 4 B of id written per token, and the split's
    own dictionary (its entries, not the padded table) read once."""
    return 8.0 * n_tokens + 4.0 * dict_len


def bitunpack_bytes(n_tokens: int, bits: int) -> float:
    """``bits/8`` B of packed words read and a 4 B code written per token."""
    return n_tokens * bits / 8.0 + 4.0 * n_tokens


def kernel_roofline(ctx: Dict, kernel: str, op: str) -> Optional[float]:
    """Percent of the least time in the traced time of the operations ``op``
    (a regex) of the jitted ``kernel``, summed over every device plane.

    The least time is the bytes that the window's batches need over one
    chip's HBM bandwidth: each ``feed.batch`` span that ends inside the
    window counts ``batch_tokens`` tokens and its split's dictionary once.
    So the same work reads the same, whether one call on one chip decodes a
    batch or a call a chip on several. None where the trace holds no such
    operation or no batch; a CPU trace has no device plane."""
    got = spans.window_batches(ctx.get("events"))
    ops = tracing.kernel_ops(ctx["events"], kernel, op) if got is not None else []
    if not ops:
        return None
    t = ctx["batch_tokens"]
    if kernel == "dict_decode":
        per_batch = dict_decode_bytes(t, ctx["dict_len_mean"])
    elif kernel == "bitunpack":
        per_batch = bitunpack_bytes(t, ctx["bits"])
    else:
        raise KeyError(kernel)
    seconds = sum(e["dur"] for e in ops) / 1e9
    least = len(got[2]) * per_batch / peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds
