"""The work each decode operation needs, and the roofline share from it."""
import pytest

import bench_tiny  # noqa: F401
from bench import roofline, tracing


def test_work_functions():
    # 16 x 4,096 tokens, a 50,304-entry dictionary: 4 B of code in, 4 B out
    assert roofline.dict_decode_bytes(65536, 50304) == 8 * 65536 + 4 * 50304
    # 16-bit codes: 2 B in, 4 B out a token
    assert roofline.bitunpack_bytes(65536, 16) == 6 * 65536
    assert roofline.bitunpack_bytes(4096, 8) == 5 * 4096


def test_unknown_device_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def _events(kernel_us, calls, module="jit_dict_decode(12)"):
    ev = [{"plane": "/host:CPU", "line": "python", "name": tracing.WINDOW_SPAN,
           "t0": 0.0, "dur": 1e9}]
    for i in range(calls):
        ev.append({"plane": "/device:TPU:0", "line": tracing.OPS_LINE,
                   "name": "_scalar_kernel", "hlo_module": module,
                   "t0": 1e6 * (i + 1), "dur": kernel_us * 1e3})
        ev.append({"plane": "/device:TPU:0", "line": tracing.OPS_LINE, "name": "pad.1",
                   "hlo_module": module, "t0": 1e6 * (i + 1) - 5e3, "dur": 1e3})
    return ev


def test_kernel_roofline_from_trace():
    ctx = {"events": _events(1000.0, 4), "decode_tokens_per_call": 65536,
           "dict_len_mean": 50304.0, "bits": 16, "device_kind": "TPU v5 lite"}
    want = 100 * roofline.dict_decode_bytes(65536, 50304) / 819e9 / 1e-3
    assert roofline.kernel_roofline(ctx, "dict_decode", r"_scalar_kernel") == pytest.approx(want)
    # no call of the kernel in the window: nothing to read, never 0
    assert roofline.kernel_roofline(ctx, "bitunpack", r"_kernel") is None
    assert roofline.kernel_roofline(dict(ctx, events=None), "dict_decode", "x") is None
