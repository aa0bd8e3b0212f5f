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
    """A 1 s window with one batch a call, each a ``feed.batch`` span that
    ends inside it, and one before it that does not count."""
    ev = [{"plane": "/host:CPU", "line": "python", "name": tracing.WINDOW_SPAN,
           "t0": 0.0, "dur": 1e9},
          {"plane": "/host:CPU", "line": "feed", "name": "feed.batch", "t0": -5e6, "dur": 4e6}]
    for i in range(calls):
        ev.append({"plane": "/host:CPU", "line": "feed", "name": "feed.batch",
                   "t0": 1e6 * (i + 1) - 1e4, "dur": 2e6})
        ev.append({"plane": "/device:TPU:0", "line": tracing.OPS_LINE,
                   "name": "_scalar_kernel", "hlo_module": module,
                   "t0": 1e6 * (i + 1), "dur": kernel_us * 1e3})
        ev.append({"plane": "/device:TPU:0", "line": tracing.OPS_LINE, "name": "pad.1",
                   "hlo_module": module, "t0": 1e6 * (i + 1) - 5e3, "dur": 1e3})
    return ev


def test_kernel_roofline_from_trace():
    ctx = {"events": _events(1000.0, 4), "batch_tokens": 65536,
           "dict_len_mean": 50304.0, "bits": 16, "device_kind": "TPU v5 lite"}
    want = 100 * roofline.dict_decode_bytes(65536, 50304) / 819e9 / 1e-3
    assert roofline.kernel_roofline(ctx, "dict_decode", r"_scalar_kernel") == pytest.approx(want)
    # no call of the kernel in the window: nothing to read, never 0
    assert roofline.kernel_roofline(ctx, "bitunpack", r"_kernel") is None
    assert roofline.kernel_roofline(dict(ctx, events=None), "dict_decode", "x") is None
    # kernel calls but no batch of the program's in the window: nothing either
    no_batch = [e for e in ctx["events"] if e["name"] != "feed.batch"]
    assert roofline.kernel_roofline(dict(ctx, events=no_batch), "dict_decode", "_scalar") is None


def _batches_on_planes(kernel, n_batches, n_planes, batch_us):
    """``n_batches`` batches, each decoded by one call on each of
    ``n_planes`` chips, the calls of a batch summing to ``batch_us``."""
    ev = [{"plane": "/host:CPU", "line": "python", "name": tracing.WINDOW_SPAN,
           "t0": 0.0, "dur": 1e9}]
    for i in range(n_batches):
        t = 1e6 * (i + 1)
        ev.append({"plane": "/host:CPU", "line": "feed", "name": "feed.batch",
                   "t0": t - 1e4, "dur": 5e5})
        for p in range(n_planes):
            ev.append({"plane": f"/device:TPU:{p}", "line": tracing.OPS_LINE,
                       "name": "%k = s32[8] custom-call(), custom_call_target=\"tpu_custom_call\"",
                       "hlo_module": f"jit_{kernel}({p})", "t0": t, "dur": batch_us * 1e3 / n_planes})
    return ev


@pytest.mark.parametrize("kernel", ["dict_decode", "bitunpack"])
def test_the_same_work_reads_the_same_on_one_chip_or_four(kernel):
    """One call a batch on one chip, and a quarter call a batch on each of
    four, with the same total kernel time, read the same share."""
    from bench import spec

    ctx = {"batch_tokens": 131072, "dict_len_mean": 50304.0, "bits": 16,
           "device_kind": "TPU v5 lite"}
    read = spec.load_reader(f"{kernel}_roofline.feed")  # with the reader's own op regex
    one = read(dict(ctx, events=_batches_on_planes(kernel, 6, 1, 400.0)))
    four = read(dict(ctx, events=_batches_on_planes(kernel, 6, 4, 400.0)))
    assert one is not None and four == pytest.approx(one, rel=1e-9)
    need = (roofline.dict_decode_bytes(131072, 50304) if kernel == "dict_decode"
            else roofline.bitunpack_bytes(131072, 16))
    assert one == pytest.approx(100 * need / 819e9 / 400e-6, rel=1e-9)
