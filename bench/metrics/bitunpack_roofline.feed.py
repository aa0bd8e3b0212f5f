"""``bitunpack``'s share of its memory roofline: the bytes the unpack of the
window's batches needs (``roofline.bitunpack_bytes``) over HBM bandwidth, against
the traced time of the kernel's operations on every chip. A CPU trace has no
device plane, and reads nothing."""
from bench.roofline import kernel_roofline

OP = r"custom-call\(.*tpu_custom_call"  # the Pallas kernel, not the padding around it


def read(ctx):
    return kernel_roofline(ctx, "bitunpack", OP)
