"""The gradient program's compiled operations that hold no product (elementwise passes,
reductions, gathers, copies, the waits for asynchronous copies) against what they have to
move: the sum of their HBM bytes (each result once, each distinct operand once, a slice at
what it takes: the program's own ``cost``) over the chip's HBM rate, over their device time
in the trace. A plain elementwise pass measured 590 of 819 GB/s on this chip: 72 % is near
the most this can read. None without a trace or ``cost``, and where more than 2 % of the
window is the gradient program's unpriced time."""

from benchmarks import op_roofline


def read(record):
    return op_roofline.side_share(record, op_roofline.GRADIENT, (op_roofline.MEMORY,))
