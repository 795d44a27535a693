"""The gradient program's compiled products (every operation the compiler made around a
``dot`` or a ``convolution``: forward, second forward and backward; no kernel, no
collective) against what they have to do: the sum of their floors (the larger of the
products' operations over the chip's peak and the operation's HBM bytes over its rate,
from the program's own ``cost``) over their device time in the trace. None without a trace
or ``cost``, and where more than 2 % of the window is the gradient program's unpriced time."""

from benchmarks import op_roofline


def read(record):
    return op_roofline.side_share(record, op_roofline.GRADIENT, (op_roofline.PRODUCT,))
