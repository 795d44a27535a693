"""Device time of the gradient program's compiled operations that hold no product
(``op_roofline``'s class ``memory``) over the traced window: what fusing, a kernel or a
layout can still remove. None without a trace or ``cost``, and where more than 2 % of the
window is the gradient program's unpriced time."""

from benchmarks import op_roofline


def read(record):
    return op_roofline.side_share(record, op_roofline.GRADIENT, (op_roofline.MEMORY,),
                                  of_window=True)
