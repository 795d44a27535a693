"""Every priced operation of the program(s) ``train.update_program`` calls (or under
``ds_apply_update`` in a fused step) against what it has to do: the sum of the floors (the
state read and written once at the chip's HBM rate, from the program's own ``cost``) over
the operations' device time in the trace. None without a trace or ``cost``, and where more
than 2 % of the window is the update's unpriced time."""

from benchmarks import op_roofline


def read(record):
    return op_roofline.side_share(record, op_roofline.UPDATE, op_roofline.PRICED)
