"""Model step: model FLOPs of the real tokens in the traced chunk steps
(``benchkit.workcount.step_flops``) over those steps' device time times
the chip's peak."""
from benchkit.readers import step_mfu


def read(rec):
    return step_mfu(rec, "chunk")
