"""Model step: model FLOPs of the real tokens in the traced chunk steps
(``step_flops`` of the tenant's architecture) over those steps' device
time times the chip's peak."""
from benchkit.readers import step_mfu


def read(rec):
    return step_mfu(rec, "chunk")
