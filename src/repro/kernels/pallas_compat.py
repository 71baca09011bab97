"""Execution-mode default shared by the Pallas kernel entry points."""
import jax


def interpret_default() -> bool:
    """Backend-derived default for a kernel's ``interpret=`` knob: CPU hosts
    (tests, CI containers) run the Pallas interpreter, a TPU backend
    compiles to Mosaic. Module-level kernel entry points take
    ``interpret=None`` and resolve it here, so callers never hardcode the
    execution mode."""
    return jax.default_backend() != "tpu"
