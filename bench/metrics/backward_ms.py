"""backward_ms (device trace): device time per step, mean over chips, of
the ops in the step's backward phase (``phases.hlo_phases``: op_name under
``transpose(jvp(train.loss))``)."""
import phases


def read(ctx):
    return phases.phase_ms(ctx, "backward")
