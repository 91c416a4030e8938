"""forward_ms (device trace): device time per step, mean over chips, of
the ops in the step's forward phase (``phases.hlo_phases``: op_name under
``jvp(train.loss)``, the named scope ``core/steps.py`` puts round the
loss)."""
import phases


def read(ctx):
    return phases.phase_ms(ctx, "forward")
