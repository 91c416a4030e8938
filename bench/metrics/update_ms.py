"""update_ms (device trace): device time per step, mean over chips, of
the ops in the step's update phase (``phases.hlo_phases``: op_name under
``train.update``: the optimizer's update, its apply and the skip select)."""
import phases


def read(ctx):
    return phases.phase_ms(ctx, "update")
