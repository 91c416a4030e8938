"""exchange_ms (device trace): device time per step, mean over chips, of
the ops in the step's exchange phase (``phases.hlo_phases``: op_name under
``train.exchange``: the replicas' collectives and their scaling), whether
or not other work overlaps them (``exchange_exposed_ms`` counts only the
part that nothing overlaps)."""
import phases


def read(ctx):
    return phases.phase_ms(ctx, "exchange")
