"""train_step.device_ms: device time of the jitted train step's programs
in the traced window, per step completed, averaged over the chips."""

STEP_PROGRAM = "train_step"


def read(ctx):
    p = ctx.profile
    if p is None or not p.chips or ctx.steps <= 0:
        return None
    per_chip = [sum(e - s for name, s, e in c.modules if STEP_PROGRAM in name)
                for c in p.chips]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) * 1e-6 / ctx.steps
