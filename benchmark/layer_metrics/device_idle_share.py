"""Percent of the traced stretch in which no operation ran on the
device: 1 - union of device-operation intervals over its length."""


def read(ctx: dict):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"] or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
