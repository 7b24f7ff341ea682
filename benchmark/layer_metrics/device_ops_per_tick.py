"""Device-operation events of the traced stretch over the ticks the
view absorbed in it: how many launches one tick of maintenance costs."""


def read(ctx: dict):
    tr = ctx.get("trace")
    if not tr or not tr["ticks"] or not tr["device_ops"]:
        return None
    return tr["device_ops"] / tr["ticks"]
