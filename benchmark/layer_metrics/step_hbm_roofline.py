"""The step's share of the HBM roofline: the least time to move the
bytes one tick must touch (``roofline.step_bytes``, from shapes alone,
whatever implements the step) at the device's peak bandwidth, over the
device-busy time one tick took in the traced stretch. Bound by bytes:
the step does integer arithmetic only, no matrix unit work."""


def read(ctx: dict):
    tr = ctx.get("trace")
    if not tr or not tr["ticks"] or not tr["busy_s"]:
        return None
    from roofline import hbm_roofline_share, step_bytes

    sh = ctx["shapes"]
    return hbm_roofline_share(
        step_bytes(sh["live_rows"], sh["delta_rows_per_tick"],
                   sh["row_bytes"]),
        tr["busy_s"] / tr["ticks"],
        ctx["peaks"]["hbm_bytes_per_s"],
    )
