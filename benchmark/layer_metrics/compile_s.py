"""Seconds the replica's compile ledger (``mz_compile_log``) recorded
before the window opened: compiles, XLA-cache loads and bank loads."""


def read(ctx: dict):
    rows = ctx["compile_before"]
    if not rows:
        return None
    return sum(r[2] for r in rows)
