"""The bytes of device memory that the judged view's operator state and
output spine reserve (capacities x stored row widths), as the program
says in the ``state_capacity_bytes`` attribute of its ``span`` records:
the value of the last span committed inside the window. A regrow inside
the window would show as a step; the cells allow none. A program whose
spans do not carry the attribute reads None."""

from program_spans import load


def read(ctx: dict):
    got = load(ctx)
    if not got:
        return None
    last = max(got["spans"], key=lambda s: s["attrs"]["upper"])
    value = last["attrs"].get("state_capacity_bytes")
    return None if value is None else float(value)
