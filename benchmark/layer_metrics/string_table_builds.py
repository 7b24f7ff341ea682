"""String side-tables (``expr/strings.py``: one per LIKE or other
string function of a view, over the whole dictionary) that the replica
built inside the window: summed ``string_tables_built`` of the ``span``
records committed inside the window. After hydration the dictionary
stands still and this is 0; a build means it moved, and the step may
compile again for a larger table. A program whose spans do not carry
the attribute reads None."""

from program_spans import load


def read(ctx: dict):
    got = load(ctx)
    if not got:
        return None
    built = [s["attrs"].get("string_tables_built") for s in got["spans"]]
    if all(b is None for b in built):
        return None
    return float(sum(b or 0 for b in built))
