"""What the package's layers may know of each other, and that the
lists the static gates walk still name code that exists."""

import ast
import inspect
import pathlib
import re

import materialize_tpu

PKG = pathlib.Path(materialize_tpu.__file__).parent
# The data plane: what a step program is rendered from and runs on.
LOWER = ("render", "ops", "arrangement", "repr", "expr", "parallel")
# The control plane and the durable one, which stand on it.
UPPER = ("coord", "server", "storage")


def _imported(path: pathlib.Path):
    """(line, absolute module) of every import in the file, at module
    level or inside a function, relative ones resolved."""
    package = ("materialize_tpu",) + path.relative_to(PKG).parts[:-1]
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            base = ".".join(
                package[: len(package) - node.level + 1]
                if node.level
                else ()
            )
            mod = ".".join(p for p in (base, node.module) if p)
            yield node.lineno, mod
            for alias in node.names:  # `from .. import coord`
                yield node.lineno, f"{mod}.{alias.name}"


def test_the_data_plane_imports_nothing_from_the_planes_above_it():
    upward = [
        f"{path.relative_to(PKG.parent)}:{line} imports {mod}"
        for layer in LOWER
        for path in sorted((PKG / layer).rglob("*.py"))
        for line, mod in _imported(path)
        if mod.split(".")[:1] == ["materialize_tpu"]
        and mod.split(".")[1:2] in ([u] for u in UPPER)
    ]
    assert upward == []


def test_every_gated_function_resolves():
    """The host-sync lint's hot path and recorder path and the donated
    dispatch sites are (module, qualname) lists: every entry names a
    function that exists."""
    from materialize_tpu.analysis import donation, host_sync

    assert set(host_sync.RECORDER_PATH) <= set(host_sync.DEFAULT_HOT_PATH)
    listed = host_sync.DEFAULT_HOT_PATH + donation.DONATED_DISPATCH_SITES
    assert len(listed) > 20
    for module, qualname in listed:
        assert inspect.isfunction(host_sync._resolve(module, qualname)), (
            module, qualname,
        )


def test_every_registered_dyncfg_is_read_somewhere():
    """A ``Config`` nobody reads is an option that selects nothing:
    each one's constant or its name must appear in the package outside
    ``utils/dyncfg.py``."""
    registry = PKG / "utils" / "dyncfg.py"
    configs = re.findall(
        r'^([A-Z][A-Z0-9_]*) = Config\(\s*"([a-z0-9_]+)"',
        registry.read_text(),
        re.MULTILINE,
    )
    assert len(configs) >= 30
    rest = "\n".join(
        p.read_text() for p in sorted(PKG.rglob("*.py")) if p != registry
    )
    unread = [
        name
        for const, name in configs
        if not re.search(rf"\b{const}\b", rest)
        and not re.search(rf"""["']{name}["']""", rest)
    ]
    assert unread == []
