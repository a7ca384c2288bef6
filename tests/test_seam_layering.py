"""The dispatch seam (`ops/dispatch`) is the one place that knows how a
codec takes a unit: the bulk engines above it hand every codec spans of
their maps and import no backend.  Read from the sources, not run."""

import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent / "seaweedfs_tpu"
BACKENDS = ("seaweedfs_tpu.native", "seaweedfs_tpu.ops.native_codec")


def _imports(path: pathlib.Path) -> set[str]:
    """Every module a file imports, at any depth of its code, and every
    name it imports from a package (`from seaweedfs_tpu import native`
    as `seaweedfs_tpu.native`)."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            out.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            out.add(node.module)
            out.update(f"{node.module}.{alias.name}" for alias in node.names)
    return out


@pytest.mark.parametrize("engine", ["storage/ec/ec_files.py",
                                    "ops/fleet_convert.py"])
def test_the_bulk_engines_import_no_backend(engine):
    got = _imports(ROOT / engine)
    assert "seaweedfs_tpu.ops.dispatch" in got  # the seam they go through
    assert not [m for m in got if m.startswith(BACKENDS)], sorted(got)
