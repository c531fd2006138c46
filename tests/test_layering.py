"""The layering of the call path (DESIGN.md §18): ``repro.kernels.stencil``
is the sweep kernel and its launcher only.  Importing it loads none of
the layers above it — the IR, the planner, the shard path, the call
entry — and its imports name none of them outside the one documented
forwarder (``__getattr__``, which resolves ``stencil_iterate`` for the
benchmark harness).  ``repro.kernels.ops``, the tile reports beside it,
names none of them either."""

import ast
import glob
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir, "src")
)
KERNEL = os.path.join(SRC, "repro", "kernels", "stencil.py")
ABOVE = ("repro.ir", "repro.plan", "repro.parallel", "repro.entry")


def _above(name):
    return any(name == a or name.startswith(a + ".") for a in ABOVE)


def _imported_modules(tree, package):
    """Every module an import statement in ``tree`` names, relative
    imports resolved against ``package``."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out += [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                parts = package.split(".")[: len(package.split("."))
                                           - node.level + 1]
                base = ".".join(parts + ([base] if base else []))
            out.append(base)
            out += [f"{base}.{alias.name}" for alias in node.names]
    return out


def test_kernel_module_loads_and_names_no_layer_above_it():
    code = (
        "import sys\n"
        "import repro.kernels.stencil\n"
        "print('\\n'.join(m for m in sys.modules if m.startswith('repro')))"
    )
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    loaded = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, check=True,
    ).stdout.split()
    assert "repro.kernels.stencil" in loaded
    assert [m for m in loaded if _above(m)] == []

    with open(KERNEL) as f:
        tree = ast.parse(f.read())
    forwarders = [
        node for node in tree.body
        if isinstance(node, ast.FunctionDef) and node.name == "__getattr__"
    ]
    assert len(forwarders) == 1
    tree.body.remove(forwarders[0])
    named = _imported_modules(tree, "repro.kernels")
    assert [m for m in named if _above(m)] == []
    # The forwarder resolves exactly the benchmark's one name.
    assert _imported_modules(forwarders[0], "repro.kernels") == [
        "repro.entry", "repro.entry.stencil_iterate",
    ]
    # The tile-report helpers beside the kernel name no layer above it.
    with open(os.path.join(SRC, "repro", "kernels", "ops.py")) as f:
        named = _imported_modules(ast.parse(f.read()), "repro.kernels")
    assert [m for m in named if _above(m)] == []


# What each package may not name, and its one documented forwarder (the
# benchmark harness calls ``repro.ir.run_program``).
_PACKAGE_RULES = {
    "plan": (("repro.kernels.stencil", "repro.entry"), None),
    "ir": (("repro.kernels", "repro.plan", "repro.entry"),
           ["repro.entry", "repro.entry.run"]),
}


@pytest.mark.parametrize("package", sorted(_PACKAGE_RULES))
def test_package_names_no_layer_above_it(package):
    """``repro.plan`` names neither the kernel nor the entry (the tuner
    gets its runner as an argument); ``repro.ir`` names neither the
    kernels, the planner nor the entry, outside its forwarder."""
    banned, forwarded = _PACKAGE_RULES[package]
    seen_forwarder = []
    for path in sorted(glob.glob(os.path.join(SRC, "repro", package,
                                              "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in list(tree.body):
            if isinstance(node, ast.FunctionDef) \
                    and node.name == "__getattr__":
                tree.body.remove(node)
                seen_forwarder += _imported_modules(node, f"repro.{package}")
        named = _imported_modules(tree, f"repro.{package}")
        assert [m for m in named
                if any(m == b or m.startswith(b + ".") for b in banned)] \
            == [], path
    assert (seen_forwarder or None) == forwarded
