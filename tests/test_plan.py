"""The execution plan (``mpi_cuda_process_tpu/plan.py``): its place in the
layering, and the one derivation of the HBM budget's arguments."""

import ast
import dataclasses
import inspect
import os

import pytest

from mpi_cuda_process_tpu import plan
from mpi_cuda_process_tpu.config import RunConfig
from mpi_cuda_process_tpu.utils import budget

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "mpi_cuda_process_tpu"
CLI = f"{PACKAGE}.cli"
# the entry point may import the CLI; nothing else below it may
ALLOWED = {os.path.join(PACKAGE, "__main__.py")}
TABLES = {"_AUTO_FUSE_K", "_AUTO_FUSE_K_BF16", "_AUTO_FULL_K",
          "_AUTO_FUSE_KIND", "_RAW_WINS", "_RAW_ABOVE_CLIFF",
          "_CLIFF_CELLS"}


def _sources():
    for top in (PACKAGE, "benchmarks"):
        for root, _, files in os.walk(os.path.join(REPO, top)):
            for name in sorted(files):
                if name.endswith(".py"):
                    path = os.path.join(root, name)
                    yield os.path.relpath(path, REPO), ast.parse(
                        open(path).read(), filename=path)


def _cli_imports(rel, tree):
    """Line numbers of the imports of the CLI module in one source."""
    package = os.path.dirname(rel).split(os.sep)
    lines = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else []
            module = ".".join(base + ([node.module] if node.module else []))
            names = [module] + [f"{module}.{a.name}" for a in node.names]
        else:
            continue
        if any(n == CLI or n.startswith(CLI + ".") for n in names):
            lines.append(node.lineno)
    return lines


def _table_definitions(tree):
    out = []
    for node in ast.walk(tree):
        targets = node.targets if isinstance(node, ast.Assign) else \
            [node.target] if isinstance(node, ast.AnnAssign) else []
        out += [t.id for t in targets
                if isinstance(t, ast.Name) and t.id in TABLES]
    return out


def test_nothing_below_the_cli_imports_it_and_one_module_holds_the_tables():
    importers, seen, tables = [], [], {}
    for rel, tree in _sources():
        lines = _cli_imports(rel, tree)
        if lines:
            (seen if rel in ALLOWED else importers).append((rel, lines))
        for name in _table_definitions(tree):
            tables.setdefault(name, []).append(rel)
    # the walk resolves relative imports: it finds the entry point's own
    assert [rel for rel, _ in seen] == sorted(ALLOWED)
    assert importers == []
    plan_py = os.path.join(PACKAGE, "plan.py")
    assert tables == {name: [plan_py] for name in TABLES}


# Every config the auto-fuse tests of tests/test_cli.py hand the probe,
# upgraded as _probe_fuse upgrades it.
_FUSED = [
    (RunConfig(stencil=name, grid=(16, 16, 128), iters=8), {})
    for name in ("heat3d", "heat3d27", "wave3d")
] + [
    (RunConfig(stencil="heat3d", grid=(24, 32, 128), iters=8),
     {"fuse_kind": "stream"}),
    (RunConfig(stencil="heat3d", grid=(24, 32, 128), iters=8), {}),
    (RunConfig(stencil="life", grid=(64, 128), iters=16), {"fuse": 8}),
    (RunConfig(stencil="heat3d", grid=(1024, 1024, 1024), iters=8), {}),
    (RunConfig(stencil="wave3d", grid=(1024, 1024, 1024), iters=64,
               log_every=16), {}),
    (RunConfig(stencil="heat3d", grid=(1024, 1024, 1024), iters=64,
               log_every=16), {}),
]


@pytest.mark.parametrize("cfg,upgrade", _FUSED,
                         ids=[f"{c.stencil}-{'x'.join(map(str, c.grid))}"
                              f"-{u.get('fuse_kind', 'auto')}"
                              for c, u in _FUSED])
def test_fused_probe_budget_arguments_are_the_guards(cfg, upgrade):
    """The fused probe's budget question is asked with the arguments it
    always had: fuse, periodic and fuse_kind, the rest at their
    defaults, and the same estimate follows."""
    fused = dataclasses.replace(cfg, **{"fuse": 4, **upgrade})
    st = plan.make_cfg_stencil(fused)
    args = plan.budget_args(fused, st)
    defaults = {name: p.default for name, p in inspect.signature(
        budget.estimate_run_bytes).parameters.items()
        if p.default is not inspect.Parameter.empty}
    assert args == {**defaults, "fuse": fused.fuse,
                    "periodic": fused.periodic,
                    "fuse_kind": fused.fuse_kind}
    assert budget.estimate_run_bytes(st, fused.grid, **args) == \
        budget.estimate_run_bytes(st, fused.grid, fuse=fused.fuse,
                                  periodic=fused.periodic,
                                  fuse_kind=fused.fuse_kind)


def test_auto_path_names_the_table_decision():
    """mktable's bold pick and the auto-fuse rule read one table lookup."""
    assert plan.auto_path("heat3d", (1024, 1024, 1024)) == "fused4"
    assert plan.auto_path("wave3d", (1024, 1024, 1024), "float32") \
        == "fused4"
    assert plan.auto_path("heat3d4th", (512, 512, 512)) == "raw"
    assert plan.auto_path("heat3d4th", (256, 256, 256)) == "jnp"
    assert plan.auto_path("heat3d", (512, 512, 512), "bfloat16") == "raw"
    assert plan.auto_path("life", (4096, 4096)) == "jnp"
    cfg = RunConfig(stencil="heat3d", grid=(256, 256, 256), iters=8)
    assert plan.auto_fuse_k(cfg, "tpu") == 4
    assert plan.auto_fuse_k(cfg, "cpu") is None
    assert plan.auto_fuse_k(dataclasses.replace(cfg, iters=6), "tpu") \
        is None
