"""Executable documentation: run the curated modules' docstring examples.

Every module listed here ships `>>>` examples in its docstrings (the same
snippets docs/API.md quotes); this test keeps them from rotting.

CURATED_MODULES is the single source of truth for the CI docs job: the
workflow runs ``python -m tests.test_doctests --list`` and feeds the
printed file paths to ``pytest --doctest-modules`` — the job can never
drift from this list again (it used to hard-code a stale copy).
"""
import doctest
import importlib

import pytest

CURATED_MODULES = [
    "repro.core.graph",
    "repro.core.features",
    "repro.core.gnn",
    "repro.data.batching",
    "repro.data.fusion",
    "repro.data.segmentation",
    "repro.data.prefetch",
    "repro.data.store",
    "repro.kernels.segment_aggregate.ops",
    "repro.autotuner.tile_autotuner",
    "repro.quant.scale",
    "repro.quant.quantize",
    "repro.search.estimator",
    "repro.search.acquisition",
    "repro.flywheel.log",
    "repro.serving.cache",
    "repro.serving.coalescer",
    "repro.serving.server",
    "repro.serving.service",
]


def module_paths() -> list[str]:
    """Repo-relative source file of every curated module (pure text
    mapping — listing must not import jax-heavy modules)."""
    return ["src/" + m.replace(".", "/") + ".py" for m in CURATED_MODULES]


@pytest.mark.parametrize("module_name", CURATED_MODULES)
def test_module_doctests(module_name):
    module = importlib.import_module(module_name)
    result = doctest.testmod(module, verbose=False)
    assert result.attempted > 0, \
        f"{module_name} is curated but has no doctest examples"
    assert result.failed == 0


def test_curated_paths_exist():
    """The --list output (what CI consumes) must point at real files."""
    import os
    root = os.path.join(os.path.dirname(__file__), "..")
    for p in module_paths():
        assert os.path.exists(os.path.join(root, p)), f"missing {p}"


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--list", action="store_true",
                    help="print the curated source files, one per line "
                         "(consumed by the CI docs job)")
    args = ap.parse_args()
    if args.list:
        print("\n".join(module_paths()))
    else:
        ap.error("nothing to do (did you mean --list, or pytest?)")
