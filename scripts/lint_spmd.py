#!/usr/bin/env python
"""SPMD lint gate — CI face of ``chainermn_tpu.analysis``.

Exit-code contract:
0 = clean (modulo baseline), 1 = findings, 2 = inputs unusable.

Unlike ``python -m chainermn_tpu.analysis`` (which imports the full
package, jax included), this script loads the analysis package
STANDALONE via importlib: with ``--no-jaxpr`` the lint runs on any box
with a Python — no jax, no framework import — exactly like the perf
gate runs anywhere that can read JSON.

Usage::

    python scripts/lint_spmd.py chainermn_tpu/ examples/ scripts/
    python scripts/lint_spmd.py --no-jaxpr --json chainermn_tpu/
    python scripts/lint_spmd.py --fix-baseline chainermn_tpu/   # accept
    python scripts/lint_spmd.py --entry train.step chainermn_tpu/train.py
    #   ^ jaxpr checks on ONE registered entry point (fast iteration)
    python scripts/lint_spmd.py --no-jaxpr --rules concurrency chainermn_tpu/
    #   ^ the ISSUE 15 lock-discipline family alone (own baseline:
    #     .concurrency-baseline.json; docs/ANALYSIS.md)
"""

import importlib.util
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_REPO, "chainermn_tpu", "analysis")


def _load_analysis():
    """Load chainermn_tpu.analysis WITHOUT importing chainermn_tpu (whose
    __init__ pulls in jax).  The package uses only stdlib + relative
    imports at top level, so a synthetic package name works."""
    name = "_spmd_lint_analysis"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_PKG, "__init__.py"),
        submodule_search_locations=[_PKG])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if not any(a == "--no-jaxpr" for a in argv):
        # the jaxpr engine needs the real package (entry points import
        # chainermn_tpu); make it importable from the repo checkout
        sys.path.insert(0, _REPO)
    an = _load_analysis()
    from _spmd_lint_analysis.cli import main as cli_main  # noqa: F401
    assert an  # loaded above; the import line binds the submodule
    return cli_main(argv)


if __name__ == "__main__":
    sys.exit(main())
